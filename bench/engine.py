"""The system under test, built from the benchmark's files.

With the architecture's module (``bench/models/<arch>.py``), which maps
a configuration file onto the program's `ModelConfig`, this is the one
module that knows the program's constructors: it maps the engine keys
of ``bench/configs/<config>.json`` and ``bench/traffic/<mix>.json``
onto `FedConfig`/`CommConfig`, and builds `LMTask` and `FedEngine` as
the launcher does; the state is made on the device from the seed and
packed, and the round is `FedEngine.round_fn(donate=True)`.  A field
the program renames is re-pointed here, in a benchmark change.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax

#: engine keys of a configuration file -> `CommConfig` fields
COMM_KEYS = ("compressor", "downlink_compressor", "quant_block",
             "state_dtype", "moment_dtype", "hessian_dtype")
#: engine keys -> `FedConfig` fields
FED_KEYS = ("optimizer", "lr", "schedule", "total_rounds", "decay_frac",
            "beta1", "beta2", "rho", "eps", "weight_decay")


def fed_config(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    from repro.configs.base import CommConfig, FedConfig
    e = cfg["engine"]
    comm = CommConfig(use_pallas=e["comm_pallas"],
                      participation=traffic["participation"],
                      **{k: e[k] for k in COMM_KEYS})
    return FedConfig(num_clients=traffic["clients"],
                     local_iters=traffic["local_iters"], tau=traffic["tau"],
                     strategy=traffic["strategy"], use_pallas=e["use_pallas"],
                     comm=comm, **{k: e[k] for k in FED_KEYS})


@dataclasses.dataclass
class System:
    """The engine, its donated round and the packed state."""
    engine: Any
    round_fn: Any
    state: Any
    rows: int
    cols: int
    total: int


def build(cell, init_key) -> System:
    """The engine and state of a `harness.Cell` as the launcher builds
    them: `init` on the device from ``init_key``, then `pack_state`."""
    from repro.core.fed import FedEngine
    from repro.models import transformer as T
    engine = FedEngine(T.LMTask(cell.model.program_config(cell.cfg)),
                       fed_config(cell.cfg, cell.traffic))
    state = engine.pack_state(engine.init(init_key))
    spec = engine.runtime_for(state["params"]).spec
    return System(engine=engine, round_fn=engine.round_fn(donate=True),
                  state=state, rows=spec.rows, cols=spec.cols,
                  total=spec.total)


def avals(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def tokens_per_round(traffic) -> int:
    """Tokens of the local Sophia steps of one round (all clients)."""
    return (traffic["clients"] * traffic["local_iters"] * traffic["batch"]
            * traffic["seq"])

