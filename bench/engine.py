"""The system under test, built from the benchmark's files.

This is the one module that knows the program's constructors: it maps
the keys of ``bench/configs/<config>.json`` and ``bench/traffic/<mix>.json``
onto `ModelConfig` (the architecture's preset, cut with
``with_depth``, then every mapped key of the file applied), `LMTask`,
`FedConfig`/`CommConfig`, and `FedEngine`, as the launcher does; the
state is made on the device from the seed and packed, and the round is
`FedEngine.round_fn(donate=True)`.  A field the program renames is
re-pointed here, in a benchmark change.
"""
from __future__ import annotations

import dataclasses
import inspect
import math
from typing import Any, Dict

import jax

#: configuration-file key -> `ModelConfig` field
MODEL_KEYS = {
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "scale_emb": "scale_emb",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}
#: engine keys of a configuration file -> `CommConfig` fields
COMM_KEYS = ("compressor", "downlink_compressor", "quant_block",
             "state_dtype", "moment_dtype", "hessian_dtype")
#: engine keys -> `FedConfig` fields
FED_KEYS = ("optimizer", "lr", "schedule", "total_rounds", "decay_frac",
            "beta1", "beta2", "rho", "eps", "weight_decay")


def model_config(cfg: Dict[str, Any]):
    """The program's `ModelConfig` for a configuration file."""
    from repro import configs
    mc = configs.get_model_config(cfg["arch"]).with_depth(
        cfg["num_hidden_layers"])
    if mc.num_layers != cfg["num_hidden_layers"]:
        raise ValueError(f"{cfg['name']}: depth {cfg['num_hidden_layers']} "
                         f"is not a whole number of layer periods")
    over = {f: cfg[k] for k, f in MODEL_KEYS.items()}
    over["residual_scale"] = cfg["scale_depth"] / math.sqrt(
        cfg["assumed"]["residual_scale_layers"])
    if cfg["hidden_act"] != "silu" or mc.ffn_kind != "swiglu":
        raise ValueError(f"{cfg['name']}: the program's {cfg['arch']} is "
                         f"{mc.ffn_kind}, the file says {cfg['hidden_act']}")
    if cfg["hidden_size"] != cfg["dim_model_base"]:
        raise ValueError("the program does not divide the logits by "
                         "hidden_size / dim_model_base")
    if norm_eps() != cfg["rms_norm_eps"]:
        raise ValueError(f"{cfg['name']}: the program's RMSNorm eps is "
                         f"{norm_eps()}, the file says {cfg['rms_norm_eps']}")
    return dataclasses.replace(mc, **over)


def norm_eps() -> float:
    """The RMSNorm epsilon the program runs (it has no setting for it)."""
    from repro.models import layers
    return inspect.signature(layers.rms_norm).parameters["eps"].default


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


def build(cfg, traffic, init_key) -> System:
    """Engine and state as the launcher builds them: `init` on the
    device from ``init_key``, then `pack_state`."""
    from repro.core.fed import FedEngine
    from repro.models import transformer as T
    engine = FedEngine(T.LMTask(model_config(cfg)),
                       fed_config(cfg, traffic))
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

