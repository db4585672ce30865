"""One run of one cell: set-up, the measured window, the traced stretch,
and the output check.  Everything a cell is made of is found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix and
  lists the metrics it reports;
* ``bench/workloads/<cell>.json`` holds the limits of its output check;
* ``bench/configs/<config>.json`` and ``bench/traffic/<mix>.json`` hold
  the model, the engine and the traffic;
* ``bench/models/<arch>.py``, for the configuration's ``arch``, holds
  everything that knows the architecture (``bench/models/__init__.py``);
* ``bench/metrics/<metric>.py`` reads one per-layer metric.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, engine, models, reference, tracing, work
from bench.traffic.tokens import batch_pool

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")
#: rounds in the profiler's window of a ``--trace 1`` run
TRACE_ROUNDS = 3
#: distinct per-round batches a run cycles through
POOL = 8


def read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict[str, Any]
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    #: the architecture's module, ``bench/models/<arch>.py``
    model: Any


def cell_names(root: str = ROOT) -> List[str]:
    """The cells of ``<root>/BENCHMARK.json``, in its order."""
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    return [w["name"] for w in spec["workloads"]]


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    try:
        wl = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    conf = next(c for c in spec["configs"] if c["name"] == wl["config"])
    cfg = read_json(os.path.join(root, conf["file"]))
    traffic = read_json(os.path.join(root, "bench", "traffic",
                                     wl["traffic"] + ".json"))
    cell = read_json(os.path.join(root, "bench", "workloads",
                                  name + ".json"))
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in moved and _reports(m, name)]
    return Cell(name=name, workload=wl, cfg=cfg, traffic=traffic,
                limits=cell["limits"], end_to_end=e2e, per_layer=per_layer,
                model=models.load(cfg["arch"], root))


def keys(seed: int) -> Dict[str, Any]:
    """The run's keys, all from ``seed`` (any non-negative integer below
    2**64): init, data, rounds."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)
    return {n: jax.random.fold_in(base, i)
            for i, n in enumerate(("init", "data", "rounds"))}


def make_pool(cell: Cell, data_key) -> List[Dict[str, Any]]:
    t = cell.traffic
    stacked = batch_pool(data_key, POOL, t["clients"], t["batch"],
                         t["seq"], cell.cfg["vocab_size"])
    return [jax.tree.map(lambda a: a[i], stacked) for i in range(POOL)]


class CompileCounter:
    """Counts lowerings (each new program a jit traces) while on."""

    def __init__(self):
        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


class Program:
    """The system under test through one run: built and driven through
    the first `check.STEPS` rounds in `setup`, then run in `window`."""

    def __init__(self, cell: Cell, seed: int,
                 plant: Optional[Callable] = None):
        self.cell = cell
        self.k = keys(seed)
        self.wire = reference.wire_of(cell.model, cell.cfg)
        self.plant = plant
        self.round = 0
        self.readings: Dict[str, Any] = {}

    def round_key(self, r: int):
        return jax.random.fold_in(self.k["rounds"], r)

    def setup(self, pool):
        """Build, then run rounds 1..STEPS through the window's own
        compiled round and batches, reading the state as they go.  Ends
        with a collection, so that no buffer held by a reference cycle
        is freed, or kept, at random in the window."""
        self.pool = pool
        sysm = engine.build(self.cell, self.k["init"])
        w = self.wire
        if (sysm.rows, sysm.cols, sysm.total) != (w.rows, w.cols, w.total):
            raise ValueError(
                f"the program packs {sysm.total} coordinates as "
                f"{sysm.rows}x{sysm.cols}; the configuration file gives "
                f"{w.total} as {w.rows}x{w.cols}")
        self.sys = sysm
        if self.plant:
            self.round_fn, self.compiled = self.plant(sysm, w), None
        else:
            # the window's round, compiled once for this state and batch;
            # the per-layer metrics read this same executable
            self.compiled = sysm.round_fn.lower(
                engine.avals(sysm.state), pool[0],
                self.round_key(0)).compile()
            self.round_fn = self.compiled
        moved_sq = jax.jit(lambda a, b: w.leaf_sq(
            a.astype(jnp.float32) - b.astype(jnp.float32)))
        p0 = jax.device_get(sysm.state["params"])
        state, losses = sysm.state, []
        sysm.state = None
        for r in range(check.STEPS):
            state, met = self.round_fn(state, pool[r % POOL],
                                       self.round_key(r))
            losses.append(float(met["loss"]))
            if r == 0:
                self.readings["step"] = np.sqrt(np.asarray(moved_sq(
                    state["params"], jax.device_put(p0)), np.float64))
        self.readings["loss"] = losses
        self.readings["change"] = np.sqrt(np.asarray(moved_sq(
            state["params"], jax.device_put(p0)), np.float64))
        self.state = state
        self.round = check.STEPS
        gc.collect()

    def step(self):
        r = self.round
        self.state, met = self.round_fn(self.state, self.pool[r % POOL],
                                        self.round_key(r))
        self.round += 1
        return met["loss"]

    def window(self, seconds: float):
        """Rounds back to back for ``seconds``, one in flight behind the
        one being dispatched; ends when the state is ready.  Returns
        (rounds, elapsed seconds, losses).  Keeps in ``marks`` the
        seconds into the window at which each dispatch returned and the
        round before it was ready."""
        losses, self.marks = [], []
        t0 = time.perf_counter()
        while True:
            losses.append(self.step())
            sent = time.perf_counter() - t0
            if len(losses) > 1:
                losses[-2].block_until_ready()
            self.marks.append((sent, time.perf_counter() - t0))
            if self.marks[-1][1] >= seconds:
                break
        jax.block_until_ready(self.state)
        elapsed = time.perf_counter() - t0
        return len(losses), elapsed, [float(x) for x in losses]

    def slowest_round(self) -> str:
        """Where the window's host time went: the median and the longest
        interval between two rounds' completions, and the longest
        dispatch."""
        ready = [r for _, r in self.marks]
        gaps = [b - a for a, b in zip(ready, ready[1:])] or [0.0]
        sends = [s - r for (s, _), (_, r) in zip(self.marks[1:],
                                                 self.marks)] or [0.0]
        k = max(range(len(gaps)), key=gaps.__getitem__)
        return (f"round completions {float(np.median(gaps))!r} s apart, "
                f"longest {gaps[k]!r} s (ending with round {k} of the "
                f"window), longest dispatch {max(sends)!r} s")

    def traced(self, directory: str, rounds: int = TRACE_ROUNDS):
        """Trace ``rounds`` more rounds into ``directory``."""
        shutil.rmtree(directory, ignore_errors=True)
        first = self.round
        ann = jax.profiler.TraceAnnotation
        jax.profiler.start_trace(directory)
        try:
            with ann(tracing.WINDOW_SPAN):
                prev = None
                for _ in range(rounds):
                    r = self.round
                    with ann("bench.batch"):
                        batch, key = self.pool[r % POOL], self.round_key(r)
                    with ann("bench.dispatch"):
                        self.state, met = self.round_fn(self.state, batch,
                                                        key)
                    self.round += 1
                    if prev is not None:
                        with ann("bench.sync"):
                            prev.block_until_ready()
                    prev = met["loss"]
                with ann("bench.sync"):
                    jax.block_until_ready(self.state)
        finally:
            jax.profiler.stop_trace()
        return first

    def free(self):
        self.state = None
        self.sys = None
        self.round_fn = None
        self.compiled = None


def reference_readings(cell: Cell, seed: int, pool,
                       lower=None) -> Dict[str, Any]:
    """The reference's numbers for the first `check.STEPS` rounds; with
    ``lower``, those of the lower-precision control."""
    ref = reference.Reference(cell.model, cell.cfg, cell.traffic, lower)
    k = keys(seed)
    st = ref.init(k["init"])
    p0 = st["theta"]
    moved = jax.jit(lambda a, b: ref.wire.leaf_sq(
        a.astype(jnp.float32) - b.astype(jnp.float32)))
    out: Dict[str, Any] = {"loss": []}
    for r in range(check.STEPS):
        st, loss, gsq = ref.round(st, pool[r % POOL],
                                  jax.random.fold_in(k["rounds"], r), r)
        out["loss"].append(loss)
        if r == 0:
            out["step"] = np.sqrt(np.asarray(moved(st["theta"], p0),
                                             np.float64))
            out["gsq"] = np.asarray(gsq, np.float64)
    out["change"] = np.sqrt(np.asarray(moved(st["theta"], p0), np.float64))
    return out


# --------------------------------------------------------- per-layer
@dataclasses.dataclass
class TraceContext:
    """What a per-layer reader may read from a ``--trace 1`` run."""
    cell: Cell
    reduction: Optional[tracing.Reduction]
    peaks: Dict[str, float]
    flops: float
    kernels: List[Dict[str, Any]]
    memory: Any
    rounds: int
    #: the text of the compiled round the traced rounds ran
    hlo_text: Optional[str] = None
    _phase_ns: Dict[Any, Any] = dataclasses.field(default_factory=dict,
                                                  repr=False)

    def phase_ms(self, label: str,
                 scopes: Dict[str, str] = tracing.PHASE_SCOPES
                 ) -> Optional[float]:
        """Device self time per traced round, in ms, of the scope
        ``scopes[label]``, or with ``label`` `tracing.REST` of the busy
        time outside every scope of ``scopes`` (label -> scope name;
        by default the round's phases).  None where the trace, the
        module or the scopes are missing.  Read once per set of scopes
        and kept for the other readers."""
        key = tuple(scopes.items())
        if key not in self._phase_ns:
            self._phase_ns[key] = None if self.hlo_text is None else (
                tracing.phase_ns(self.reduction,
                                 tracing.hlo_phases(self.hlo_text, scopes),
                                 tuple(scopes)))
        per = self._phase_ns[key]
        if per is None:
            return None
        return per[label] / self.reduction.devices / self.rounds * 1e-6

    def kernel_roofline(self, family: str) -> Optional[float]:
        """Bytes the family's launches move, at the HBM peak, over
        their summed device time; None where none ran."""
        red = self.reduction
        if red is None:
            return None
        byte_s, dev_ns = 0.0, 0.0
        for k in self.kernels:
            if k["family"] != family or k["name"] not in red.op_ns:
                continue
            byte_s += red.op_count[k["name"]] * k["bytes"]
            dev_ns += red.op_ns[k["name"]]
        if dev_ns <= 0:
            return None
        return 100.0 * byte_s / self.peaks["hbm_bytes_per_s"] / (
            dev_ns * 1e-9)


def read_metric(name: str, ctx: TraceContext):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def breakdown(red: tracing.Reduction) -> Dict[str, List]:
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, ns * 1e-9 / red.devices] for n, ns in ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in red.gaps[:10]]}


def trace_context(cell: Cell, prog: Program, first: int, directory: str,
                  peaks) -> TraceContext:
    red = tracing.reduce(tracing.load(directory))
    compiled = prog.compiled
    text = compiled.as_text()
    per_token = cell.model.train_flops_per_token(cell.cfg,
                                                 cell.traffic["seq"])
    return TraceContext(
        cell=cell, reduction=red, peaks=peaks,
        flops=work.round_flops(per_token, cell.traffic, TRACE_ROUNDS, first),
        kernels=work.kernel_calls(text),
        memory=compiled.memory_analysis(), rounds=TRACE_ROUNDS,
        hlo_text=text)
