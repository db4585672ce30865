"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program's ``src/``.  One process: it builds the cell's engine
and state on the device from the seed, drives the first rounds while
reading the state for the output check (set-up, with compilation and
warm-up), runs rounds back to back for ``--seconds``, and with
``--trace 1`` traces a few more rounds and reduces the trace to the
cell's per-layer metrics.  It then frees the program's state, runs the
float32 reference over the first rounds, and prints each compared
number beside its limit on standard error, then the JSON result line on
standard output.  Without a TPU, with fewer chips than the cell asks
for, or without the program's sources, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a non-negative integer")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no program sources under {os.path.join(ROOT, 'src')}")
    if not os.path.isfile(os.path.join(ROOT, "BENCHMARK.json")):
        fail(f"no BENCHMARK.json in {ROOT}")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax
    # the checkout's own compilation cache, at a fixed path, small
    # programs included; set here so that it wins over the environment
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import check, engine, harness, work
    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found (JAX reports {devs[0].platform!r})")
    if len(devs) < cell.workload["chips"]:
        fail(f"{cell.name} needs {cell.workload['chips']} chips, "
             f"JAX sees {len(devs)}")
    dev = devs[0]
    peaks = work.peaks(dev.device_kind)

    counter = harness.CompileCounter()
    pool = harness.make_pool(cell, harness.keys(args.seed)["data"])
    prog = harness.Program(cell, args.seed)
    prog.setup(pool)
    jax.block_until_ready(prog.state)
    setup_s = time.perf_counter() - T_START

    counter.on = True
    rounds, elapsed, losses = prog.window(args.seconds)
    counter.on = False
    failed = sum(1 for x in losses if x != x or abs(x) == float("inf"))

    out_metrics, device, extra = {}, {}, {}
    if args.trace:
        tdir = os.path.join(harness.OUT, "trace")
        first = prog.traced(tdir)
        ctx = harness.trace_context(cell, prog, first, tdir, peaks)
        for m in cell.per_layer:
            v = harness.read_metric(m["name"], ctx)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if ctx.reduction is not None:
            device["busy_s"] = ctx.reduction.busy_ns * 1e-9
            device["window_s"] = ctx.reduction.window_ns * 1e-9
            extra["breakdown"] = harness.breakdown(ctx.reduction)
    # the TPU runtime holds loaded programs' temporaries in memory it
    # reserves apart from the buffers in use: the window's footprint is
    # the two together.  Set-up's transients (the state built, then
    # packed) show only in the allocator's peak, reported apart.  A
    # collection first, so that buffers held by reference cycles are
    # freed in every run and not only where the collector happened to run
    gc.collect()
    stats = dev.memory_stats() or {}
    footprint = (int(stats.get("bytes_in_use", 0))
                 + int(stats.get("bytes_reserved", 0)))
    peak = max(int(stats.get("peak_bytes_in_use", 0)), footprint)
    if not args.trace:
        values = {
            "train_tokens_per_s": (
                rounds * engine.tokens_per_round(cell.traffic) / elapsed),
            "peak_hbm_gib": footprint / 2 ** 30,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            out_metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    print(f"bench: {cell.name} seed {args.seed} setup_s {setup_s!r} "
          f"window {rounds} rounds in {elapsed!r} s, "
          f"{counter.count} compilations in the window; "
          f"{prog.slowest_round()}; "
          f"{len(jax.live_arrays())} live arrays; memory {stats}",
          file=sys.stderr, flush=True)

    readings = prog.readings
    prog.free()
    ref = harness.reference_readings(cell, args.seed, pool)
    found = check.gaps(readings, ref)
    correct = check.verdict(found, cell.limits) and failed == 0
    for line in check.lines(found, cell.limits):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    result = {
        "correct": bool(correct),
        "attempted": rounds,
        "failed": failed,
        "metrics": out_metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": peak,
                   **device},
        **extra,
        "check": {k: {"value": found[k] if math.isfinite(found[k])
                      else None, "limit": cell.limits[k]}
                  for k in check.NUMBERS},
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
