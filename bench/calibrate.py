"""Readings that set the output check's limits, for one cell, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 \
        [--controls 3] [--faults half_batch,altered] \
        [--out <jsonl>]

One process.  For each seed it runs the program's set-up (the compiled
round through the first rounds, as a benchmark run does), then the
float32 reference, and prints the compared numbers; for the first
``--controls`` seeds also the lower-precision control (the reference
with the model stored in float8 e4m3 and every matrix product reading
e4m3 operands) and each planted fault of ``bench/faults.py``.  Lower readings are the
largest of the program's; upper readings the smallest of the control's
and of the faults' (see PERF.md).  Seeds are ``--first-seed`` + i.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", default="half_batch,altered")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import check, faults, harness
    cell = harness.load_cell(args.workload)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i in range(args.seeds):
        seed = args.first_seed + i
        pool = harness.make_pool(cell, harness.keys(seed)["data"])
        runs = [("program", None, None)]
        if i < args.controls:
            runs.append(("control", None, "lower"))
            runs += [(f, faults.PLANTS[f], None)
                     for f in filter(None, args.faults.split(","))]
        # the program before the reference, as in a benchmark run
        got = {}
        for kind, plant, lower in runs:
            t0 = time.perf_counter()
            if lower:
                got[kind] = harness.reference_readings(cell, seed, pool,
                                                       "float8_e4m3fn")
            else:
                prog = harness.Program(cell, seed, plant=plant)
                prog.setup(pool)
                got[kind] = prog.readings
                prog.free()
                del prog
            got[kind]["seconds"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = harness.reference_readings(cell, seed, pool)
        t_ref = time.perf_counter() - t0
        g = np.sqrt(ref["gsq"])
        for kind, _, _ in runs:
            emit({"cell": cell.name, "seed": seed, "kind": kind,
                  "gaps": check.gaps(got[kind], ref),
                  "loss": list(got[kind]["loss"]),
                  "ref_loss": list(ref["loss"]),
                  "seconds": got[kind]["seconds"],
                  "ref_seconds": t_ref,
                  "flat_leaves": int(np.sum(g < check.FLAT_LEAF
                                            * np.median(g))),
                  "peak_bytes": (jax.devices()[0].memory_stats() or {}
                                 ).get("peak_bytes_in_use")})
    if out:
        out.close()


if __name__ == "__main__":
    main()
