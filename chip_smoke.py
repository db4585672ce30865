#!/usr/bin/env python3
"""Chip smoke test: the federated Fed-Sophia path on one TPU, end to end.

    python chip_smoke.py [--seed N]

Run it from the root of a checkout on a machine with a TPU.  Everything
runs in this one process, because a chip belongs to one process at a
time.  Where JAX finds no TPU, or the checkout's ``src/`` is missing,
it exits non-zero and prints no result.  Phases:

a. device: a TPU, with the Pallas kernels compiled through Mosaic
   rather than interpreted;
b. kernels: every family of `repro.kernels.KERNELS` once at the packed
   wire geometry of phase c's model, for each resident dtype phase c
   stores, against its `repro.kernels.ref` oracle;
c. sync rounds: minicpm-2b at its published widths, depth cut to 2
   layers, through `repro.launch.train.run` with the kernels on, then
   its kernels-off twin; the losses and the final state must agree;
d. scheduler events: semisync aggregation (the `stale_accum` kernel)
   and trimmed-mean aggregation (`robust_agg`), kernels on and off.

Every phase prints its own lines; a failed check raises, so the script
never exits 0 past a failure.  The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The times
it prints are facts about one run, not a benchmark.  Data comes from
``--seed`` through `repro.data.synthetic`; nothing is read from disk
but the checkout's own files.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

#: phase c: the model, its cut and the round, as launcher flags.  One
#: client, because the engine computes a round in fp32 wire buffers of
#: 1.6 GB each at this size: one client's round needs 9.2 GiB of
#: temporaries beside 2.3 GiB of resident state (AOT memory analysis
#: for a v5e), and a second client does not fit the chip's 16 GB.
ROUND_ARGS = [
    "--arch", "minicpm-2b", "--num-layers", "2", "--clients", "1",
    "--batch", "1", "--seq", "2048", "--rounds", "3",
    "--compressor", "int8", "--downlink-compressor", "int8",
    "--state-dtype", "bfloat16", "--moment-dtype", "float8_e4m3fn",
    "--hessian-dtype", "float8_e5m2",
]
#: phase d: the scheduler holds every in-flight arrival's fp32 wire
#: (1.6 GB each at phase c's size) and stacks them to aggregate, so
#: the four arrivals a trim of one per side needs cannot fit one chip
#: at full width; the events run at the launcher's reduced widths, and
#: phase b compiles both kernels at the full wire geometry
SCHED_ARGS = [
    "--arch", "minicpm-2b", "--reduced", "--clients", "4", "--batch", "2",
    "--seq", "128", "--rounds", "4", "--schedule", "semisync",
    "--latency-profile", "straggler",
    "--compressor", "int8", "--downlink-compressor", "int8",
    "--state-dtype", "bfloat16", "--moment-dtype", "float8_e4m3fn",
    "--hessian-dtype", "float8_e5m2",
]
SEMISYNC_ARGS = ["--buffer-size", "2"]
TRIMMED_ARGS = ["--buffer-size", "4", "--aggregator", "trimmed_mean",
                "--trim-fraction", "0.25"]
KERNEL_ARGS = ["--use-pallas", "--comm-pallas"]

#: int8 wire codes (the phase c compressor)
QMAX = 127
#: scheduler-kernel arrivals; trim 1 per side needs K >= 3
K = 4
#: row block of the oracle comparison: the K-stack refs hold several
#: fp32 copies of their block, which would not fit at the full geometry
ROW_BLOCK = 1 << 16

#: one-ulp-class band per stored dtype, as in
#: tests/test_kernel_conformance.py: the narrow formats round each
#: output once (2^-mantissa bits); fp32 runs the same fp32 ops, but
#: Mosaic and XLA may round a division or a contracted add differently
#: in the last place
BAND = {"float32": 1e-6, "bfloat16": 2.0 ** -8,
        "float8_e4m3fn": 2.0 ** -3, "float8_e5m2": 2.0 ** -2}
#: a quantized output may move by one whole quantization step where
#: x/s + u lies within one fp32 ulp of an integer, so that a last-place
#: difference in the division flips floor(): at most 2 ulps of the
#: largest code, 2 * 2^-23 * (QMAX + 1) = 2^-15 of the coordinates
FLIP_FRACTION = 2.0 * 2.0 ** -23 * (QMAX + 1)
#: sync rounds and scheduler events, kernels on vs off: per-event loss
#: agreement, relative; the two programs differ only in the rounding of
#: the fused elementwise passes and the rare flips above
LOSS_RTOL = 1e-3
#: ... and per state buffer ||on - off|| / ||off - initial||: the flips
#: and narrow-store roundings touch a vanishing share of coordinates,
#: while dropping Sophia's clip from the kernel makes the losses NaN and
#: dropping its h-EMA update leaves h at 0 (ratio 1 on h, 0.094 on the
#: params in a CPU run of the reduced model)
STATE_RTOL = 5e-2


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def preflight():
    """The checkout and the chip, before any phase prints a result."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no repro package under {SRC}; run this "
                 "script from the root of a checkout")
    sys.path.insert(0, SRC)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX reports "
                 f"'{dev.platform}'); this test runs only on a TPU")
    return jax


def peak(jax):
    """The process's peak device memory so far, beside what the chip
    lets it allocate."""
    stats = jax.devices()[0].memory_stats() or {}
    gib = {k: stats.get(k, 0) / 2 ** 30
           for k in ("peak_bytes_in_use", "bytes_limit")}
    return (f"peak_bytes_in_use={gib['peak_bytes_in_use']:.2f} GiB of "
            f"bytes_limit={gib['bytes_limit']:.2f} GiB")


# ------------------------------------------------------------ phase a
def phase_a(jax, cache_dir):
    import repro.kernels
    check(repro.kernels.INTERPRET is False,
          "repro.kernels.INTERPRET is not False on a TPU")
    dev = jax.devices()[0]
    print(f"[a] device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} kernels=compiled "
          f"compile_cache={cache_dir}", flush=True)
    return dev


# ------------------------------------------------------------ phase b
def kernel_cases(jax, jnp, R, C):
    """name -> (make inputs(key, dtype), kernel fn, ref fn, output
    kinds): each input set is one client's arrays (or K arrivals),
    built on the device; an output kind is "quant" where a one-step
    quantizer flip is allowed (with the per-row step) or None.  Inputs
    follow tests/test_kernel_conformance.py: state in the resident
    dtype, gradients, noise and scales fp32.

    One client's arrays are made (R, C) and stacked to (1, R, C) inside
    the kernel's program, a bitcast there: a (1, R, C) fp32 array made
    in a program of its own takes the TPU's default layout for it,
    (1, 128) tiles, and every launch would relayout it into the (8, 128)
    tiles the kernel reads, a 1.5 GB copy per operand at this size."""
    from repro.kernels import quantize as q
    from repro.kernels import ref
    from repro.kernels.robust_agg import robust_agg_flat
    from repro.kernels.sophia_update import sophia_update_batched
    from repro.kernels.stale_accum import stale_accum_flat
    f32 = jnp.float32
    hp = dict(beta1=0.9, beta2=0.95, rho=0.04, eps=1e-12,
              weight_decay=1e-4)

    def nrm(k, shape, dt, s=1.0):
        return (s * jax.random.normal(k, shape, f32)).astype(dt)

    def scales(d):
        return jnp.max(jnp.abs(d), axis=-1, keepdims=True) / QMAX

    def one(i, *names):
        """The named arrays of ``i`` as one client's stacks."""
        return [i[n][None] for n in names]

    @functools.partial(jax.jit, static_argnums=(1,))
    def stack(key, dt):
        ks = jax.random.split(key, 4)
        x = nrm(ks[0], (R, C), dt)
        return dict(x=x, x_scale=scales(x.astype(f32)),
                    noise=jax.random.uniform(ks[1], (R, C), f32),
                    per_client=jnp.full((1,), 0.7, f32),
                    thr=jnp.full((1,), 0.8, f32))

    @functools.partial(jax.jit, static_argnums=(1,))
    def delta_code(key, dt):
        ks = jax.random.split(key, 4)
        theta = nrm(ks[0], (R, C), dt)
        rep = nrm(ks[1], (R, C), dt)
        ef = nrm(ks[2], (R, C), dt, 0.01)
        d = theta.astype(f32) - rep.astype(f32) + ef.astype(f32)
        return dict(theta=theta, rep=rep, ef=ef, s=scales(d),
                    noise=jax.random.uniform(ks[3], (R, C), f32))

    @functools.partial(jax.jit, static_argnums=(1,))
    def sophia(key, dt):
        ks = jax.random.split(key, 5)
        # theta is fp32 in the engine's local loop; m and h resident
        return dict(t=nrm(ks[0], (R, C), f32),
                    m=nrm(ks[1], (R, C), dt, 0.1),
                    h=jnp.abs(nrm(ks[2], (R, C), dt, 0.01)),
                    g=nrm(ks[3], (R, C), f32, 0.5),
                    hh=jnp.abs(nrm(ks[4], (R, C), f32, 0.02)))

    @functools.partial(jax.jit, static_argnums=(1,))
    def arrivals(key, dt):
        return dict(w=nrm(key, (K, R, C), dt, 10.0),
                    wt=jnp.linspace(0.5, 2.0, K, dtype=f32),
                    sc=jnp.linspace(1.0, 0.25, K, dtype=f32))

    qm = dict(qmax=QMAX)
    code = ("theta", "rep", "ef", "noise", "s")
    return {
        "quant_roundtrip": (
            stack, lambda i: q.quant_roundtrip_batched(
                *one(i, "x", "noise", "x_scale"), **qm),
            lambda i: ref.quant_roundtrip_ref(
                *one(i, "x", "noise", "x_scale"), **qm),
            [("quant", "x_scale")]),
        "broadcast_roundtrip": (
            delta_code, lambda i: q.broadcast_roundtrip_batched(
                i["theta"], *one(i, *code[1:]), **qm),
            lambda i: ref.broadcast_roundtrip_ref(*one(i, *code), **qm),
            [("quant", "s"), ("quant", "s")]),
        "uplink_roundtrip": (
            delta_code, lambda i: q.uplink_roundtrip_batched(
                *one(i, *code), **qm),
            lambda i: ref.uplink_roundtrip_ref(*one(i, *code), **qm),
            [("quant", "s"), ("quant", "s")]),
        "sign_roundtrip": (
            stack, lambda i: q.sign_roundtrip_batched(
                *one(i, "x"), i["per_client"]),
            lambda i: ref.sign_roundtrip_ref(*one(i, "x"),
                                             i["per_client"]),
            [None]),
        "topk_threshold": (
            stack, lambda i: q.topk_threshold_batched(*one(i, "x"),
                                                      i["thr"]),
            lambda i: ref.topk_threshold_ref(*one(i, "x"), i["thr"]),
            [None]),
        "sophia_update": (
            sophia, lambda i: sophia_update_batched(
                *one(i, "t", "m", "h", "g", "hh"), True, 1e-3, **hp),
            lambda i: ref.sophia_update_ref(
                *one(i, "t", "m", "h", "g", "hh"), 1.0, lr=1e-3, **hp),
            [None, None, None]),
        "stale_accum": (
            arrivals, lambda i: stale_accum_flat(
                i["w"], i["wt"], 1.0 / jnp.sum(i["wt"])),
            lambda i: ref.stale_accum_ref(
                i["w"], i["wt"], 1.0 / jnp.sum(i["wt"])),
            ["stale"]),
        "robust_agg": (
            arrivals, lambda i: robust_agg_flat(
                i["w"], i["wt"], i["sc"], trim=1, normalize=True),
            lambda i: ref.robust_agg_ref(
                i["w"], i["wt"], i["sc"], trim=1, normalize=True),
            ["robust"]),
    }


def rows_2d(jax, tree):
    """Every output of ``tree`` as an (R, C) array: one client's
    (1, R, C) stack loses its client axis (a bitcast on a TPU)."""
    return [o.reshape(o.shape[-2:]) for o in jax.tree.leaves(tree)]


def block_gap(jax, jnp):
    """jitted (out, ref, kind, inputs) -> (worst err / limit, count over
    the limit, count over the limit plus one quantization step) for
    one row block."""
    f32 = jnp.float32

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def gap(out, ref, kind, band, inputs):
        a, b = out.astype(f32), ref.astype(f32)
        err = jnp.abs(a - b)
        step = jnp.zeros((), f32)
        if kind == "stale":
            # K products summed: contraction into FMAs on either side,
            # the conformance suite's band
            lim = 1e-6 + 1e-5 * jnp.abs(b)
        elif kind == "robust":
            # one fp32 rounding per add of the K survivors, over the
            # surviving weight (tests/test_robust.py)
            w = inputs["wt"] * inputs["sc"]
            terms = jnp.abs(inputs["w"].astype(f32)) * w[:, None, None]
            lim = (K * jnp.finfo(f32).eps * jnp.sum(terms, axis=0)
                   / ((K - 2) * jnp.min(inputs["wt"])))
        else:
            lim = band + band * jnp.abs(b)
            if kind is not None:
                step = inputs[kind[1]]
        return (jnp.max(err / lim), jnp.sum(err > lim),
                jnp.sum(err > lim + step))

    return gap


def rows_of(tree, R, lo, hi):
    """Row block [lo, hi) of every array with a row axis of length R."""
    import jax
    return jax.tree.map(
        lambda a: a[..., lo:hi, :] if a.ndim >= 2 and a.shape[-2] == R
        else a, tree)


def check_kernel(jax, jnp, gap, name, case, key, dt, R):
    """Run one kernel family at one resident dtype and compare it with
    its oracle, row block by row block.  Its arrays die on return, so
    the next case starts from an empty chip."""
    import numpy as np
    make, kern, oracle, kinds = case
    dt = jnp.dtype(dt)
    inputs = make(key, dt)
    t0 = time.perf_counter()
    outs = jax.block_until_ready(jax.jit(lambda i: rows_2d(jax, kern(i)))(
        inputs))
    first_s = time.perf_counter() - t0
    ref_fn = jax.jit(lambda i: rows_2d(jax, oracle(i)))
    worst = 0.0
    over = np.zeros(len(outs), np.int64)
    beyond = np.zeros(len(outs), np.int64)
    for lo in range(0, R, ROW_BLOCK):
        hi = min(R, lo + ROW_BLOCK)
        blk = rows_of(inputs, R, lo, hi)
        refs = ref_fn(blk)
        for n, (o, r, kind) in enumerate(zip(outs, refs, kinds)):
            w, c_over, c_beyond = gap(
                rows_of(o, R, lo, hi), r, kind,
                BAND[jnp.dtype(o.dtype).name], blk)
            worst = max(worst, float(w))
            over[n] += int(c_over)
            beyond[n] += int(c_beyond)
    size = math.prod(outs[0].shape)
    allowed = [int(FLIP_FRACTION * size)
               if isinstance(k, tuple) else 0 for k in kinds]
    print(f"[b] {name:19s} {dt.name:13s} first_call_s={first_s:.3f} "
          f"worst_err/limit={worst:.3g} "
          f"over_limit={over.tolist()} (allowed {allowed}) "
          f"over_limit+step={beyond.tolist()}", flush=True)
    check(not beyond.any() and (over <= allowed).all(),
          f"{name} at {dt.name} disagrees with its oracle")


def phase_b(jax, jnp, seed, spec, dtypes):
    R, C = spec.rows, spec.cols
    cases = kernel_cases(jax, jnp, R, C)
    gap = block_gap(jax, jnp)
    from repro.kernels import KERNELS
    check(sorted(cases) == sorted(KERNELS),
          f"phase b covers {sorted(cases)}, registry has {KERNELS}")
    key = jax.random.PRNGKey(seed)
    print(f"[b] wire geometry {R}x{C} ({spec.total:,} coords), "
          f"resident dtypes {', '.join(dtypes)}", flush=True)
    for dt in dtypes:
        for name in KERNELS:
            check_kernel(jax, jnp, gap, name, cases[name],
                         jax.random.fold_in(key, KERNELS.index(name)), dt,
                         R)
    print(f"[b] ok: {len(KERNELS)} kernel families x {len(dtypes)} "
          f"dtypes agree with their oracles; {peak(jax)}", flush=True)


# ---------------------------------------------------- phases c and d
@contextlib.contextmanager
def counting(module, name, counts):
    """Count trace-time calls of ``module.name`` (callers import it
    inside their jitted bodies, so a call means the kernel is in the
    compiled program)."""
    fn = getattr(module, name)

    def wrapper(*a, **kw):
        counts[name] = counts.get(name, 0) + 1
        return fn(*a, **kw)

    setattr(module, name, wrapper)
    try:
        yield counts
    finally:
        setattr(module, name, fn)


def launch(jax, argv):
    """One launcher run; returns it with its final state on the host
    (the device holds one run's state at a time)."""
    from repro.launch import train
    t0 = time.perf_counter()
    run = train.run(train.parse_args(argv))
    wall = time.perf_counter() - t0
    state = jax.device_get(run.state)
    # the final state leaves the chip before the initial one is made
    # again: making it is the run's largest transient (up to 9.7 GiB at
    # phase c's size), larger than the round's own
    run.state = None
    init = jax.device_get(run.engine.pack_state(run.engine.init(run.key)))
    return run, state, init, wall


def compare(jax, name, on, off, on_state, off_state, init):
    """Kernels-on vs kernels-off: per-event losses and every state
    buffer's difference relative to its change from the initial
    state."""
    import numpy as np
    losses = np.asarray(on.losses, np.float64)
    ref = np.asarray(off.losses, np.float64)
    check(np.isfinite(losses).all() and np.isfinite(ref).all(),
          f"{name}: non-finite losses {losses} / {ref}")
    loss_gap = float(np.max(np.abs(losses - ref) / np.abs(ref)))
    print(f"[{name}] losses on={losses.tolist()} off={ref.tolist()} "
          f"max_rel_gap={loss_gap:.3g} (limit {LOSS_RTOL:g})", flush=True)
    check(loss_gap <= LOSS_RTOL, f"{name}: losses disagree")
    flat_on = jax.tree_util.tree_flatten_with_path(on_state)[0]
    flat_off = jax.tree.leaves(off_state)
    flat_init = jax.tree.leaves(init)
    worst = 0.0
    for (path, a), b, b0 in zip(flat_on, flat_off, flat_init):
        a, b, b0 = (np.asarray(x).astype(np.float32).ravel()
                    for x in (a, b, b0))
        if a.size == 1:
            check(a[0] == b[0], f"{name}: {jax.tree_util.keystr(path)} "
                  f"{a[0]} != {b[0]}")
            continue
        check(np.isfinite(a).all(), f"{name}: non-finite state")
        moved = float(np.sqrt(np.dot(b - b0, b - b0)))
        diff = float(np.sqrt(np.dot(a - b, a - b)))
        rel = diff / moved if moved else (0.0 if diff == 0 else math.inf)
        worst = max(worst, rel)
        print(f"[{name}] state {jax.tree_util.keystr(path)} "
              f"{a.size:,} coords |on-off|/|off-init|={rel:.3g}",
              flush=True)
    check(worst <= STATE_RTOL,
          f"{name}: final state disagrees ({worst:.3g} > {STATE_RTOL:g})")
    return loss_gap, worst


def phase_c(jax, seed):
    import repro.kernels.sophia_update as ksophia
    argv = ROUND_ARGS + ["--seed", str(seed)]
    counts = {}
    with counting(ksophia, "sophia_update_batched", counts):
        on, on_state, init, on_wall = launch(jax, argv + KERNEL_ARGS)
    avals = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         on_state)
    hlo = on.round_fn.lower(avals, on.make_batches(0),
                            jax.random.fold_in(on.key, 0)).as_text()
    n_calls = hlo.count("tpu_custom_call")
    print(f"[c] kernels on: first_round_s={on.seconds[0]:.2f} (compile "
          f"included) steady_s_per_round="
          f"{sum(on.seconds[1:]) / len(on.seconds[1:]):.3f} "
          f"run_s={on_wall:.1f} tpu_custom_call={n_calls} "
          f"sophia_kernel_traced={counts.get('sophia_update_batched', 0)}"
          f" {peak(jax)}", flush=True)
    check(n_calls > 0 and counts.get("sophia_update_batched"),
          "the compiled round holds no Pallas kernel")
    off, off_state, _, off_wall = launch(jax, argv)
    print(f"[c] kernels off: first_round_s={off.seconds[0]:.2f} "
          f"steady_s_per_round="
          f"{sum(off.seconds[1:]) / len(off.seconds[1:]):.3f} "
          f"run_s={off_wall:.1f} {peak(jax)}", flush=True)
    loss_gap, state_gap = compare(jax, "c", on, off, on_state, off_state,
                                  init)
    print(f"[c] ok: 3 rounds of minicpm-2b (2 layers, full width) agree "
          f"with the kernels-off twin (loss {loss_gap:.3g}, state "
          f"{state_gap:.3g})", flush=True)


def phase_d(jax, seed):
    import repro.kernels.robust_agg as krobust
    import repro.kernels.stale_accum as kstale
    for label, extra, module, kernel in (
            ("semisync", SEMISYNC_ARGS, kstale, "stale_accum_flat"),
            ("trimmed_mean", TRIMMED_ARGS, krobust, "robust_agg_flat")):
        argv = SCHED_ARGS + extra + ["--seed", str(seed)]
        counts = {}
        with counting(module, kernel, counts):
            on, on_state, init, on_wall = launch(jax, argv + KERNEL_ARGS)
        print(f"[d] {label} kernels on: {len(on.losses)} events "
              f"run_s={on_wall:.1f} {kernel}_traced="
              f"{counts.get(kernel, 0)}", flush=True)
        check(len(on.losses) == 4 and counts.get(kernel),
              f"{label}: the events did not run through {kernel}")
        off, off_state, _, off_wall = launch(jax, argv)
        compare(jax, "d", on, off, on_state, off_state, init)
        print(f"[d] ok: {label} events agree with the kernels-off twin "
              f"(run_s on={on_wall:.1f} off={off_wall:.1f})", flush=True)
    print(f"[d] {peak(jax)}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    jax = preflight()
    import jax.numpy as jnp
    from repro.configs.base import FedConfig
    from repro.core.fed import FedEngine
    from repro.launch import train
    from repro.models import transformer as T

    cache_dir = train.use_compile_cache()
    dev = phase_a(jax, cache_dir)

    # phase c's packed wire layout, without allocating the model
    round_args = train.parse_args(ROUND_ARGS)
    cfg = train.model_config(round_args)
    params = jax.eval_shape(T.LMTask(cfg).init, jax.random.PRNGKey(0))
    spec = FedEngine(T.LMTask(cfg), FedConfig()).runtime_for(params).spec
    dtypes = sorted({round_args.state_dtype, round_args.moment_dtype,
                     round_args.hessian_dtype})
    phase_b(jax, jnp, args.seed, spec, dtypes)
    phase_c(jax, args.seed)
    phase_d(jax, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
