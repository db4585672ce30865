"""Training launcher: federated Fed-Sophia (or baselines) on any arch.

Everything runs on the default device, with no mesh.  On the CPU,
``--reduced`` shrinks the widths for end-to-end validation:

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --reduced --rounds 5

On a TPU, ``--num-layers`` cuts only the depth and keeps every
published width (``chip_smoke.py`` at the repo root drives this path):

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --num-layers 2 --clients 1 --batch 1 --seq 2048 \
        --use-pallas --comm-pallas --compressor int8

`run` builds the engine and state and drives the rounds; `main` and
``chip_smoke.py`` both call it.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.checkpoint import ckpt
from repro.comm import round_bytes
from repro.comm import flat as cflat
from repro.configs.base import (AGGREGATORS, ATTACKS, LATENCY_PROFILES,
                                SCHED_DISCIPLINES, CommConfig, FedConfig,
                                ObsConfig, RobustConfig, SchedConfig)
from repro.core.fed import FedEngine
from repro.data import synthetic as syn
from repro.metrics import energy
from repro.models import transformer as T
from repro.robust import aggregators as robust_agg
from repro.robust import attacks as robust_attacks
from repro.sched import VirtualScheduler

#: the checkout's root (src/repro/launch/train.py -> three levels up)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing else is set here; otherwise the cache is the
    fixed ``.jax_cache/`` at the checkout's root, so a later process in
    the same checkout finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-iters", type=int, default=5)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--tau", type=int, default=5)
    ap.add_argument("--optimizer", default="fed_sophia")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced model dims (CPU-feasible)")
    ap.add_argument("--num-layers", type=int, default=0,
                    help="cut the depth to this many layers, rounded "
                         "down to whole block_pattern periods (at least "
                         "one); every width stays as published "
                         "(0 = the published depth)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="fused Sophia kernel (interpret mode on CPU)")
    # communication layer (repro.comm)
    ap.add_argument("--compressor", default="identity",
                    choices=("identity", "int8", "int4", "topk", "signsgd"),
                    help="uplink delta compressor")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled per round")
    ap.add_argument("--topk-ratio", type=float, default=0.01)
    ap.add_argument("--error-feedback", default="auto",
                    choices=("auto", "on", "off"),
                    help="per-client EF residuals (auto: biased "
                         "compressors only)")
    ap.add_argument("--sign-majority", action="store_true",
                    help="signsgd: server-side majority vote")
    ap.add_argument("--downlink-compressor", default="identity",
                    choices=("identity", "int8", "int4", "topk", "signsgd"),
                    help="server broadcast compressor (delta vs each "
                         "client's last-received model, server-side EF)")
    ap.add_argument("--hessian-compressor", default="off",
                    choices=("off", "identity", "int8", "int4", "topk",
                             "signsgd"),
                    help="Sophia h-EMA uplink compressor (curvature "
                         "averaging; 'off' keeps curvature local)")
    ap.add_argument("--comm-pallas", action="store_true",
                    help="fused quantize/dequantize kernels (interpret on CPU)")
    # device residency of the engine state (docs/architecture.md
    # "Memory layout: the life of a round")
    ap.add_argument("--state-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="storage dtype of resident wire-layout state "
                         "(params between rounds, Sophia m/h, EF, "
                         "replicas); bfloat16 halves its HBM, compute "
                         "stays fp32")
    ap.add_argument("--moment-dtype", default="",
                    choices=("", "float32", "bfloat16",
                             "float8_e4m3fn", "float8_e5m2"),
                    help="per-buffer override of --state-dtype for the "
                         "Sophia first-moment stack (e4m3: more "
                         "mantissa; '' = follow --state-dtype)")
    ap.add_argument("--hessian-dtype", default="",
                    choices=("", "float32", "bfloat16",
                             "float8_e4m3fn", "float8_e5m2"),
                    help="per-buffer override of --state-dtype for the "
                         "hessian-EMA stack (e5m2: more range; "
                         "'' = follow --state-dtype)")
    ap.add_argument("--tree-state", action="store_true",
                    help="keep params as a pytree between rounds and "
                         "skip buffer donation (the pre-residency "
                         "engine; default: packed, donated rounds)")
    # virtual-time round scheduling (repro.sched)
    ap.add_argument("--schedule", default="sync",
                    choices=SCHED_DISCIPLINES,
                    help="round discipline: sync (today's engine), "
                         "semisync (FedBuff-style buffered rounds) or "
                         "async (per-arrival staleness-weighted apply)")
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="semisync: arrivals aggregated per round "
                         "(0 = all in-flight participants)")
    ap.add_argument("--staleness-power", type=float, default=0.5,
                    help="arrival weight (1+staleness)^-p")
    ap.add_argument("--dispatch-chunk", type=int, default=0,
                    help="run dispatch groups larger than this as a "
                         "sequence of fixed-size chunks (one "
                         "compilation; 0 = whole group at once)")
    ap.add_argument("--latency-profile", default="uniform",
                    choices=LATENCY_PROFILES,
                    help="per-client latency model of the virtual clock")
    # adversarial fleet (repro.robust; docs/robustness.md)
    ap.add_argument("--aggregator", default="mean", choices=AGGREGATORS,
                    help="server-side combiner of client contributions "
                         "(degenerate parameterizations keep the mean "
                         "path bitwise)")
    ap.add_argument("--trim-fraction", type=float, default=0.0,
                    help="trimmed_mean: per-coordinate per-side trim "
                         "fraction of the arrival stack")
    ap.add_argument("--clip-norm", type=float, default=0.0,
                    help="norm_clip: max L2 norm per arrival (0 = off)")
    ap.add_argument("--attack", default="none", choices=ATTACKS,
                    help="byzantine wire attack applied to malicious "
                         "clients' packed uplink buffers")
    ap.add_argument("--attack-fraction", type=float, default=0.0,
                    help="fraction of clients byzantine")
    ap.add_argument("--attack-scale", type=float, default=10.0,
                    help="multiplier of the 'scale' attack")
    ap.add_argument("--label-noise-fraction", type=float, default=0.0,
                    help="fraction of clients training on corrupted "
                         "labels")
    ap.add_argument("--label-noise-rate", type=float, default=0.5,
                    help="per-sample corruption probability on "
                         "label-noise clients")
    ap.add_argument("--dropout-prob", type=float, default=0.0,
                    help="per-dispatch client dropout probability on "
                         "the virtual clock (scheduler disciplines)")
    ap.add_argument("--rejoin-delay-s", type=float, default=0.0,
                    help="extra virtual seconds before a dropped "
                         "client's update is delivered")
    # structured telemetry (repro.obs; docs/observability.md)
    ap.add_argument("--probes", action="store_true",
                    help="device-side Sophia health probes in the round "
                         "metrics (clip fraction, m/h norms, curvature "
                         "freshness; fed_sophia only)")
    ap.add_argument("--trace", action="store_true",
                    help="per-dispatch trace contexts on the virtual "
                         "clock (sched_dispatch records + trace_ids; "
                         "export with tools/obs_trace.py)")
    ap.add_argument("--obs-log", default="",
                    help="write schema-validated JSONL telemetry to this "
                         "path (+ a .manifest.json on exit)")
    ap.add_argument("--obs-flush-every", type=int, default=10,
                    help="rounds per device-metrics flush (host syncs "
                         "only at this boundary in obs runs)")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the run into "
                         "this directory (annotated round/kernel spans)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true",
                    help="restore params from --ckpt-dir first "
                         "(validates the checkpoint's wire-layout "
                         "headers against the current comm config)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def model_config(args):
    """The architecture's config with the launcher's cuts: ``--reduced``
    shrinks widths (CPU tests), ``--num-layers`` only the depth."""
    cfg = configs.get_model_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(d_model=128)
    if args.num_layers:
        cfg = cfg.with_depth(args.num_layers)
    return cfg


@dataclasses.dataclass
class Run:
    """What `run` leaves behind: the engine, its final state, the
    compiled round entry point with the inputs it was fed, and one
    loss and one host wall time per round (sync) or aggregation event
    (scheduler disciplines; the times are then simulated seconds)."""
    engine: FedEngine
    state: Any
    round_fn: Callable
    make_batches: Callable[[int], Any]
    key: Any
    losses: List[float]
    seconds: List[float]


def run(args: argparse.Namespace) -> Run:
    """Build the engine and its state from ``args`` and drive
    ``args.rounds`` rounds (or scheduler events), printing one line
    each."""
    cfg = model_config(args)
    over = configs.get_fed_overrides(args.arch)
    ef = {"auto": "auto", "on": True, "off": False}[args.error_feedback]
    comm = CommConfig(compressor=args.compressor,
                      participation=args.participation,
                      topk_ratio=args.topk_ratio,
                      error_feedback=ef,
                      sign_majority=args.sign_majority,
                      downlink_compressor=args.downlink_compressor,
                      hessian_compressor=args.hessian_compressor,
                      state_dtype=args.state_dtype,
                      moment_dtype=args.moment_dtype,
                      hessian_dtype=args.hessian_dtype,
                      use_pallas=args.comm_pallas)
    sched = SchedConfig(discipline=args.schedule,
                        buffer_size=args.buffer_size,
                        staleness_power=args.staleness_power,
                        dispatch_chunk=args.dispatch_chunk,
                        latency_profile=args.latency_profile)
    robust = RobustConfig(aggregator=args.aggregator,
                          trim_fraction=args.trim_fraction,
                          clip_norm=args.clip_norm,
                          attack=args.attack,
                          attack_fraction=args.attack_fraction,
                          attack_scale=args.attack_scale,
                          label_noise_fraction=args.label_noise_fraction,
                          label_noise_rate=args.label_noise_rate,
                          dropout_prob=args.dropout_prob,
                          rejoin_delay_s=args.rejoin_delay_s,
                          seed=args.seed)
    fed = FedConfig(num_clients=args.clients, local_iters=args.local_iters,
                    optimizer=args.optimizer, lr=args.lr, tau=args.tau,
                    total_rounds=args.rounds, use_pallas=args.use_pallas,
                    schedule=over.get("schedule", "const"), comm=comm,
                    sched=sched, robust=robust,
                    obs=ObsConfig(probes=args.probes, trace=args.trace,
                                  flush_every=args.obs_flush_every))
    task = T.LMTask(cfg)
    engine = FedEngine(task, fed)
    key = jax.random.PRNGKey(args.seed)
    state = engine.init(key)
    if args.resume:
        manifest = ckpt.load_manifest(args.ckpt_dir)
        cflat.check_headers(manifest.get("extra", {}).get("wire", {}),
                            engine.wire_headers(state["params"]))
        # rebuild the wire-layout client state (downlink replicas, EF
        # residuals) around the restored model — broadcasting deltas
        # against the discarded random init would be garbage
        state = engine.restore_params(
            state, ckpt.restore(args.ckpt_dir, state["params"]))
        print(f"resumed params from {args.ckpt_dir} "
              f"(step {manifest['step']}, wire headers OK)")
    if not args.tree_state:
        # device residency: params stay packed in wire layout BETWEEN
        # rounds (pytrees materialize only at the eval/checkpoint
        # boundary below) and the jitted round donates the state, so
        # resident buffers update in place
        state = engine.pack_state(state)
    round_fn = engine.round_fn(donate=not args.tree_state)

    n_params = engine.num_params(state)
    # exact integers from the accounting model; the obs record schema
    # (repro.obs.schema) carries them downstream as exact int64 columns
    wire = round_bytes(comm, n_params, fed.num_clients)
    uplink_round = wire["uplink_bytes"]
    total_round = wire["total_bytes"]
    print(f"arch={cfg.name} params={n_params:,}"
          f" clients={fed.num_clients} J={fed.local_iters}"
          f" opt={fed.optimizer} compressor={comm.compressor}"
          f" downlink={comm.downlink_compressor}"
          f" hessian={comm.hessian_compressor}"
          f" participation={comm.participation:g}")
    # effective robust path of a full sync cohort (degenerate
    # parameterizations resolve to "mean" — today's path, bitwise)
    eff_agg = robust_agg.resolve(robust, wire["participants"])
    attack_on = robust_attacks.wire_attack_active(robust,
                                                 fed.num_clients)
    if eff_agg != "mean" or robust.adversarial:
        byz = [int(i) for i in
               robust_attacks.byzantine_mask(
                   robust, fed.num_clients).nonzero()[0]]
        print(f"adversarial fleet: aggregator={eff_agg} "
              f"attack={robust.attack if attack_on else 'none'} "
              f"byzantine={byz} "
              f"label_noise={robust.label_noise_fraction:g} "
              f"dropout={robust.dropout_prob:g}")
    print("per-round wire bytes: "
          + " ".join(f"{k}={wire[k]:,}" for k in
                     ("uplink_bytes", "downlink_bytes",
                      "hessian_uplink_bytes", "hessian_downlink_bytes",
                      "total_bytes")))
    # the canonical flat layout every resident state buffer lives in
    # (docs/architecture.md "Memory layout"); its header rides along in
    # the checkpoint manifest and is validated on --resume
    rt = engine.runtime_for(state["params"])
    residency = "tree" if args.tree_state else "packed+donated"
    dtypes = comm.state_dtype
    if comm.moment_dtype or comm.hessian_dtype:
        dtypes += (f" (m: {comm.moment_dtype or comm.state_dtype}, "
                   f"h: {comm.hessian_dtype or comm.state_dtype})")
    print(f"flat-resident state layout: {rt.spec.rows}x{rt.spec.cols} "
          f"{dtypes} ({rt.spec.total:,} coords + "
          f"{rt.spec.padded - rt.spec.total} pad), "
          f"between-round residency: {residency}")

    # per-round energy/carbon (paper Eq. 13-14 over the EXACT wire
    # bytes; repro.metrics.energy): static in the config, so priced once
    chan = energy.ChannelModel()
    comm_J = energy.tx_energy_joules(wire["total_bytes"], chan)
    # compute side: ~6*N FLOPs per trained token (fwd+bwd), J local
    # iterations per participant per round
    flops_iter = 6.0 * n_params * args.batch * args.seq
    compute_J = (energy.ComputeModel().energy_per_iteration(flops_iter)
                 * fed.local_iters * wire["participants"])
    round_J = comm_J + compute_J
    round_carbon = energy.footprint_kg_co2(round_J)

    recorder = None
    if args.obs_log:
        recorder = obs.RunRecorder(
            args.obs_log, ring_capacity=fed.obs.ring_capacity,
            meta={"arch": cfg.name, "params": n_params,
                  "clients": fed.num_clients,
                  "local_iters": fed.local_iters,
                  "optimizer": fed.optimizer,
                  "compressor": comm.compressor,
                  "schedule": args.schedule, "probes": fed.obs.probes,
                  "trace": fed.obs.trace, "residency": residency,
                  "state_dtype": comm.state_dtype,
                  "aggregator": robust.aggregator,
                  "attack": robust.attack})

    noisy = robust_attacks.label_noise_mask(robust, fed.num_clients)

    def make_batches(r):
        kb = jax.random.fold_in(key, 1000 + r)
        batches = syn.make_token_batch(kb, fed.num_clients, args.batch,
                                       args.seq, cfg.vocab_size)
        if noisy.any():
            # label-noise clients train on corrupted targets; the
            # corruption runs at data-build time (host numpy), so the
            # jitted round is untouched
            batches = dict(batches, labels=jnp.asarray(
                robust_attacks.corrupt_labels(robust, batches["labels"],
                                              noisy, cfg.vocab_size)))
        if cfg.embedding_inputs:
            ke = jax.random.fold_in(kb, 1)
            batches = {"embeds": jax.random.normal(
                ke, (fed.num_clients, args.batch, args.seq, cfg.d_model),
                dtype=T.param_dtype(cfg)), "labels": batches["labels"]}
        return batches

    spans = obs.SpanLog()
    losses: List[float] = []
    seconds: List[float] = []

    def round_line(r, loss, lr, dt, row=None):
        clip = (f" clip={row['clip_fraction']:.3f}"
                if row and "clip_fraction" in row else "")
        return (f"round {r:3d} loss={loss:.4f} lr={lr:.2e} "
                f"uplink={uplink_round / 2**20:.2f}MiB "
                f"total={total_round / 2**20:.2f}MiB "
                f"(cum {(r + 1) * total_round / 2**20:.2f}MiB)"
                f"{clip} ({dt:.1f}s)")

    def emit_round(r, row, wall_s):
        rec = {"record": "round", "round": r, "loss": row["loss"],
               "lr": row["lr"], "participants": wire["participants"],
               "cum_total_bytes": (r + 1) * total_round,
               "energy_J": round_J, "comm_J": comm_J,
               "compute_J": compute_J, "carbon_kg": round_carbon,
               "wall_s": wall_s}
        for k in ("uplink_bytes", "downlink_bytes",
                  "hessian_uplink_bytes", "hessian_downlink_bytes",
                  "total_bytes"):
            rec[k] = wire[k]
        for k in obs.PROBE_METRICS:
            if k in row:
                rec[k] = row[k]
        # robust context rides along only when the run departs from
        # the default mean/no-attack path (schema: optional fields)
        if eff_agg != "mean":
            rec["aggregator"] = eff_agg
        if attack_on:
            rec["attack"] = robust.attack
        recorder.emit(rec)

    with obs.profile_trace(args.profile_dir):
        if args.schedule == "sync" and recorder is None:
            # the existing synchronous loop, bit-identical to earlier
            # builds (the per-round host sync is the loss print itself)
            for r in range(args.rounds):
                t0 = time.time()
                with spans.span("round"):
                    state, metrics = round_fn(state, make_batches(r),
                                              jax.random.fold_in(key, r))
                losses.append(float(metrics["loss"]))
                seconds.append(time.time() - t0)
                print(round_line(r, losses[-1], float(metrics["lr"]),
                                 seconds[-1]), flush=True)
        elif args.schedule == "sync":
            # obs loop: round metrics (incl. the in-jit Sophia health
            # probes) accumulate in a device-side buffer; the host
            # syncs, records and prints only at the flush boundary —
            # strictly FEWER host syncs than the plain loop
            acc = obs.MetricsAccumulator(fed.obs.flush_every)
            pending = []
            t0 = time.time()
            for r in range(args.rounds):
                with spans.span("round"):
                    state, metrics = round_fn(state, make_batches(r),
                                              jax.random.fold_in(key, r))
                acc.add(metrics)
                pending.append(r)
                if len(acc) == fed.obs.flush_every or r == args.rounds - 1:
                    with spans.span("flush"):
                        rows = acc.flush()
                    dt = (time.time() - t0) / len(pending)
                    for rr, row in zip(pending, rows):
                        losses.append(row["loss"])
                        seconds.append(dt)
                        emit_round(rr, row, dt)
                        print(round_line(rr, row["loss"], row["lr"], dt,
                                         row), flush=True)
                    pending = []
                    t0 = time.time()
        else:
            # virtual-time event loop (repro.sched): --rounds counts
            # aggregation events; the printed time is SIMULATED seconds.
            # The apply jit donates the state unless --tree-state.
            scheduler = VirtualScheduler(engine, make_batches,
                                         donate=not args.tree_state)
            state, trace = scheduler.run(state, args.rounds, key)
            for ev in trace.events:
                losses.append(ev.loss)
                seconds.append(ev.time)
                stale = max(ev.staleness) if ev.staleness else 0
                clip = (f" clip={ev.probes['clip_fraction']:.3f}"
                        if ev.probes else "")
                print(f"event {ev.version:3d} t={ev.time:9.2f}s "
                      f"loss={ev.loss:.4f} clients={list(ev.clients)} "
                      f"max_stale={stale} "
                      f"cum={ev.cum_bytes / 2**20:.2f}MiB{clip}",
                      flush=True)
            print(f"{args.schedule}: {len(trace.events)} events, "
                  f"simulated {trace.final_time:.2f}s, "
                  f"{trace.total_bytes / 2**20:.2f}MiB on the wire")
            if recorder is not None:
                # structured SchedEvent records (exact per-stream int64
                # byte counters, staleness histogram, per-event
                # energy), then the scheduler's own span timers
                recorder.emit_all(trace.to_records(channel=chan))
                recorder.emit_all(scheduler.spans.records())
    if recorder is not None:
        recorder.emit_all(spans.records())
        recorder.close()
        print(f"wrote {recorder.counts} obs records to {args.obs_log} "
              f"(+ {recorder.manifest_path})")
    if args.ckpt_dir:
        extra = {"arch": args.arch,
                 "wire": engine.wire_headers(state["params"])}
        if engine.params_packed(state["params"]):
            # checkpoint boundary shim: the on-disk format is the
            # pytree regardless of the between-round residency
            ckpt.save_packed(args.ckpt_dir, state["params"], rt.spec,
                             step=args.rounds, extra=extra)
        else:
            ckpt.save(args.ckpt_dir, state["params"], step=args.rounds,
                      extra=extra)
        print(f"saved checkpoint to {args.ckpt_dir}")
    return Run(engine=engine, state=state, round_fn=round_fn,
               make_batches=make_batches, key=key, losses=losses,
               seconds=seconds)


def main(argv: Optional[List[str]] = None) -> None:
    use_compile_cache()
    run(parse_args(argv))


if __name__ == "__main__":
    main()
