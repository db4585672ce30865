"""Virtual-time event scheduler over `FedEngine` (repro.sched).

A deterministic discrete-event simulator: the *virtual clock* is pure
host arithmetic over the latency model (`repro.sched.latency`), while
all model math stays in jitted JAX calls that reuse the engine's own
comm-path client step (`FedEngine.comm_client_step_batched`) — the
same downlink-replica / error-feedback / compressor bookkeeping as
the synchronous round, driven one dispatch group at a time through
the client-batched kernel launches.

Disciplines (``SchedConfig.discipline``):

* ``sync``     — delegates each event to ``FedEngine.round`` verbatim
  (bit-identical to the existing engine); the event takes as long as
  the round's slowest participant.
* ``semisync`` — FedBuff-style buffered aggregation: the first
  ``buffer_size`` arrivals form the round; the server applies their
  staleness-weighted **mean** and immediately re-dispatches them,
  while stragglers keep training and deliver stale deltas into a
  later buffer.  With ``buffer_size == num_clients``, full
  participation and uniform latencies this is bit-identical to the
  synchronous comm path (under partial participation the disciplines
  differ by construction: sync resamples its cohort every round,
  while the event loop keeps the version-0 cohort in flight).
* ``async``    — every arrival is applied immediately (buffer of one)
  with the **unnormalized** staleness-decayed weight
  ``(1 + staleness)^-staleness_power`` (FedAsync-style mixing).

Staleness ``tau`` of an arrival is the number of server model
versions applied between its dispatch and its arrival.  Applying an
aggregate bumps the server version; a client dispatched at version
``v`` trains with ``round_idx = v`` (LR schedule and Sophia refresh
timing follow the dispatch-time version).

Execution note: a dispatch's client math runs eagerly at dispatch
time (the broadcast must see the then-current server model — exactly
the replica semantics of `repro.comm.downlink`); only its *delivery*
is deferred to the arrival's virtual timestamp.  Everything the clock
decides (latencies, arrival order, buffer membership, staleness) is
deterministic in the configured seeds, so a run replays bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import accounting
from repro.comm import downlink as cdown
from repro.comm import flat as cflat
from repro.configs.base import SCHED_DISCIPLINES
from repro.core.schedules import lr_at_round
from repro.obs.spans import SpanLog, phase
from repro.robust import aggregators as robust_agg
from repro.robust import attacks as robust_attacks
from repro.sched import latency


@dataclasses.dataclass(frozen=True)
class SchedEvent:
    """One aggregation event of the virtual clock.

    Byte counters are EXACT Python ints from the accounting model
    (`repro.comm.accounting.stream_bytes`) — ``cum_bytes`` is the
    all-streams total and always equals the sum of the four per-stream
    counters; ``probes`` carries the Sophia health scalars
    (`repro.obs.probes`) when the engine runs with
    ``ObsConfig.probes``."""
    time: float               # virtual seconds at which it was applied
    version: int              # server model version it produced
    kind: str                 # "round" (sync) | "aggregate"
    clients: Tuple[int, ...]  # arrivals folded into this event
    staleness: Tuple[int, ...]
    weights: Tuple[float, ...]
    loss: float               # mean local-training loss of the arrivals
    cum_bytes: int            # cumulative wire bytes, all streams
    eval_loss: Optional[float] = None
    # exact cumulative per-stream wire bytes (all = 0 only before the
    # first dispatch)
    cum_uplink_bytes: int = 0
    cum_downlink_bytes: int = 0
    cum_hessian_uplink_bytes: int = 0
    cum_hessian_downlink_bytes: int = 0
    probes: Optional[Dict[str, float]] = None
    # trace ids of the arrivals folded into this event, aligned with
    # ``clients`` — populated only under ``ObsConfig.trace``
    trace_ids: Tuple[int, ...] = ()
    # adversarial-fleet context (repro.robust): the *effective*
    # aggregator that combined this event's arrivals, the wire attack
    # in play, the byzantine arrivals among ``clients``, and the
    # arrivals that were dropout/rejoin deliveries — all defaults
    # (hence absent from records) for non-adversarial runs
    aggregator: str = "mean"
    attack: str = "none"
    byzantine: Tuple[int, ...] = ()
    dropped: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class SchedDispatch:
    """One dispatch's trace context (``ObsConfig.trace``): the
    compute -> transfer -> arrival chain of a single client on the
    virtual clock, with its exact per-leg byte prices.

    Leg durations come from `latency.dispatch_legs` — a decomposition
    of the lumped `latency.dispatch_seconds` the clock runs on, so
    their sum may differ from ``arrival - time`` in the last ulps;
    ``arrival`` is authoritative."""
    trace_id: int             # unique per run, 1-based, dispatch order
    client: int
    version: int              # server version it trained against
    time: float               # virtual seconds at dispatch
    arrival: float            # virtual seconds at delivery
    compute_s: float
    downlink_s: float
    uplink_s: float
    downlink_bytes: int = 0
    uplink_bytes: int = 0
    hessian_uplink_bytes: int = 0
    hessian_downlink_bytes: int = 0

    def to_record(self) -> Dict[str, Any]:
        return {
            "record": "sched_dispatch", "trace_id": self.trace_id,
            "client": self.client, "version": self.version,
            "time_s": self.time, "arrival_s": self.arrival,
            "compute_s": self.compute_s,
            "downlink_s": self.downlink_s, "uplink_s": self.uplink_s,
            "downlink_bytes": self.downlink_bytes,
            "uplink_bytes": self.uplink_bytes,
            "hessian_uplink_bytes": self.hessian_uplink_bytes,
            "hessian_downlink_bytes": self.hessian_downlink_bytes}

    @staticmethod
    def from_record(r: Dict[str, Any]) -> "SchedDispatch":
        return SchedDispatch(
            trace_id=r["trace_id"], client=r["client"],
            version=r["version"], time=r["time_s"],
            arrival=r["arrival_s"], compute_s=r["compute_s"],
            downlink_s=r["downlink_s"], uplink_s=r["uplink_s"],
            downlink_bytes=r.get("downlink_bytes", 0),
            uplink_bytes=r.get("uplink_bytes", 0),
            hessian_uplink_bytes=r.get("hessian_uplink_bytes", 0),
            hessian_downlink_bytes=r.get("hessian_downlink_bytes", 0))


@dataclasses.dataclass
class SchedTrace:
    """The full event log of one scheduler run."""
    discipline: str
    events: List[SchedEvent] = dataclasses.field(default_factory=list)
    # per-dispatch trace contexts (empty unless ``ObsConfig.trace``)
    dispatches: List[SchedDispatch] = dataclasses.field(
        default_factory=list)

    @property
    def final_time(self) -> float:
        return self.events[-1].time if self.events else 0.0

    @property
    def total_bytes(self) -> int:
        return self.events[-1].cum_bytes if self.events else 0

    def _target_event(self, target_loss: float) -> Optional[SchedEvent]:
        for ev in self.events:
            loss = ev.eval_loss if ev.eval_loss is not None else ev.loss
            if loss <= target_loss:
                return ev
        return None

    def time_to_target(self, target_loss: float) -> Optional[float]:
        """Virtual seconds until the (eval) loss first reached target."""
        ev = self._target_event(target_loss)
        return None if ev is None else ev.time

    def bytes_to_target(self, target_loss: float) -> Optional[int]:
        ev = self._target_event(target_loss)
        return None if ev is None else ev.cum_bytes

    def staleness_hist(self) -> Dict[int, int]:
        """staleness value -> arrival count, over the whole run (the
        per-discipline staleness histogram of docs/observability.md)."""
        hist: Dict[int, int] = {}
        for ev in self.events:
            for t in ev.staleness:
                hist[t] = hist.get(t, 0) + 1
        return hist

    def to_records(self, channel=None) -> List[Dict[str, Any]]:
        """The trace as obs schema records: one ``sched_event`` per
        event (plus its probe scalars, when present) and one final
        ``sched_summary`` with the staleness histogram.  With a
        `repro.metrics.energy.ChannelModel`, each event also carries
        the transmission energy/carbon of its byte DELTA at the
        Shannon rate.  `from_records` inverts this exactly."""
        from repro.metrics import energy as _energy
        recs: List[Dict[str, Any]] = []
        prev_bytes = 0
        for ev in self.events:
            r: Dict[str, Any] = {
                "record": "sched_event", "time_s": ev.time,
                "version": ev.version, "kind": ev.kind,
                "clients": list(ev.clients),
                "staleness": list(ev.staleness),
                "weights": list(ev.weights), "loss": ev.loss,
                "cum_uplink_bytes": ev.cum_uplink_bytes,
                "cum_downlink_bytes": ev.cum_downlink_bytes,
                "cum_hessian_uplink_bytes": ev.cum_hessian_uplink_bytes,
                "cum_hessian_downlink_bytes":
                    ev.cum_hessian_downlink_bytes,
                "cum_total_bytes": ev.cum_bytes}
            if ev.eval_loss is not None:
                r["eval_loss"] = ev.eval_loss
            if channel is not None:
                r["energy_J"] = _energy.tx_energy_joules(
                    ev.cum_bytes - prev_bytes, channel)
                r["carbon_kg"] = _energy.footprint_kg_co2(r["energy_J"])
            prev_bytes = ev.cum_bytes
            if ev.probes:
                r.update(ev.probes)
            if ev.trace_ids:
                r["trace_ids"] = list(ev.trace_ids)
            if ev.aggregator != "mean":
                r["aggregator"] = ev.aggregator
            if ev.attack != "none":
                r["attack"] = ev.attack
            if ev.byzantine:
                r["byzantine_clients"] = list(ev.byzantine)
            if ev.dropped:
                r["dropped_clients"] = list(ev.dropped)
            recs.append(r)
        recs.extend(d.to_record() for d in self.dispatches)
        recs.append({
            "record": "sched_summary", "discipline": self.discipline,
            "events": len(self.events), "final_time_s": self.final_time,
            "cum_total_bytes": self.total_bytes,
            "staleness_hist": [[k, v] for k, v in
                               sorted(self.staleness_hist().items())]})
        return recs

    @staticmethod
    def from_records(records) -> "SchedTrace":
        """Rebuild a trace from `to_records` output (e.g. a parsed
        JSONL log).  Derived fields (energy/carbon) are recomputable,
        so the round trip ``to_records(from_records(to_records(t)))``
        is exact — pinned by tests/test_obs.py."""
        from repro.obs.probes import PROBE_METRICS
        events: List[SchedEvent] = []
        dispatches: List[SchedDispatch] = []
        discipline = None
        for r in records:
            if r.get("record") == "sched_summary":
                discipline = r["discipline"]
            elif r.get("record") == "sched_dispatch":
                dispatches.append(SchedDispatch.from_record(r))
            elif r.get("record") == "sched_event":
                probes = {k: r[k] for k in PROBE_METRICS if k in r}
                events.append(SchedEvent(
                    time=r["time_s"], version=r["version"],
                    kind=r["kind"], clients=tuple(r["clients"]),
                    staleness=tuple(r["staleness"]),
                    weights=tuple(r["weights"]), loss=r["loss"],
                    cum_bytes=r["cum_total_bytes"],
                    eval_loss=r.get("eval_loss"),
                    cum_uplink_bytes=r["cum_uplink_bytes"],
                    cum_downlink_bytes=r["cum_downlink_bytes"],
                    cum_hessian_uplink_bytes=r["cum_hessian_uplink_bytes"],
                    cum_hessian_downlink_bytes=r[
                        "cum_hessian_downlink_bytes"],
                    probes=probes or None,
                    trace_ids=tuple(r.get("trace_ids", ())),
                    aggregator=r.get("aggregator", "mean"),
                    attack=r.get("attack", "none"),
                    byzantine=tuple(r.get("byzantine_clients", ())),
                    dropped=tuple(r.get("dropped_clients", ()))))
        if discipline is None:
            raise ValueError(
                "no sched_summary record — not a to_records() trace")
        return SchedTrace(discipline=discipline, events=events,
                          dispatches=dispatches)


@dataclasses.dataclass
class _InFlight:
    """One dispatched client's precomputed results awaiting delivery."""
    arrival: float
    version: int
    wire: Any
    stat: Any
    loss: float
    ef: Any = None
    opt: Any = None
    dnm: Any = None
    dnef: Any = None
    trace_id: int = 0         # 0 when tracing is off
    dropped: bool = False     # delivery delayed by a dropout/rejoin


class VirtualScheduler:
    """Drives `FedEngine` rounds on a virtual clock.

    ``batch_fn(version) -> pytree`` must return a batch pytree with
    leading client axis ``C`` for the given server version (clients
    dispatched at version ``v`` train on their row of
    ``batch_fn(v)``); ``eval_fn(params) -> scalar loss`` is optional
    and sampled every ``eval_every`` aggregations (it always receives
    the params *pytree* — packed-resident state is unpacked at this
    boundary).

    ``donate=True`` donates end to end: the state to the sync-round
    and apply jits (resident buffers update in place on
    donation-capable backends), the dispatch group's batches to the
    dispatch jit, and the stacked wire/stat/client-state-row buffers
    of each aggregation to the apply jit — every buffer that is
    consumed by its call is handed to XLA instead of being recopied
    per group.  Donation contract: the state passed to `run` is
    consumed — its buffers are invalidated by the first aggregation —
    and ``batch_fn`` results are consumed by the dispatch that reads
    them, so under ``donate=True`` ``batch_fn`` must return fresh
    buffers per version (host/numpy pytrees are always safe: jit
    re-commits them to device each call).  Callers keep only the
    returned state.  The default is undonated (state and batches
    survive `run`, e.g. for side-by-side comparisons).
    State residency follows the engine: tree- and packed-resident
    state (`FedEngine.pack_state`) both work, at any
    `CommConfig.state_dtype` (incl. per-buffer fp8 via
    `moment_dtype`/`hessian_dtype`).
    """

    def __init__(self, engine, batch_fn: Callable[[int], Any],
                 eval_fn: Optional[Callable[[Any], Any]] = None,
                 eval_every: int = 1, donate: bool = False):
        fed = engine.fed
        sched = fed.sched
        comm = fed.comm
        if sched.discipline not in SCHED_DISCIPLINES:
            raise ValueError(
                f"unknown schedule discipline {sched.discipline!r} "
                f"(want one of {SCHED_DISCIPLINES})")
        if comm.hessian_enabled and sched.discipline != "sync":
            raise ValueError(
                "the hessian stream's curvature averaging is a round-"
                "synchronous collective (one common broadcast per "
                "round); use discipline='sync' or disable "
                "hessian_compressor")
        self.engine = engine
        self.fed = fed
        self.sched = sched
        self.comm = comm
        self.batch_fn = batch_fn
        self.eval_fn = eval_fn
        self.eval_every = max(1, eval_every)
        C = fed.num_clients
        self.num_clients = C
        self.cohort = comm.num_participants(C)
        if sched.discipline == "semisync":
            k = sched.buffer_size or self.cohort
            if not 1 <= k <= self.cohort:
                raise ValueError(
                    f"buffer_size={sched.buffer_size} must be in "
                    f"[1, {self.cohort}] (the in-flight cohort)")
            self.buffer_size = k
        else:
            self.buffer_size = 1           # async applies every arrival
        self._stateful = (fed.optimizer == "fed_sophia"
                          and fed.persistent_client_state)
        # adversarial fleet (repro.robust): the byzantine mask is a
        # static host constant folded into the dispatch jit; churn
        # draws come from a dedicated host rng stream consumed per
        # dispatch (in group order), so runs replay bit-for-bit —
        # and are consumed AT ALL only when churn is configured
        rb = fed.robust
        self.robust = rb
        self._byz_mask = robust_attacks.byzantine_mask(rb, C)
        self._attack_on = robust_attacks.wire_attack_active(rb, C)
        self._churn_on = rb.dropout_prob > 0.0
        self._churn_rng = np.random.default_rng([rb.seed, 3])
        self._round_fn = engine.round_fn(donate=donate)
        self._donate = donate
        # dispatch READS the state (its outputs are per-client rows,
        # not a new state), so the state argument never donates there
        # — but the dispatch group's batches are consumed by the call
        # (the batch cache resets after a donating dispatch), and the
        # apply step donates the state plus its stacked
        # wire/stat/client-state-row buffers (freshly stacked per
        # aggregation, never reused afterwards)
        self._dispatch_fn = jax.jit(
            self._dispatch_impl,
            donate_argnums=(1,) if donate else ())
        self._apply_fn = jax.jit(
            self._apply_impl,
            donate_argnums=(0, 1, 2, 5, 6, 7, 8) if donate else ())
        self._batch_cache: Tuple[int, Any] = (-1, None)
        # host-side span timers (docs/observability.md): every
        # dispatch/apply/round is timed and correlated with the
        # virtual clock; launchers read `spans.records()`
        self.spans = SpanLog()
        # Sophia health probes per event (`repro.obs.probes`): the
        # sync discipline reads them out of the round metrics; the
        # event loop probes the post-apply state through this jit
        self._probes_on = fed.obs.probes
        self._probe_fn = (jax.jit(engine.probe_metrics)
                          if self._probes_on else None)
        # per-dispatch trace contexts (`ObsConfig.trace`): pure host
        # bookkeeping — ids, leg durations and byte prices ride the
        # trace/spans, never the jitted math, so the traced run's
        # state is bitwise identical to the untraced one
        self._trace_on = fed.obs.trace

    # ---------------------------------------------------------- jit bodies
    def _dispatch_impl(self, state, batches, idx, rng_v, round_idx):
        """Run the comm-path client step for the dispatch group ``idx``
        against the current server model (client-batched, same math as
        `_round_comm`).  The server model is packed ONCE into the
        canonical wire layout; the dispatch group runs as ONE
        client-batched step (`FedEngine.comm_client_step_batched`) —
        gathered rows keep the resident dtype (the kernels upcast
        loads in-VMEM), and the Pallas path is one launch per fused
        op with the dispatch group as a grid axis."""
        engine = self.engine
        params = state["params"]
        rt = engine.runtime_for(params)
        lr = lr_at_round(self.fed, round_idx)
        theta = (params.astype(jnp.float32)
                 if engine.params_packed(params)
                 else cflat.pack(params, rt.spec))
        with phase("wire"):
            theta_dn = (cflat.repack(theta, rt.spec, rt.spec_dn)
                        if rt.dn_on else None)

        def take(tree):
            return (None if tree is None
                    else jax.tree.map(lambda x: x[idx], tree))

        with phase("rows"):
            opts_g = take(state.get("client_opt") if self._stateful
                          else None)
            ef_g = take(state.get("comm_ef"))
            dnm_g = take(state.get(cdown.MODEL_KEY))
            dnef_g = take(state.get(cdown.EF_KEY))
            batches_g = take(batches)
        rngs_g = jax.vmap(lambda i: jax.random.fold_in(rng_v, i))(idx)

        out = engine.comm_client_step_batched(
            rt, theta, theta_dn, round_idx, lr,
            opts_g, ef_g, dnm_g, dnef_g, batches_g, rngs_g)
        if self._attack_on:
            # byzantine rows of the dispatch group mount the
            # configured transform on their packed uplink wire buffer
            # (repro.robust.attacks); benign runs never trace this
            wires = robust_attacks.attack_wires(
                self.robust, out[0],
                jnp.asarray(self._byz_mask)[idx], rng_v)
            out = (wires,) + out[1:]
        return out

    def _apply_impl(self, state, wires, stats, weights, idx,
                    ef_rows, opt_rows, dnm_rows, dnef_rows):
        """Apply one staleness-weighted aggregate of K arrivals.

        semisync normalizes (weighted mean, FedBuff); async applies the
        raw ``(1+tau)^-p``-weighted delta (FedAsync mixing).  Scatters
        the arrivals' client-state rows back alongside.
        """
        engine = self.engine
        comm = self.comm
        params = state["params"]
        rt = engine.runtime_for(params)
        packed = engine.params_packed(params)
        normalize = self.sched.discipline == "semisync"
        with phase("combine"):
            wsum = jnp.sum(weights)
            inv_norm = (1.0 / wsum) if normalize else jnp.float32(1.0)
            if robust_agg.resolve(self.robust, wires.shape[0]) != "mean":
                # robust combine of the arrival stack (same staleness
                # weights and normalization semantics); degenerate
                # parameterizations resolve to "mean" above and keep
                # the stale_accum path below untouched — bitwise
                agg_flat = robust_agg.aggregate_stack(
                    self.robust, wires, weights, normalize=normalize,
                    use_pallas=comm.use_pallas)
            elif comm.use_pallas:
                from repro.kernels.stale_accum import stale_accum_flat
                agg_flat = stale_accum_flat(wires, weights, inv_norm)
            else:
                w3 = weights[:, None, None]
                agg_flat = jnp.sum(wires * w3, axis=0)
                agg_flat = agg_flat / wsum if normalize else agg_flat
            wstat = jnp.sum(stats * weights)
            if normalize:
                wstat = wstat / wsum
            agg_flat = rt.comp.server_combine(agg_flat, wstat)
            theta = (params.astype(jnp.float32) if packed
                     else cflat.pack(params, rt.spec))
            if rt.dn_on:
                # arrivals trained from their OWN received replicas:
                # fold in each arrival's (replica - current model)
                # reference shift, weighted like its delta
                packed_now = cflat.repack(theta, rt.spec, rt.spec_dn)
                dn_acc = jnp.sum(dnm_rows * weights[:, None, None], axis=0)
                if normalize:
                    corr = dn_acc / wsum - packed_now
                else:
                    corr = dn_acc - wsum * packed_now
                agg_flat = agg_flat + cflat.repack(corr, rt.spec_dn,
                                                   rt.spec)
            # flat axpy + ONE unpack at the state boundary (no per-leaf
            # delta application; none at all in packed-resident mode)
            if packed:
                state = engine._apply_aggregate_flat(state,
                                                     theta + agg_flat)
            else:
                state = engine._apply_aggregate(
                    state, cflat.unpack(theta + agg_flat, rt.spec))
        state = {**state, "round": state["round"] + 1}
        # scatters downcast the arrivals' rows back to the resident
        # storage dtype (no-op for fp32)
        with phase("rows"):
            if self._stateful and opt_rows is not None:
                state = {**state, "client_opt": jax.tree.map(
                    lambda full, g: full.at[idx].set(g),
                    state["client_opt"], engine._store_opt(opt_rows))}
            if ef_rows is not None:
                state = {**state, "comm_ef": state["comm_ef"].at[idx].set(
                    engine._store(ef_rows))}
            if dnm_rows is not None:
                state = {**state, cdown.MODEL_KEY:
                         state[cdown.MODEL_KEY].at[idx].set(
                             engine._store(dnm_rows))}
            if dnef_rows is not None:
                state = {**state, cdown.EF_KEY:
                         state[cdown.EF_KEY].at[idx].set(
                             engine._store(dnef_rows))}
        return state

    # ------------------------------------------------------------- helpers
    def _batches(self, version: int):
        # dispatches only ever draw the CURRENT version's batches, so a
        # one-entry cache suffices (async runs see many versions)
        if self._batch_cache[0] != version:
            self._batch_cache = (version, self.batch_fn(version))
        return self._batch_cache[1]

    def _maybe_eval(self, state, version: int,
                    final: bool) -> Optional[float]:
        if self.eval_fn is None:
            return None
        if final or (version % self.eval_every) == 0:
            # packed-resident state materializes the params pytree
            # only here, at the eval boundary
            return float(self.eval_fn(self.engine.unpack_params(state)))
        return None

    def _weight(self, staleness: int) -> float:
        return float((1.0 + staleness) ** (-self.sched.staleness_power))

    def _event_ctx(self, ids, dropped=()) -> Dict[str, Any]:
        """Adversarial-fleet fields of one event (`repro.robust`): the
        effective aggregator for this event's arrival count, the wire
        attack in play, and the byzantine arrivals among ``ids`` —
        all defaults for non-adversarial runs, so existing traces and
        their records are unchanged."""
        return {
            "aggregator": robust_agg.resolve(self.robust, len(ids)),
            "attack": self.robust.attack if self._attack_on else "none",
            "byzantine": tuple(i for i in ids if self._byz_mask[i]),
            "dropped": tuple(dropped)}

    def _event_probes(self, state=None,
                      metrics=None) -> Optional[Dict[str, float]]:
        """Sophia health scalars of one event (None when probing is
        off): sync rounds computed them inside the round jit already
        (pass ``metrics``); the event loop probes the post-apply
        ``state``.  The host sync this forces lands on values the
        event record fetches anyway (loss is float()ed per event)."""
        if not self._probes_on:
            return None
        if metrics is not None:
            from repro.obs.probes import PROBE_METRICS
            return {k: float(metrics[k]) for k in PROBE_METRICS}
        return {k: float(v) for k, v in self._probe_fn(state).items()}

    # ----------------------------------------------------------------- run
    def run(self, state, num_events: int, rng, *,
            target_loss: Optional[float] = None,
            stop_at_target: bool = False):
        """Advance the virtual clock through ``num_events`` aggregation
        events (sync: rounds).  Returns ``(state, SchedTrace)``;
        with ``stop_at_target`` the run ends at the first event whose
        (eval) loss reaches ``target_loss``.
        """
        if self.sched.discipline == "sync":
            return self._run_sync(state, num_events, rng, target_loss,
                                  stop_at_target)
        return self._run_event_loop(state, num_events, rng, target_loss,
                                    stop_at_target)

    def _run_sync(self, state, num_events, rng, target_loss,
                  stop_at_target):
        fed, comm = self.fed, self.comm
        C = self.num_clients
        n_params = self.engine.num_params(state)
        durations = latency.dispatch_seconds(fed, n_params, C)
        per_round = accounting.round_bytes(comm, n_params, C)
        legs = (latency.dispatch_legs(fed, n_params, C)
                if self._trace_on else None)
        stream_dn = accounting.stream_bytes(comm, "downlink", n_params)
        stream_up = accounting.stream_bytes(comm, "uplink", n_params)
        stream_h = accounting.stream_bytes(comm, "hessian", n_params)
        trace = SchedTrace(discipline="sync")
        now, cum_bytes, next_tid = 0.0, 0, 1
        cum = {"uplink_bytes": 0, "downlink_bytes": 0,
               "hessian_uplink_bytes": 0, "hessian_downlink_bytes": 0}
        for v in range(num_events):
            rng_v = jax.random.fold_in(rng, v)
            # participation is a pure function of rng_v (the round jit
            # re-derives the same sample), so reading it pre-round for
            # the trace context changes nothing downstream
            part = np.asarray(self.engine.round_participants(rng_v))
            tids: Tuple[int, ...] = ()
            if self._trace_on:
                tids = tuple(range(next_tid, next_tid + len(part)))
                next_tid += len(part)
                for tid, i in zip(tids, part):
                    trace.dispatches.append(SchedDispatch(
                        trace_id=tid, client=int(i), version=v,
                        time=now, arrival=now + float(durations[i]),
                        downlink_s=float(legs[0][i]),
                        compute_s=float(legs[1][i]),
                        uplink_s=float(legs[2][i]),
                        downlink_bytes=stream_dn,
                        uplink_bytes=stream_up,
                        hessian_uplink_bytes=stream_h,
                        hessian_downlink_bytes=stream_h))
            with self.spans.span("round", virtual_s=now,
                                 trace_id=tids[0] if tids else None):
                state, metrics = self._round_fn(state, self._batches(v),
                                                rng_v)
            now += float(np.max(durations[part]))
            cum_bytes += per_round["total_bytes"]
            for k in cum:
                cum[k] += per_round[k]
            final = v == num_events - 1
            ev = SchedEvent(
                time=now, version=v + 1, kind="round",
                clients=tuple(int(i) for i in part),
                staleness=(0,) * len(part),
                weights=(1.0,) * len(part),
                loss=float(metrics["loss"]), cum_bytes=cum_bytes,
                eval_loss=self._maybe_eval(state, v + 1, final),
                cum_uplink_bytes=cum["uplink_bytes"],
                cum_downlink_bytes=cum["downlink_bytes"],
                cum_hessian_uplink_bytes=cum["hessian_uplink_bytes"],
                cum_hessian_downlink_bytes=cum["hessian_downlink_bytes"],
                probes=self._event_probes(metrics=metrics),
                trace_ids=tids,
                **self._event_ctx([int(i) for i in part]))
            trace.events.append(ev)
            if self._hit_target(ev, target_loss, stop_at_target):
                break
        return state, trace

    def _run_event_loop(self, state, num_events, rng, target_loss,
                        stop_at_target):
        fed, comm = self.fed, self.comm
        C = self.num_clients
        n_params = self.engine.num_params(state)
        durations = latency.dispatch_seconds(fed, n_params, C)
        down_bytes, up_bytes = latency.leg_bytes(comm, n_params)
        # per-stream pricing of one leg: the hessian payload rides both
        # legs when enabled (`latency.leg_bytes`), so the lumped leg
        # totals always decompose as down = dn + h, up = up + h
        stream_dn = accounting.stream_bytes(comm, "downlink", n_params)
        stream_up = accounting.stream_bytes(comm, "uplink", n_params)
        stream_h = accounting.stream_bytes(comm, "hessian", n_params)
        legs = (latency.dispatch_legs(fed, n_params, C)
                if self._trace_on else None)
        trace = SchedTrace(discipline=self.sched.discipline)
        inflight: Dict[int, _InFlight] = {}
        buffer: List[Tuple[int, _InFlight]] = []
        now, version, cum_bytes = 0.0, 0, 0
        next_tid = 1
        cum = {"uplink_bytes": 0, "downlink_bytes": 0,
               "hessian_uplink_bytes": 0, "hessian_downlink_bytes": 0}

        def dispatch(group, at_time):
            nonlocal cum_bytes, next_tid
            group = sorted(group)
            idx = jnp.asarray(group, jnp.int32)
            rng_v = jax.random.fold_in(rng, version)
            with self.spans.span("dispatch", virtual_s=at_time,
                                 trace_id=(next_tid if self._trace_on
                                           else None)):
                (wires, stats, ef_new, opt_new, losses, dnm_new,
                 dnef_new, _h, _hs) = self._dispatch_fn(
                    state, self._batches(version), idx, rng_v,
                    jnp.asarray(version, jnp.int32))
                if self._donate:
                    # the dispatch consumed (donated) the cached
                    # batches — drop the invalidated object so a
                    # same-version lookup never resurrects it
                    self._batch_cache = (-1, None)

                def row(tree, pos):
                    return (None if tree is None
                            else jax.tree.map(lambda x: x[pos], tree))

                for pos, i in enumerate(group):
                    # dropout/rejoin on the virtual clock: the client
                    # goes offline mid-round and delivers its (stale)
                    # update rejoin_delay_s after coming back — one
                    # host rng draw per dispatched client, in group
                    # order, so replays are deterministic
                    extra, was_dropped = 0.0, False
                    if self._churn_on and (self._churn_rng.random()
                                           < self.robust.dropout_prob):
                        extra = float(self.robust.rejoin_delay_s)
                        was_dropped = True
                    arrival = at_time + float(durations[i]) + extra
                    tid = 0
                    if self._trace_on:
                        tid, next_tid = next_tid, next_tid + 1
                        trace.dispatches.append(SchedDispatch(
                            trace_id=tid, client=i, version=version,
                            time=at_time,
                            arrival=arrival,
                            downlink_s=float(legs[0][i]),
                            compute_s=float(legs[1][i]),
                            uplink_s=float(legs[2][i]),
                            downlink_bytes=stream_dn,
                            uplink_bytes=stream_up,
                            hessian_uplink_bytes=stream_h,
                            hessian_downlink_bytes=stream_h))
                    inflight[i] = _InFlight(
                        arrival=arrival,
                        version=version,
                        wire=wires[pos], stat=stats[pos],
                        loss=float(losses[pos]),
                        ef=row(ef_new, pos), opt=row(opt_new, pos),
                        dnm=row(dnm_new, pos), dnef=row(dnef_new, pos),
                        trace_id=tid, dropped=was_dropped)
                    cum_bytes += down_bytes
                    cum["downlink_bytes"] += stream_dn
                    cum["hessian_downlink_bytes"] += stream_h

        # initial cohort: the participation sample of version 0; the
        # same clients stay in flight for the whole run (delivering
        # re-dispatches them), so `participation` is the concurrency
        part0 = np.asarray(self.engine.round_participants(
            jax.random.fold_in(rng, 0)))
        dispatch([int(i) for i in part0], now)

        def stack(rows):
            if rows[0] is None:
                return None
            return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)

        while version < num_events and inflight:
            i = min(inflight, key=lambda j: (inflight[j].arrival, j))
            rec = inflight.pop(i)
            now = rec.arrival
            cum_bytes += up_bytes
            cum["uplink_bytes"] += stream_up
            cum["hessian_uplink_bytes"] += stream_h
            buffer.append((i, rec))
            if len(buffer) < self.buffer_size:
                continue
            ids = [i for i, _ in buffer]
            recs = [r for _, r in buffer]
            stale = [version - r.version for r in recs]
            weights = [self._weight(t) for t in stale]
            tids = (tuple(r.trace_id for r in recs)
                    if self._trace_on else ())
            with self.spans.span("apply", virtual_s=now,
                                 trace_id=(min(tids) if tids
                                           else None)):
                state = self._apply_fn(
                    state,
                    jnp.stack([r.wire for r in recs]),
                    jnp.stack([r.stat for r in recs]),
                    jnp.asarray(weights, jnp.float32),
                    jnp.asarray(ids, jnp.int32),
                    stack([r.ef for r in recs]),
                    stack([r.opt for r in recs]),
                    stack([r.dnm for r in recs]),
                    stack([r.dnef for r in recs]))
            version += 1
            final = version == num_events
            ev = SchedEvent(
                time=now, version=version, kind="aggregate",
                clients=tuple(ids), staleness=tuple(stale),
                weights=tuple(weights),
                loss=float(np.mean([r.loss for r in recs])),
                cum_bytes=cum_bytes,
                eval_loss=self._maybe_eval(state, version, final),
                cum_uplink_bytes=cum["uplink_bytes"],
                cum_downlink_bytes=cum["downlink_bytes"],
                cum_hessian_uplink_bytes=cum["hessian_uplink_bytes"],
                cum_hessian_downlink_bytes=cum["hessian_downlink_bytes"],
                probes=self._event_probes(state=state),
                trace_ids=tids,
                **self._event_ctx(ids, dropped=[
                    i for i, r in zip(ids, recs) if r.dropped]))
            trace.events.append(ev)
            buffer = []
            if self._hit_target(ev, target_loss, stop_at_target):
                break
            if not final:
                dispatch(ids, now)        # delivered clients go again
        return state, trace

    @staticmethod
    def _hit_target(ev: SchedEvent, target_loss, stop_at_target) -> bool:
        if target_loss is None or not stop_at_target:
            return False
        loss = ev.eval_loss if ev.eval_loss is not None else ev.loss
        return loss <= target_loss
