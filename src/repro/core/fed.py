"""Federated runtime: one jitted call = one communication round (Alg. 1).

Two execution strategies (DESIGN.md §4):
  * parallel   — vmap over a leading client axis; client axis is sharded
                 along the mesh 'data' (and 'pod') axes, so the final
                 aggregation mean lowers to the cross-client all-reduce
                 that realises Eq. 4.
  * sequential — lax.scan over clients; each client trains with the whole
                 mesh (FSDP); memory O(1) in the number of clients.

Optimizers: fed_sophia (the paper), fedavg, done, fedadam, fedyogi.

Memory layout (docs/architecture.md "Memory layout"): the engine is
**flat-resident** — the packed (rows, cols) fp32 wire buffer of
`repro.comm.flat` is the canonical in-round representation of every
piece of client-visible state outside the local loop: the round-start
model, each client's trained theta, the Sophia m/h EMAs (stored across
rounds as (C, rows, cols) arrays), uplink EF residuals and downlink
replicas.  The wire compressors consume state that is *already* in
their layout, the uplink delta is a flat subtraction, and the hessian
stream reads ``opt.h`` without conversion.  The local loop runs in the
model's leaf layout: theta and m/h cross from the packed buffers into
leaves (each in its resident dtype) once at the loop's start and back
once at its end, so the forward/backward, the GNB estimate and the
per-leaf Sophia kernel launches of every local step see leaves and
never pack or unpack.  Leaf flattening order is frozen
(`flat.FlatSpec`), which makes the flat round bit-identical to the
historical pytree engine for fp32 models (tests/test_flat_engine.py
pins this per config, and the leaf-layout loop against the loop that
crossed the layout at every step).

Device residency (docs/architecture.md "Memory layout: the life of a
round"): the engine goes one step further than in-round flatness —

* **Packed params between rounds.** `pack_state` re-lays
  ``state["params"]`` (and the FedOpt server m/v) as wire buffers, and
  `round` consumes/produces them without the per-round pack/unpack
  bracket; the pytree then exists only at the init / eval / checkpoint
  boundaries (`unpack_params` / `unpack_state` are the inverse shims).
* **Buffer donation.** `round_fn(donate=True)` jits the round with the
  state argument donated, so on donation-capable backends theta, the
  (C, rows, cols) Sophia m/h stacks, EF residuals and downlink
  replicas update IN PLACE — zero per-round device copies of resident
  client state.  Contract: the caller must not touch the state it
  passed in after the call (XLA invalidates those buffers); rebind the
  returned state, as ``state, metrics = round_fn(state, ...)`` does.
* **bf16 resident state.** ``CommConfig.state_dtype="bfloat16"``
  stores all resident wire-layout state in bf16 (half the HBM);
  gathered rows feed the kernels *in their storage dtype* — the
  kernels upcast loads to fp32 in-VMEM (`repro.kernels` dtype
  contract), jnp promotion handles the mixed-dtype flat arithmetic
  exactly, and rows downcast on the scatter back (`_store`).  No
  bulk gather-side upcast ever materializes an fp32 copy of resident
  state.  Wire bytes are unaffected; fp32 configs see only no-op
  casts and stay bit-identical (tests/test_residency.py).

* **Client-batched kernels.** The parallel strategy steps the whole
  cohort through ONE client-batched pipeline (`comm_client_step_
  batched`): downlink broadcast, the local Sophia scan, uplink
  encode and the hessian round-trip each run as a single Pallas
  launch over the packed (C, rows, cols) stacks instead of C vmapped
  (rows, cols) launches — bitwise equal to the vmapped per-client
  path (tests/test_residency.py pins it).

Communication model (repro.comm): with the default CommConfig (lossless
identity uplink/downlink, hessian stream off, full participation) the
round aggregates client params directly — bit-identical to the original
engine.  Any compression, partial participation, or extra stream routes
through the multi-stream delta-space pipeline:

    [downlink]  broadcast delta theta - theta_i^rx (+ server EF)
                -> encode/decode -> client model replica updated
    local-train from theta_i^rx
    [uplink]    delta = theta_i - theta_i^rx (+ client EF residual)
                -> encode/decode over the packed wire buffer
    [hessian]   (optional) compressed Sophia h-EMA uplink
    server: participation-weighted mean of reconstructions; applies the
    aggregated model delta (or FedOpt on it) and broadcasts ONE common
    averaged-curvature payload back to the participants.

Round metrics always include exact per-stream byte counts.

Beyond the synchronous round, `comm_client_step` is the reusable
per-participant core (broadcast -> local train -> uplink encode): the
virtual-time scheduler (`repro.sched`) drives it one dispatch at a
time for asynchronous / semi-synchronous disciplines, with
`comm_runtime` supplying the per-stream (spec, compressor) handles —
memoized on the params' avals, so re-traces and scheduler dispatches
reuse one construction — and `wire_headers` fingerprinting the wire
layouts (including the flat client-state layout) for checkpoint
restore.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.comm import accounting, downlink as cdown, flat as cflat
from repro.comm.compressors import (make_compressor, make_stream_compressor,
                                    participation_indices,
                                    wants_error_feedback)
from repro.configs.base import AGGREGATORS, ATTACKS, FedConfig
from repro.core import sophia
from repro.core.gnb import gnb_estimate
from repro.obs import probes as obs_probes
from repro.obs.spans import phase
from repro.core.schedules import lr_at_round
from repro.robust import aggregators as robust_agg
from repro.robust import attacks as robust_attacks
from repro.utils.tree import (tree_count_params, tree_sq_norm,
                              tree_zeros_like)


#: rng salt of the per-round participation sample (shared by
#: `FedEngine._round_comm` and `FedEngine.round_participants`)
PARTICIPATION_SALT = 0x9A70


class CommRuntime(NamedTuple):
    """Trace-time comm-path handles: one (spec, compressor) per active
    stream.  ``spec`` (the uplink layout) doubles as the canonical
    geometry of all flat-resident engine state.  Per-stream packing
    geometry (``CommConfig.downlink_quant_block`` /
    ``hessian_quant_block``) means the streams may disagree on
    (rows, cols); they always share the flattened ``total`` coordinate
    order, so `repro.comm.flat.repack` moves buffers between
    geometries (a no-op in the traced graph when they agree)."""
    spec: Any                      # uplink layout == engine state layout
    comp: Any                      # uplink compressor
    spec_dn: Any = None
    comp_dn: Any = None
    spec_h: Any = None
    comp_h: Any = None

    @property
    def dn_on(self) -> bool:
        return self.comp_dn is not None

    @property
    def h_on(self) -> bool:
        return self.comp_h is not None


def _auto(s: NamedSharding, lead=()) -> NamedSharding:
    """``s``, with ``lead`` prepended to its spec, over the same devices
    with every mesh axis Auto: the round leaves its shardings to GSPMD
    (the launcher jits it with in/out shardings), and a constraint
    inside it may not name an Explicit axis."""
    m = s.mesh
    auto = Mesh(m.devices, m.axis_names,
                axis_types=(AxisType.Auto,) * len(m.axis_names))
    return NamedSharding(auto, P(*lead, *s.spec))


class FedEngine:
    def __init__(self, task, fed: FedConfig, gather_shardings=None,
                 carry_shardings=None):
        self.task = task
        self.fed = fed
        if fed.comm.hessian_enabled and not (
                fed.optimizer == "fed_sophia"
                and fed.persistent_client_state):
            raise ValueError(
                "the hessian comm stream aggregates the Sophia h-EMA: it "
                "requires optimizer='fed_sophia' with "
                "persistent_client_state=True")
        if fed.obs.probes and not (
                fed.optimizer == "fed_sophia"
                and fed.persistent_client_state):
            raise ValueError(
                "ObsConfig.probes reads the persistent Sophia m/h EMAs: "
                "it requires optimizer='fed_sophia' with "
                "persistent_client_state=True")
        rb = fed.robust
        if rb.aggregator not in AGGREGATORS:
            raise ValueError(
                f"unknown aggregator {rb.aggregator!r} (want one of "
                f"{AGGREGATORS})")
        if rb.attack not in ATTACKS:
            raise ValueError(
                f"unknown attack {rb.attack!r} (want one of {ATTACKS})")
        if not 0.0 <= rb.trim_fraction < 0.5:
            raise ValueError(
                f"trim_fraction={rb.trim_fraction} must be in [0, 0.5) "
                "(trimming both sides must leave a survivor)")
        for name in ("attack_fraction", "label_noise_fraction",
                     "label_noise_rate", "dropout_prob"):
            v = getattr(rb, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name}={v} must be in [0, 1]")
        # FSDP (sequential strategy): params are STORED sharded over the
        # data axes; each use must see them model-only-sharded, otherwise
        # GSPMD resolves the data-axis contraction by replicating the
        # batch-sharded activations instead (16x activation traffic).
        # gather_shardings = model-only NamedSharding pytree; constraining
        # params to it at each local step lowers to the per-step weight
        # all-gather that defines FSDP/ZeRO-3.
        self.gather_shardings = gather_shardings
        # The local loops carry a client's model and its Sophia m/h as
        # the model's leaves, unpacked once per client-round.  A packed
        # buffer's sharding does not survive that unpack (its cols are
        # no axis of a leaf), so a multi-device launch states how the
        # carried leaves are stored: one NamedSharding per leaf of ONE
        # client's model (the batched loops leave their leading client
        # axis to GSPMD).  None: no constraint.
        self.carry_shardings = carry_shardings
        # comm_runtime memoization: specs/compressors are pure static
        # metadata, keyed on the params' avals (the engine's CommConfig
        # is immutable, so it needs no key component)
        self._rt_cache: Dict[Any, CommRuntime] = {}
        # the runtime of the packed-resident state (set by init /
        # pack_state / restore shims): packed buffers carry no treedef,
        # so rounds over packed state read the layout from here
        self._packed_rt: CommRuntime | None = None

    # ------------------------------------------------- residency helpers
    @property
    def state_dtype(self):
        """Storage dtype of resident wire-layout state
        (`CommConfig.state_dtype`); in-round compute is always fp32."""
        return cflat.as_dtype(self.fed.comm.state_dtype)

    @property
    def moment_dtype(self):
        """Storage dtype of the (C, rows, cols) Sophia m stack
        (`CommConfig.moment_dtype`, "" -> `state_dtype`)."""
        return cflat.as_dtype(self.fed.comm.moment_dtype
                              or self.fed.comm.state_dtype)

    @property
    def hessian_dtype(self):
        """Storage dtype of the (C, rows, cols) Sophia h stack
        (`CommConfig.hessian_dtype`, "" -> `state_dtype`)."""
        return cflat.as_dtype(self.fed.comm.hessian_dtype
                              or self.fed.comm.state_dtype)

    @staticmethod
    def params_packed(params) -> bool:
        """Whether ``state["params"]`` is a packed (rows, cols) wire
        buffer (packed-resident mode, `pack_state`) rather than a
        parameter pytree.  Model pytrees are containers, never a bare
        rank-2 array, so the array rank is the discriminator."""
        return getattr(params, "ndim", None) == 2

    def _store(self, tree):
        """Scatter-side downcast: fp32 compute values -> the resident
        storage dtype.  No-op for fp32 state."""
        if tree is None:
            return None
        dt = self.state_dtype
        return jax.tree.map(lambda x: x.astype(dt), tree)

    def _store_opt(self, opt):
        """Scatter-side downcast of Sophia m/h to their per-buffer
        resident dtypes (`CommConfig.moment_dtype`/`hessian_dtype`,
        falling back to `state_dtype`).  No-op for fp32 state."""
        if opt is None:
            return None
        return sophia.SophiaState(m=opt.m.astype(self.moment_dtype),
                                  h=opt.h.astype(self.hessian_dtype))

    def _gathered(self, params):
        if self.gather_shardings is None:
            return params
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, _auto(s)),
            params, self.gather_shardings)

    def _carried(self, tree, batched: bool = False):
        """A local loop's leaf-layout carry (theta, m or h) held to
        `carry_shardings`; unchanged without them."""
        if self.carry_shardings is None:
            return tree
        lead = (P.UNCONSTRAINED,) if batched else ()
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, _auto(s, lead)),
            tree, self.carry_shardings)

    def _stateful(self) -> bool:
        """Persistent per-client Sophia state lives in the engine state
        dict (as (C, rows, cols) wire-layout buffers)."""
        return (self.fed.optimizer == "fed_sophia"
                and self.fed.persistent_client_state)

    def _value_and_grad(self, loss_fn, params, batch, rng=None):
        """value_and_grad with optional exact micro-batch accumulation."""
        n = self.fed.grad_microbatches
        if n <= 1:
            return jax.value_and_grad(loss_fn)(params, batch, rng)
        mb = jax.tree.map(
            lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)

        def body(acc, xs):
            i, b = xs
            r = jax.random.fold_in(rng, i) if rng is not None else None
            l, g = jax.value_and_grad(loss_fn)(params, b, r)
            acc = (acc[0] + l / n,
                   jax.tree.map(lambda a, gg: a + gg / n, acc[1], g))
            return acc, None

        init = (jnp.zeros((), jnp.float32), tree_zeros_like(params))
        (loss, grads), _ = jax.lax.scan(
            body, init, (jnp.arange(n), mb))
        return loss, grads

    def _flat_value_and_grad(self, t, batch, spec, rng=None):
        """The local step's loss/grad boundary.  ``t`` is the client's
        model in the leaf layout the local loop carries (each leaf in
        its resident dtype, unpacked once per client-round); the view
        fed to `value_and_grad` is a per-leaf cast to the model's own
        dtypes, gathered for FSDP.  Nothing is packed or unpacked
        here: the grads stay leaves in the model's dtypes.  Also
        returns the (gathered) view, which the GNB refresh reuses."""
        pg = self._gathered(cflat.model_view(t, spec))
        loss, grads = self._value_and_grad(self.task.loss, pg, batch, rng)
        return loss, grads, pg

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict[str, Any]:
        params = self.task.init(key)
        state: Dict[str, Any] = {"params": params,
                                 "round": jnp.zeros((), jnp.int32)}
        rt = self.comm_runtime(params)
        self._packed_rt = rt
        C = self.fed.num_clients
        comm = self.fed.comm
        dt = self.state_dtype
        if self._stateful():
            # per-client Sophia EMAs, stored directly in wire layout
            # (and in the resident storage dtype) — the local loop and
            # the hessian stream consume them with zero conversion
            state["client_opt"] = sophia.SophiaState(
                m=cflat.zeros(rt.spec, (C,), self.moment_dtype),
                h=cflat.zeros(rt.spec, (C,), self.hessian_dtype))
        if self.fed.optimizer in ("fedadam", "fedyogi"):
            state["server_opt"] = {"m": tree_zeros_like(params),
                                   "v": tree_zeros_like(params)}
        if wants_error_feedback(comm):
            # per-client error-feedback residual, stored in uplink
            # wire layout
            state["comm_ef"] = cflat.zeros(rt.spec, (C,), dt)
        if comm.downlink_enabled:
            # per-client last-received model replicas (+ server-side
            # EF), stored in the downlink stream's own layout
            state.update(cdown.init_state(
                comm, rt.spec_dn,
                cflat.repack(cflat.pack(params, rt.spec, dtype=dt),
                             rt.spec, rt.spec_dn),
                C, dtype=dt))
        return state

    def restore_params(self, state, params) -> Dict[str, Any]:
        """Swap restored params into ``state``, rebuilding the
        wire-layout client state that references the model: downlink
        replicas must re-sync to the restored params (a delta-coded
        broadcast against the old init would be garbage) and EF
        residuals restart at zero."""
        state = {**state, "params": params}
        rt = self.comm_runtime(params)
        self._packed_rt = rt
        comm = self.fed.comm
        if "comm_ef" in state:
            state["comm_ef"] = tree_zeros_like(state["comm_ef"])
        if comm.downlink_enabled:
            state.update(cdown.init_state(
                comm, rt.spec_dn,
                cflat.repack(cflat.pack(params, rt.spec,
                                        dtype=self.state_dtype),
                             rt.spec, rt.spec_dn),
                self.fed.num_clients, dtype=self.state_dtype))
        return state

    # ------------------------------------------- packed-resident boundary
    def pack_state(self, state) -> Dict[str, Any]:
        """Re-lay ``state["params"]`` (and the FedOpt server m/v) as
        wire buffers so the state is device-resident in wire layout
        BETWEEN rounds too: `round` then consumes and returns packed
        buffers with no per-round pack/unpack bracket.  Idempotent.
        The pytree reappears only through `unpack_params` /
        `unpack_state` (eval/checkpoint boundaries)."""
        params = state["params"]
        if self.params_packed(params):
            return state
        rt = self.comm_runtime(params)
        self._packed_rt = rt
        dt = self.state_dtype
        out = {**state, "params": cflat.pack(params, rt.spec, dtype=dt)}
        if "server_opt" in state:
            out["server_opt"] = {
                k: cflat.pack(v, rt.spec, dtype=dt)
                for k, v in state["server_opt"].items()}
        return out

    def unpack_state(self, state) -> Dict[str, Any]:
        """Inverse of `pack_state`: materialize the params (and FedOpt
        server m/v) pytrees.  Idempotent on tree-resident state."""
        params = state["params"]
        if not self.params_packed(params):
            return state
        spec = self._require_packed_rt().spec
        out = {**state, "params": cflat.unpack(params, spec)}
        if "server_opt" in state:
            out["server_opt"] = {
                k: cflat.unpack(v, spec)
                for k, v in state["server_opt"].items()}
        return out

    def unpack_params(self, state):
        """The params pytree view of ``state`` regardless of residency
        — the eval/checkpoint shim of the packed-resident engine."""
        params = state["params"]
        if not self.params_packed(params):
            return params
        return cflat.unpack(params, self._require_packed_rt().spec)

    def _require_packed_rt(self) -> CommRuntime:
        if self._packed_rt is None:
            raise ValueError(
                "packed-resident state reached the engine before its "
                "layout was established — create the state with this "
                "engine's init()+pack_state() (or restore through its "
                "shims) so the packed spec is known")
        return self._packed_rt

    def runtime_for(self, params) -> CommRuntime:
        """`comm_runtime` for either residency: pytree params build
        (memoized) specs; packed params read the layout recorded by
        `pack_state`."""
        if self.params_packed(params):
            return self._require_packed_rt()
        return self.comm_runtime(params)

    def num_params(self, state) -> int:
        """True model coordinate count under either residency (the
        packed buffer's pad tail never counts)."""
        params = state["params"]
        if self.params_packed(params):
            return self._require_packed_rt().spec.total
        return tree_count_params(params)

    def round_fn(self, *, donate: bool = True):
        """The jitted round entry point.

        With ``donate=True`` the state argument is donated to XLA:
        on donation-capable backends every resident buffer — packed
        params, the (C, rows, cols) Sophia m/h stacks, EF residuals,
        downlink replicas — is updated IN PLACE (zero per-round device
        copies of client state).  Donation contract: the caller must
        not reuse the state object it passed in (its buffers are
        invalidated); rebind the return value, as in
        ``state, metrics = round_fn(state, batches, rng)``.
        """
        if donate:
            return jax.jit(self.round, donate_argnums=(0,))
        return jax.jit(self.round)

    # ------------------------------------------------------ comm plumbing
    def uses_direct_path(self) -> bool:
        """Whether `round` takes the direct client-mean path (lossless
        identity, full participation, no extra streams) instead of the
        delta-space comm path."""
        comm = self.fed.comm
        C = self.fed.num_clients
        return (comm.lossless and comm.num_participants(C) == C
                and not comm.multi_stream)

    def round_participants(self, rng) -> jnp.ndarray:
        """The client ids `round(state, batches, rng)` trains — the
        direct path trains everyone; the comm path gathers the
        participation sample.  The single source of truth for
        schedulers/reports that need the cohort outside the jit."""
        C = self.fed.num_clients
        if self.uses_direct_path():
            return jnp.arange(C)
        return participation_indices(
            jax.random.fold_in(rng, PARTICIPATION_SALT
                               + self.fed.comm.seed),
            C, self.fed.comm.num_participants(C))

    def comm_runtime(self, params) -> CommRuntime:
        """The per-stream (spec, compressor) handles — trace-time only
        (specs/compressors hold no arrays), memoized on the params'
        avals so every round trace, scheduler dispatch and init/restore
        shares one construction instead of re-flattening the pytree."""
        key = cflat.aval_key(params)
        rt = self._rt_cache.get(key)
        if rt is not None:
            return rt
        comm = self.fed.comm
        spec = cflat.flat_spec(params, cols=comm.quant_block)
        kw: Dict[str, Any] = {}
        if comm.downlink_enabled:
            s = cflat.flat_spec(
                params, cols=comm.stream("downlink").quant_block)
            kw.update(spec_dn=s,
                      comp_dn=make_stream_compressor(comm, "downlink", s))
        if comm.hessian_enabled:
            s = cflat.flat_spec(
                params, cols=comm.stream("hessian").quant_block)
            kw.update(spec_h=s,
                      comp_h=make_stream_compressor(comm, "hessian", s))
        rt = CommRuntime(spec=spec, comp=make_compressor(comm, spec), **kw)
        self._rt_cache[key] = rt
        return rt

    def wire_headers(self, params) -> Dict[str, Dict[str, Any]]:
        """Versioned wire-layout headers of every active stream — plus
        the ``client_state`` layout fingerprint of the flat-resident
        per-client optimizer state — as plain dicts.  Store them in
        checkpoint manifests; `repro.comm.flat.check_headers` rejects a
        restore whose comm/EF/client state was written under a
        different layout."""
        rt = self.runtime_for(params)
        out = {"uplink": rt.comp.header().to_dict()}
        if rt.dn_on:
            out["downlink"] = rt.comp_dn.header().to_dict()
        if rt.h_on:
            out["hessian"] = rt.comp_h.header().to_dict()
        if self._stateful():
            # the Sophia m/h buffers are stored in wire layout (and in
            # the resident storage dtype): a restore under a different
            # packing geometry or dtype would silently re-interpret
            # the rows
            out["client_state"] = cflat.Header(
                compressor="identity", total=rt.spec.total,
                quant_block=rt.spec.cols,
                state_dtype=self.fed.comm.state_dtype).to_dict()
        return out

    def comm_client_step(self, rt: CommRuntime, theta, theta_dn,
                         round_idx, lr, opt, ef_i, dnm_i, dnef_i, batch,
                         crng):
        """One participant's comm-path step — the reusable core of
        `_round_comm`, also driven one dispatch at a time by the
        virtual-time scheduler (`repro.sched`):

        downlink broadcast (replica update) -> local training from the
        received model -> fused uplink delta encode/decode [-> hessian-
        EMA encode/decode].

        Everything but the local loop stays in wire layout: ``theta``
        is the packed server model (canonical ``rt.spec`` geometry;
        ``theta_dn`` the same coordinates in the downlink geometry, None
        when that stream is off), the received replica *is* the
        local-training start state, and the uplink delta is a flat
        subtraction inside `Compressor.encode_delta`.  Gathered
        resident rows flow in UN-upcast (`CommConfig.state_dtype`): the
        kernels upcast loads to fp32 in-VMEM and jnp promotion covers
        the flat arithmetic; callers downcast the returned rows on the
        scatter back (`_store`).  For fp32 state every cast is a
        no-op.

        Returns ``(xhat, stat, ef_new, opt_new, loss, dnm_new,
        dnef_new, h_hat, h_stat)`` with ``None`` for inactive pieces.
        """
        if rt.dn_on:
            with phase("wire"):
                dnm_i, dnef_i = cdown.broadcast(
                    rt.comp_dn, jax.random.fold_in(crng, 0xD0),
                    theta_dn, dnm_i, dnef_i)
                start = cflat.repack(dnm_i, rt.spec_dn, rt.spec)
        else:
            start = theta
        t_i, opt_i, loss = self._local_update_flat(
            rt.spec, start, opt, batch, crng, round_idx, lr)
        with phase("wire"):
            xhat, stat, ef_new = rt.comp.encode_delta(
                jax.random.fold_in(crng, 0xC0), t_i, start, ef_i)
        h_hat = h_stat = None
        if rt.h_on:
            # opt.h is already a wire buffer; only a geometry re-lay
            # (if the hessian stream packs its own quant_block) stands
            # between it and the compressor.  The explicit fp32 upcast
            # keeps the wire semantics (scales, payload dtype) fixed
            # when the resident EMAs are stored bf16 (no-op for fp32).
            with phase("wire"):
                h_hat, h_stat = rt.comp_h.roundtrip(
                    jax.random.fold_in(crng, 0x4E),
                    cflat.repack(opt_i.h, rt.spec,
                                 rt.spec_h).astype(jnp.float32))
        return (xhat, stat, ef_new, opt_i, loss,
                dnm_i if rt.dn_on else None, dnef_i, h_hat, h_stat)

    def comm_client_step_batched(self, rt: CommRuntime, theta, theta_dn,
                                 round_idx, lr, opts, efs, dnms, dnefs,
                                 batches, crngs):
        """`comm_client_step` for the whole cohort in one pass — the
        parallel strategy's client step, and the scheduler's batched
        dispatch.

        Every per-client buffer argument carries a leading client axis
        N (None when that piece is off); ``theta`` / ``theta_dn`` stay
        the one shared packed server model; ``crngs``: (N,) per-client
        rng keys.  Each stage — downlink broadcast, the local Sophia
        scan, uplink encode, the hessian round-trip — runs as ONE
        client-batched Pallas launch over the (N, rows, cols) stacks
        (`repro.kernels`) instead of N per-client launches, and is
        bitwise equal to ``jax.vmap(comm_client_step)`` over the same
        rows (tests/test_residency.py pins it).  Returns the same
        9-tuple as `comm_client_step`, stacked along clients.

        Dispatch groups larger than `SchedConfig.dispatch_chunk`
        (when set) run as a lax-driven sequence of fixed-size chunks
        through this same batched path — see
        `_comm_client_step_chunked`; each chunk is bitwise the
        unchunked batched step over its rows.
        """
        chunk = self.fed.sched.dispatch_chunk
        if 0 < chunk < int(crngs.shape[0]):
            return self._comm_client_step_chunked(
                rt, theta, theta_dn, round_idx, lr, opts, efs, dnms,
                dnefs, batches, crngs, chunk)
        if rt.dn_on:
            with phase("wire"):
                keys = jax.vmap(
                    lambda k: jax.random.fold_in(k, 0xD0))(crngs)
                dnms, dnefs = cdown.broadcast_batched(
                    rt.comp_dn, keys, theta_dn, dnms, dnefs)
                starts = jax.vmap(
                    lambda b: cflat.repack(b, rt.spec_dn, rt.spec))(dnms)
        else:
            starts = theta
        t, opt, losses = self._local_update_flat_batched(
            rt.spec, starts, opts, batches, crngs, round_idx, lr)
        # the draws after the local loop wait for it: XLA would
        # otherwise draw the uplink noise beside the downlink's, before
        # the loop, and hold a model-sized buffer through it
        crngs, t = jax.lax.optimization_barrier((crngs, t))
        with phase("wire"):
            xhat, stat, ef_new = rt.comp.encode_delta_batched(
                jax.vmap(lambda k: jax.random.fold_in(k, 0xC0))(crngs),
                t, starts, efs)
        h_hat = h_stat = None
        if rt.h_on:
            with phase("wire"):
                h_rows = jax.vmap(
                    lambda hrow: cflat.repack(hrow, rt.spec, rt.spec_h)
                )(opt.h).astype(jnp.float32)
                h_hat, h_stat = rt.comp_h.roundtrip_batched(
                    jax.vmap(lambda k: jax.random.fold_in(k, 0x4E))(
                        crngs), h_rows)
        return (xhat, stat, ef_new, opt, losses,
                dnms if rt.dn_on else None, dnefs, h_hat, h_stat)

    def _comm_client_step_chunked(self, rt: CommRuntime, theta, theta_dn,
                                  round_idx, lr, opts, efs, dnms, dnefs,
                                  batches, crngs, chunk: int):
        """Large-group dispatch: run an N-client group as a
        `lax.map`-driven sequence of fixed-size ``chunk`` launches of
        `comm_client_step_batched` (the autotuned per-chunk kernel
        geometry — `kernels.tuning` keys on the chunk's client count),
        plus one direct tail call for the N % chunk remainder.

        Every per-client stack is reshaped (N, ...) -> (G, chunk, ...)
        so the compiled graph holds ONE chunk-sized program body
        regardless of G; the shared ``theta``/``theta_dn`` broadcast
        into the body unchanged.  Per-chunk results are bitwise the
        unchunked batched step over the same rows (each stage is
        elementwise per client row), pinned by
        tests/test_residency.py."""
        n = int(crngs.shape[0])
        g = n // chunk
        per_client = (opts, efs, dnms, dnefs, batches, crngs)
        head = jax.tree.map(
            lambda x: x[:g * chunk].reshape((g, chunk) + x.shape[1:]),
            per_client)
        outs = jax.lax.map(
            lambda c: self.comm_client_step_batched(
                rt, theta, theta_dn, round_idx, lr, *c), head)
        outs = jax.tree.map(
            lambda x: x.reshape((g * chunk,) + x.shape[2:]), outs)
        if n % chunk:
            rest = jax.tree.map(lambda x: x[g * chunk:], per_client)
            tail = self.comm_client_step_batched(
                rt, theta, theta_dn, round_idx, lr, *rest)
            outs = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), outs, tail)
        return outs

    # ------------------------------------------- local client training (flat)
    # Every local loop crosses between the packed layout and the
    # model's leaf layout once per client-round: theta (in `fed.grad`)
    # and the Sophia m/h (in `fed.sophia`) are unpacked into leaves in
    # their resident dtypes at the loop's start and packed at its end.
    # The scan carries leaves, so gradients, GNB estimates and the
    # per-leaf Sophia kernel launches never touch the packed layout;
    # the wires, combine and resident state see the same packed
    # buffers.  Every op in the loop is per coordinate, so op by op the
    # results are bitwise those of a loop that packed at every step
    # (compiled, XLA:CPU may contract multiply-adds across the new
    # fusions differently: last-ulp differences).
    def _local_sophia_flat(self, spec, theta, m, h, batch, round_idx, rng,
                           lr):
        """Flat-resident Sophia local loop for one client: theta/m/h
        arrive and leave as packed (rows, cols) wire buffers and cross
        into the leaf layout once (see above)."""
        fed = self.fed
        task = self.task
        with phase("grad"):
            t0 = self._carried(cflat.unpack_leaves(theta, spec))
        with phase("sophia"):
            m0 = self._carried(cflat.unpack_leaves(m, spec))
            h0 = self._carried(cflat.unpack_leaves(h, spec))

        # round mode (Alg. 1 line 9 literal: refresh when k mod tau == 0):
        # the GNB estimate uses the round-start params, so it hoists out of
        # the local-iteration scan — one estimator call per refresh round
        # instead of a lax.cond in every local step.
        round_mode = fed.hessian_every_unit == "round"
        if round_mode:
            do_h_round = (round_idx % fed.tau) == 0
            with phase("gnb"):
                pg0 = self._gathered(cflat.model_view(t0, spec))
                h_hat_round = jax.lax.cond(
                    do_h_round,
                    lambda: gnb_estimate(
                        task, pg0, batch,
                        jax.random.fold_in(rng, 0x7FFFFFFF),
                        vg_fn=self._value_and_grad),
                    lambda: tree_zeros_like(pg0))

        def step(carry, j):
            t, m_, h_ = carry
            with phase("grad"):
                loss, g, pg = self._flat_value_and_grad(t, batch, spec)
            if round_mode:
                do_h = do_h_round & (j == 0)   # EMA applied once per refresh
                hh = h_hat_round
            else:
                tstep = round_idx * fed.local_iters + j
                do_h = (tstep % fed.tau) == 0
                rng_j = jax.random.fold_in(rng, j)
                with phase("gnb"):
                    hh = jax.lax.cond(
                        do_h,
                        lambda: gnb_estimate(task, pg, batch, rng_j,
                                             vg_fn=self._value_and_grad),
                        lambda: tree_zeros_like(pg))
            with phase("sophia"):
                t, m_, h_ = sophia.sophia_step_flat(
                    t, m_, h_, g, hh, do_h,
                    lr=lr, beta1=fed.beta1, beta2=fed.beta2, rho=fed.rho,
                    eps=fed.eps, weight_decay=fed.weight_decay,
                    use_pallas=fed.use_pallas)
            return tuple(map(self._carried, (t, m_, h_))), loss

        (t, m, h), losses = jax.lax.scan(
            step, (t0, m0, h0), jnp.arange(fed.local_iters))
        with phase("grad"):
            theta = cflat.pack_leaves(t, spec)
        with phase("sophia"):
            m, h = cflat.pack_leaves(m, spec), cflat.pack_leaves(h, spec)
        return theta, m, h, jnp.mean(losses)

    def _local_sophia_flat_batched(self, spec, theta, m, h, batches,
                                   round_idx, rngs, lr):
        """`_local_sophia_flat` for N clients at once: ONE scan over
        local iterations whose body vmaps the loss/grad boundary and
        feeds the leaves (each with a leading client axis N) to a
        single batched Sophia kernel launch per leaf and iteration.
        ``theta`` may be the shared (rows, cols) start model or a
        per-client (N, rows, cols) stack (downlink replicas); m/h are
        (N, rows, cols).  scan(vmap(grad)) computes exactly what
        vmap(scan(grad)) would, so this is bitwise equal to vmapping
        the per-client loop."""
        fed = self.fed
        task = self.task
        N = rngs.shape[0]
        shared = theta.ndim == 2
        with phase("grad"):
            ts = self._carried(cflat.unpack_leaves(theta, spec),
                               batched=not shared)
        with phase("sophia"):
            m0 = self._carried(cflat.unpack_leaves(m, spec), batched=True)
            h0 = self._carried(cflat.unpack_leaves(h, spec), batched=True)

        round_mode = fed.hessian_every_unit == "round"
        if round_mode:
            do_h_round = (round_idx % fed.tau) == 0
            with phase("gnb"):
                def estimate(pg, b, r):
                    return gnb_estimate(
                        task, pg, b, jax.random.fold_in(r, 0x7FFFFFFF),
                        vg_fn=self._value_and_grad)

                if shared:
                    # shared start model: ONE view feeds every client's
                    # estimator (what vmap hoists anyway)
                    pg0 = self._gathered(cflat.model_view(ts, spec))

                    def gnb_round():
                        return jax.vmap(functools.partial(estimate, pg0))(
                            batches, rngs)
                else:
                    def gnb_round():
                        return jax.vmap(lambda t, b, r: estimate(
                            self._gathered(cflat.model_view(t, spec)), b, r)
                        )(ts, batches, rngs)

                def no_gnb():
                    return jax.tree_util.tree_unflatten(spec.treedef, [
                        jnp.zeros((N,) + shp, dt)
                        for shp, dt in zip(spec.shapes, spec.dtypes)])
                h_hat_round = jax.lax.cond(do_h_round, gnb_round, no_gnb)

        def step(carry, j):
            t, m_, h_ = carry
            with phase("grad"):
                losses, g, pgs = jax.vmap(
                    lambda tt, bb: self._flat_value_and_grad(tt, bb, spec)
                )(t, batches)
            if round_mode:
                do_h = do_h_round & (j == 0)
                hh = h_hat_round
            else:
                tstep = round_idx * fed.local_iters + j
                do_h = (tstep % fed.tau) == 0
                with phase("gnb"):
                    hh = jax.lax.cond(
                        do_h,
                        lambda: jax.vmap(
                            lambda pg, bb, r: gnb_estimate(
                                task, pg, bb, jax.random.fold_in(r, j),
                                vg_fn=self._value_and_grad)
                        )(pgs, batches, rngs),
                        lambda: tree_zeros_like(pgs))
            with phase("sophia"):
                t, m_, h_ = sophia.sophia_step_flat(
                    t, m_, h_, g, hh, do_h,
                    lr=lr, beta1=fed.beta1, beta2=fed.beta2, rho=fed.rho,
                    eps=fed.eps, weight_decay=fed.weight_decay,
                    use_pallas=fed.use_pallas, batched=True)
            return tuple(self._carried(x, batched=True)
                         for x in (t, m_, h_)), losses

        with phase("grad"):
            t0 = (self._carried(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (N,) + x.shape), ts),
                batched=True) if shared else ts)
        (t, m, h), losses = jax.lax.scan(
            step, (t0, m0, h0), jnp.arange(fed.local_iters))
        with phase("grad"):
            theta = cflat.pack_leaves(t, spec)
        with phase("sophia"):
            m, h = cflat.pack_leaves(m, spec), cflat.pack_leaves(h, spec)
        return theta, m, h, jnp.mean(losses, axis=0)

    @staticmethod
    def _sgd_step(t, g, lr):
        """theta - lr * g per leaf, in fp32, stored in theta's dtype."""
        return jax.tree.map(
            lambda x, gg: (x - lr * gg.astype(jnp.float32)).astype(x.dtype),
            t, g)

    def _local_sgd_flat(self, spec, theta, batch, rng, lr):
        """Flat-resident local SGD: the same once-per-client-round
        crossing as the Sophia loop; the update is a per-leaf axpy."""
        def step(t, j):
            with phase("grad"):
                loss, g, _ = self._flat_value_and_grad(t, batch, spec)
            return self._carried(self._sgd_step(t, g, lr)), loss

        with phase("grad"):
            t0 = self._carried(cflat.unpack_leaves(theta, spec))
        t, losses = jax.lax.scan(step, t0, jnp.arange(self.fed.local_iters))
        with phase("grad"):
            theta = cflat.pack_leaves(t, spec)
        return theta, jnp.mean(losses)

    def _local_sgd_flat_batched(self, spec, theta, batches, rngs, lr):
        """`_local_sgd_flat` for N clients at once (see
        `_local_sophia_flat_batched` for the scan/vmap layout)."""
        N = rngs.shape[0]

        def step(t, j):
            with phase("grad"):
                losses, g, _ = jax.vmap(
                    lambda tt, bb: self._flat_value_and_grad(tt, bb, spec)
                )(t, batches)
            t = self._carried(self._sgd_step(t, g, lr), batched=True)
            return t, losses

        with phase("grad"):
            t0 = cflat.unpack_leaves(theta, spec)
            if theta.ndim == 2:
                t0 = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (N,) + x.shape), t0)
            t0 = self._carried(t0, batched=True)
        t, losses = jax.lax.scan(step, t0, jnp.arange(self.fed.local_iters))
        with phase("grad"):
            theta = cflat.pack_leaves(t, spec)
        return theta, jnp.mean(losses, axis=0)

    def _local_sgd(self, params, batch, rng, lr):
        """Pytree local SGD — the reference twin of `_local_sgd_flat`
        (bit-identical per coordinate for fp32 models), kept for the
        manual-recomputation equivalence tests."""
        fed = self.fed
        task = self.task

        def step(p, j):
            loss, grads = self._value_and_grad(
                task.loss, self._gathered(p), batch, None)
            p = jax.tree.map(lambda t, g: (t - lr * g).astype(t.dtype),
                             p, grads)
            return p, loss

        params, losses = jax.lax.scan(step, params,
                                      jnp.arange(fed.local_iters))
        return params, jnp.mean(losses)

    def _local_done(self, params, batch, rng, lr):
        """DONE baseline: Richardson iteration for d ~= H^-1 g (HVPs).

        Richardson requires alpha * (lmax + damping) < 2; non-IID clients
        have wildly different local curvature, so alpha is set per client
        from a short power-iteration estimate of lmax.  Inherently a
        pytree algorithm (nested jvp over the loss), so the flat engine
        brackets it with one unpack/pack pair per client round.
        """
        fed = self.fed
        task = self.task
        params_g = self._gathered(params)
        loss, g = jax.value_and_grad(task.loss)(params_g, batch, None)
        grad_fn = lambda p: jax.grad(task.loss)(p, batch, None)

        def hvp(d):
            return jax.jvp(grad_fn, (params_g,), (d,))[1]

        def power(v, _):
            hv = hvp(v)
            nrm = jnp.sqrt(tree_sq_norm(hv)) + 1e-12
            return jax.tree.map(lambda x: x / nrm, hv), nrm

        v0 = jax.tree.map(
            lambda x: x / (jnp.sqrt(tree_sq_norm(g)) + 1e-12), g)
        _, norms = jax.lax.scan(power, v0, None, length=5)
        lmax = norms[-1]
        alpha = 0.9 / (lmax + fed.done_damping)

        def rich(d, _):
            hd = hvp(d)
            # damped Richardson: d += alpha * (g - (H + delta I) d)
            d = jax.tree.map(
                lambda dd, gg, hh: dd + alpha
                * (gg - hh - fed.done_damping * dd), d, g, hd)
            return d, None

        d, _ = jax.lax.scan(rich, tree_zeros_like(params), None,
                            length=fed.done_richardson_iters)
        # trust region: indefinite local Hessians can still blow the
        # Richardson solve up on pathological non-IID clients — cap the
        # Newton step at a multiple of the gradient norm.
        gn = jnp.sqrt(tree_sq_norm(g))
        dn = jnp.sqrt(tree_sq_norm(d))
        cap = jnp.minimum(1.0, 10.0 * gn / (dn + 1e-12))
        new = jax.tree.map(lambda t, dd: (t - lr * cap * dd).astype(t.dtype),
                           params, d)
        return new, loss

    # ------------------------------------------------- one client, dispatch
    def _local_update_flat(self, spec, theta, opt, batch, crng, round_idx,
                           lr):
        """One client's local training over wire-layout state.

        theta: (rows, cols) packed start model; opt: `SophiaState` of
        (rows, cols) buffers or None.  Returns (new_theta,
        new_opt_or_None, mean_loss); new_opt is None for optimizers
        without persistent per-client state.
        """
        fed = self.fed
        if fed.optimizer == "fed_sophia":
            if opt is None:   # stateless: fresh EMAs each round
                opt = sophia.SophiaState(m=cflat.zeros(spec),
                                         h=cflat.zeros(spec))
            t, m, h, loss = self._local_sophia_flat(
                spec, theta, opt.m, opt.h, batch, round_idx, crng, lr)
            opt = sophia.SophiaState(m=m, h=h)
            return t, (opt if fed.persistent_client_state else None), loss
        if fed.optimizer in ("fedavg", "fedadam", "fedyogi"):
            t, loss = self._local_sgd_flat(spec, theta, batch, crng, lr)
            return t, None, loss
        if fed.optimizer == "done":
            p, loss = self._local_done(cflat.unpack(theta, spec), batch,
                                       crng, lr)
            return cflat.pack(p, spec), None, loss
        raise ValueError(fed.optimizer)

    def _local_update_flat_batched(self, spec, theta, opts, batches,
                                   crngs, round_idx, lr):
        """`_local_update_flat` for the whole cohort: per-client state
        carries a leading client axis N; ``theta`` may be the shared
        (rows, cols) start model or a per-client (N, rows, cols)
        stack.  fed_sophia / fedavg-family run the batched flat loops
        (one kernel launch per iteration for the whole cohort); done
        is inherently a pytree algorithm, so it stays a vmap of the
        per-client step."""
        fed = self.fed
        N = crngs.shape[0]
        if fed.optimizer == "fed_sophia":
            if opts is None:   # stateless: fresh EMAs each round
                opts = sophia.SophiaState(m=cflat.zeros(spec, (N,)),
                                          h=cflat.zeros(spec, (N,)))
            t, m, h, loss = self._local_sophia_flat_batched(
                spec, theta, opts.m, opts.h, batches, round_idx, crngs,
                lr)
            opt = sophia.SophiaState(m=m, h=h)
            return t, (opt if fed.persistent_client_state else None), loss
        if fed.optimizer in ("fedavg", "fedadam", "fedyogi"):
            t, loss = self._local_sgd_flat_batched(spec, theta, batches,
                                                   crngs, lr)
            return t, None, loss
        theta_ax = None if theta.ndim == 2 else 0
        return jax.vmap(
            lambda t, b, r: self._local_update_flat(
                spec, t, None, b, r, round_idx, lr),
            in_axes=(theta_ax, 0, 0))(theta, batches, crngs)

    def _apply_aggregate(self, state, agg):
        """Server step on the aggregated params-space model `agg`."""
        if self.fed.optimizer in ("fedadam", "fedyogi"):
            return self._server_opt_update(state, agg)
        return {**state, "params": agg}

    def _apply_aggregate_flat(self, state, agg_flat):
        """`_apply_aggregate` for packed-resident state: the server
        model update never leaves wire layout (stored back in the
        resident dtype)."""
        if self.fed.optimizer in ("fedadam", "fedyogi"):
            return self._server_opt_update_flat(state, agg_flat)
        return {**state,
                "params": agg_flat.astype(state["params"].dtype)}

    # ------------------------------------------------------------- the round
    def round(self, state, batches, rng):
        """batches: pytree with leading client axis C. Returns (state, metrics).

        Accepts either residency: tree-resident state (`init`) or
        packed-resident state (`pack_state`) — the latter skips the
        per-round params pack/unpack bracket entirely.  Jit through
        `round_fn` to opt into buffer donation (in-place resident
        state)."""
        fed = self.fed
        comm = fed.comm
        round_idx = state["round"]
        lr = lr_at_round(fed, round_idx)
        C = fed.num_clients
        S = comm.num_participants(C)
        rt = self.runtime_for(state["params"])
        client_rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
            jnp.arange(C))

        if self.uses_direct_path():
            # lossless identity at full participation, no extra streams:
            # aggregate client params directly — bit-identical to the
            # pre-comm engine
            state, loss = self._round_direct(state, batches, client_rngs,
                                             round_idx, lr, rt)
        else:
            state, loss = self._round_comm(state, batches, client_rngs,
                                           round_idx, lr, rng, rt)

        state = {**state, "round": round_idx + 1}
        n = self.num_params(state)
        wire = accounting.round_bytes(comm, n, C)
        metrics = {"loss": loss, "lr": lr,
                   "participants": jnp.asarray(S, jnp.float32)}
        for k in ("uplink_bytes", "downlink_bytes", "hessian_uplink_bytes",
                  "hessian_downlink_bytes", "total_bytes"):
            metrics[k] = jnp.asarray(wire[k], jnp.float32)
        if fed.obs.probes:
            # Sophia health probes, computed INSIDE this jit: pure
            # elementwise/reduction reads of the state the round just
            # produced — no layout ops, no extra host syncs, and the
            # returned state is bitwise identical to the unprobed round
            # (pinned by tests/test_obs.py)
            metrics.update(obs_probes.sophia_health(
                state["client_opt"], round_idx, fed, rt.spec.total))
        return state, metrics

    def probe_metrics(self, state) -> Dict[str, jnp.ndarray]:
        """The Sophia health probes of `repro.obs.probes` for a state
        OUTSIDE the round jit — the virtual-time scheduler applies
        aggregates through its own jits, so it probes the post-apply
        state with this (jittable; requires the stateful engine)."""
        if not self._stateful():
            raise ValueError(
                "probe_metrics reads the persistent Sophia m/h EMAs: "
                "it requires optimizer='fed_sophia' with "
                "persistent_client_state=True")
        rt = self.runtime_for(state["params"])
        return obs_probes.sophia_health(
            state["client_opt"], state["round"] - 1, self.fed,
            rt.spec.total)

    def _round_direct(self, state, batches, client_rngs, round_idx, lr, rt):
        """Original aggregation: server model <- mean of client params —
        computed entirely in wire layout (ONE pack of the server model
        in, ONE unpack of the aggregate out — and ZERO of either in
        packed-resident mode).  Resident rows feed the local loops in
        their storage dtype (the kernels upcast loads in-VMEM) and
        downcast on the store back (no-ops for fp32 state)."""
        fed = self.fed
        spec = rt.spec
        params = state["params"]
        C = fed.num_clients
        stateful = self._stateful()
        packed = self.params_packed(params)
        theta = (params.astype(jnp.float32) if packed
                 else cflat.pack(params, spec))
        opts = state.get("client_opt") if stateful else None

        # adversarial fleet (repro.robust): both knobs are static
        # config — when off, neither branch below enters the traced
        # graph and the round is bitwise the historical mean path
        rb = fed.robust
        attack_on = robust_attacks.wire_attack_active(rb, C)
        robust_on = robust_agg.resolve(rb, C) != "mean"
        adversarial = attack_on or robust_on

        if fed.strategy == "parallel":
            # the whole cohort steps through the batched flat loop —
            # one kernel launch per local iteration over (C, rows,
            # cols) stacks
            new_t, new_opt, losses = self._local_update_flat_batched(
                spec, theta, opts, batches, client_rngs, round_idx, lr)
            if not adversarial:
                with phase("combine"):
                    agg_flat = jnp.mean(new_t, axis=0)
        elif adversarial:
            # robust/attacked sequential: the scan stacks each
            # client's params (same memory as the parallel stack —
            # trimming needs the whole cohort at once)
            def scan_collect(_, xs):
                opt, batch, crng = xs
                t_i, opt_i, loss = self._local_update_flat(
                    spec, theta, opt, batch, crng, round_idx, lr)
                return 0, (t_i, opt_i, loss)
            _, (new_t, new_opt, losses) = jax.lax.scan(
                scan_collect, 0, (opts, batches, client_rngs))
        else:
            def scan_body(acc, xs):
                opt, batch, crng = xs
                t_i, opt_i, loss = self._local_update_flat(
                    spec, theta, opt, batch, crng, round_idx, lr)
                with phase("combine"):
                    acc = acc + t_i / C
                return acc, (opt_i, loss)
            agg_flat, (new_opt, losses) = jax.lax.scan(
                scan_body, jnp.zeros_like(theta),
                (opts, batches, client_rngs))

        with phase("combine"):
            if adversarial:
                # the direct path carries whole client models; attacks
                # and robust combination are defined on the
                # *contribution delta* vs the round-start model —
                # equivalent to the wire transforms of the comm path on
                # an uncompressed uplink
                deltas = new_t - theta
                if attack_on:
                    deltas = robust_attacks.attack_wires(
                        rb, deltas,
                        jnp.asarray(robust_attacks.byzantine_mask(rb, C)),
                        client_rngs[0])
                agg_flat = theta + robust_agg.aggregate_stack(
                    rb, deltas, jnp.ones((C,), jnp.float32),
                    normalize=True, use_pallas=fed.comm.use_pallas)
            if packed:
                state = self._apply_aggregate_flat(state, agg_flat)
            else:
                state = self._apply_aggregate(state,
                                              cflat.unpack(agg_flat, spec))
        if stateful:
            with phase("rows"):
                state = {**state, "client_opt": self._store_opt(new_opt)}
        return state, jnp.mean(losses)

    def _round_comm(self, state, batches, client_rngs, round_idx, lr, rng,
                    rt):
        """Multi-stream delta-space round (docs/architecture.md):

        1. [downlink] each participant receives the compressed delta of
           the server model vs its own last-received replica (server-side
           per-client EF) and trains from what it actually received;
        2. [uplink] its model delta vs that replica is compressed (with
           optional client EF), decoded server-side, and the decoded wire
           payloads are aggregated weighted by participation;
        3. [hessian] optionally, its Sophia h-EMA is compressed and
           uploaded; the server averages the curvature and broadcasts
           one common payload back, re-syncing the participants' h.

        With the downlink/hessian streams disabled, steps 1 and 3
        vanish from the traced graph and the round is the PR-1 uplink
        pipeline unchanged.  Participation is a gather: only the S
        sampled clients run local training (their rows are gathered up
        front and their state rows scattered back), so partial
        participation saves real compute in both strategies instead of
        masking discarded work.
        """
        fed = self.fed
        comm = fed.comm
        params = state["params"]
        C = fed.num_clients
        S = comm.num_participants(C)
        spec, comp = rt.spec, rt.comp
        dn_on, h_on = rt.dn_on, rt.h_on
        packed = self.params_packed(params)
        theta = (params.astype(jnp.float32) if packed
                 else cflat.pack(params, spec))
        if dn_on:
            with phase("wire"):
                theta_dn = cflat.repack(theta, spec, rt.spec_dn)
        else:
            theta_dn = None
        idx = participation_indices(
            jax.random.fold_in(rng, PARTICIPATION_SALT + comm.seed), C, S)
        stateful = self._stateful()
        opts = state.get("client_opt") if stateful else None
        ef = state.get("comm_ef")
        dn_model = state.get(cdown.MODEL_KEY)
        dn_ef = state.get(cdown.EF_KEY)

        def take(tree):
            # gathered rows stay in the resident storage dtype — the
            # kernels upcast loads in-VMEM (no bulk fp32 copy)
            return (None if tree is None
                    else jax.tree.map(lambda x: x[idx], tree))

        with phase("rows"):
            opts_g, ef_g = take(opts), take(ef)
            dnm_g, dnef_g = take(dn_model), take(dn_ef)
            batches_g, rngs_g = take(batches), client_rngs[idx]

        client = functools.partial(self.comm_client_step, rt, theta,
                                   theta_dn, round_idx, lr)

        # adversarial fleet (repro.robust): static config — when off,
        # the attack/robust branches never enter the traced graph and
        # the aggregation below is the historical weighted-mean path.
        # Attacks transform the packed uplink wire buffer only; the
        # downlink-replica correction and hessian streams keep their
        # participation means (docs/robustness.md).
        rb = fed.robust
        attack_on = robust_attacks.wire_attack_active(rb, C)
        robust_on = robust_agg.resolve(rb, S) != "mean"

        def combine_wires(wires):
            with phase("combine"):
                if attack_on:
                    byz = jnp.asarray(robust_attacks.byzantine_mask(rb, C))
                    wires = robust_attacks.attack_wires(
                        rb, wires, byz[idx], rng)
                if robust_on:
                    return robust_agg.aggregate_stack(
                        rb, wires, jnp.ones((S,), jnp.float32),
                        normalize=True, use_pallas=comm.use_pallas)
                return jnp.sum(wires, axis=0) / S

        if fed.strategy == "parallel":
            (wires, stats, ef_new_g, opt_new_g, losses, dnm_new_g,
             dnef_new_g, h_hat_g, h_stat_g) = self.comm_client_step_batched(
                rt, theta, theta_dn, round_idx, lr,
                opts_g, ef_g, dnm_g, dnef_g, batches_g, rngs_g)
            agg_flat = combine_wires(wires)
            with phase("combine"):
                wstat = jnp.sum(stats) / S
                if dn_on:
                    dn_mean = jnp.sum(dnm_new_g, axis=0) / S
                if h_on:
                    h_agg = jnp.sum(h_hat_g, axis=0) / S
                    h_wstat = jnp.sum(h_stat_g) / S
        else:
            collect = attack_on or robust_on

            def scan_body(acc, xs):
                opt, ef_i, dnm_i, dnef_i, batch, crng = xs
                (wire, stat, ef_i_new, opt_i, loss, dnm_new, dnef_new,
                 h_hat, h_stat) = client(opt, ef_i, dnm_i, dnef_i,
                                         batch, crng)
                # robust/attacked runs stack the wires (trimming needs
                # the whole cohort) instead of accumulating the mean
                with phase("combine"):
                    if not collect:
                        acc = {**acc, "w": acc["w"] + wire / S}
                    acc = {**acc, "s": acc["s"] + stat / S}
                    if dn_on:
                        acc = {**acc, "dn": acc["dn"] + dnm_new / S}
                    if h_on:
                        acc = {**acc, "h": acc["h"] + h_hat / S,
                               "hs": acc["hs"] + h_stat / S}
                ys = (ef_i_new, opt_i, loss, dnm_new, dnef_new)
                return acc, (ys + (wire,)) if collect else ys
            acc0 = {"s": jnp.zeros((), jnp.float32)}
            if not collect:
                acc0["w"] = cflat.zeros(spec)
            if dn_on:
                acc0["dn"] = cflat.zeros(rt.spec_dn)
            if h_on:
                acc0["h"] = cflat.zeros(rt.spec_h)
                acc0["hs"] = jnp.zeros((), jnp.float32)
            acc, ys = jax.lax.scan(scan_body, acc0,
                                   (opts_g, ef_g, dnm_g, dnef_g,
                                    batches_g, rngs_g))
            (ef_new_g, opt_new_g, losses, dnm_new_g, dnef_new_g) = ys[:5]
            agg_flat = combine_wires(ys[5]) if collect else acc["w"]
            wstat = acc["s"]
            if dn_on:
                dn_mean = acc["dn"]
            if h_on:
                h_agg, h_wstat = acc["h"], acc["hs"]

        with phase("combine"):
            agg_flat = comp.server_combine(agg_flat, wstat)
            if dn_on:
                # clients trained from their OWN received replicas: the
                # aggregated model is mean_S(replica + decoded uplink
                # delta), expressed as a server-side delta vs the true
                # model
                corr = cflat.repack(dn_mean - theta_dn, rt.spec_dn, spec)
                agg_flat = agg_flat + corr
            # the server model update is a flat axpy; the pytree
            # appears only at the state boundary (and not at all in
            # packed-resident mode)
            if packed:
                state = self._apply_aggregate_flat(state, theta + agg_flat)
            else:
                state = self._apply_aggregate(
                    state, cflat.unpack(theta + agg_flat, spec))
        if stateful:
            # scatter the participants' optimizer state rows back
            # (downcast to the per-buffer resident dtypes; no-op for
            # fp32)
            with phase("rows"):
                new_opts = jax.tree.map(
                    lambda full, g: full.at[idx].set(g),
                    state["client_opt"], self._store_opt(opt_new_g))
            if h_on:
                # curvature averaging: every participant's h re-synced
                # to the (re-quantized) common averaged broadcast
                with phase("wire"):
                    h_down, _ = rt.comp_h.roundtrip(
                        jax.random.fold_in(rng, 0x4D),
                        rt.comp_h.server_combine(h_agg, h_wstat))
                    h_common = cflat.repack(
                        h_down, rt.spec_h, spec).astype(new_opts.h.dtype)
                with phase("rows"):
                    new_opts = new_opts._replace(h=new_opts.h.at[idx].set(
                        jnp.broadcast_to(h_common[None],
                                         (S,) + h_common.shape)))
            state = {**state, "client_opt": new_opts}
        with phase("rows"):
            if ef is not None:
                state = {**state, "comm_ef":
                         ef.at[idx].set(self._store(ef_new_g))}
            if dn_model is not None:
                state = {**state, cdown.MODEL_KEY:
                         dn_model.at[idx].set(self._store(dnm_new_g))}
            if dn_ef is not None:
                state = {**state, cdown.EF_KEY:
                         dn_ef.at[idx].set(self._store(dnef_new_g))}
        return state, jnp.mean(losses)

    # ------------------------------------------------ server-side optimizers
    def _server_opt_update(self, state, agg):
        """FedOpt family: Delta = params - mean(client params) is the
        pseudo-gradient; apply Adam/Yogi on the server."""
        fed = self.fed
        params = state["params"]
        so = state["server_opt"]
        delta = jax.tree.map(jnp.subtract, params, agg)
        m = jax.tree.map(lambda mm, d: fed.server_beta1 * mm
                         + (1 - fed.server_beta1) * d, so["m"], delta)
        if fed.optimizer == "fedadam":
            v = jax.tree.map(lambda vv, d: fed.server_beta2 * vv
                             + (1 - fed.server_beta2) * d * d, so["v"], delta)
        else:  # fedyogi
            v = jax.tree.map(
                lambda vv, d: vv - (1 - fed.server_beta2) * d * d
                * jnp.sign(vv - d * d), so["v"], delta)
        new_params = jax.tree.map(
            lambda p, mm, vv: (p - fed.server_lr * mm
                               / (jnp.sqrt(vv) + fed.server_eps)).astype(p.dtype),
            params, m, v)
        return {**state, "params": new_params,
                "server_opt": {"m": m, "v": v}}

    def _server_opt_update_flat(self, state, agg):
        """`_server_opt_update` over packed wire buffers (packed-
        resident mode): identical per-coordinate math on the flattened
        coordinates, fp32 compute, stored back in the resident dtype.
        ``agg`` is the fp32 aggregated packed model."""
        fed = self.fed
        so = state["server_opt"]
        params = state["params"].astype(jnp.float32)
        m0, v0 = (so["m"].astype(jnp.float32),
                  so["v"].astype(jnp.float32))
        delta = params - agg
        m = fed.server_beta1 * m0 + (1 - fed.server_beta1) * delta
        if fed.optimizer == "fedadam":
            v = (fed.server_beta2 * v0
                 + (1 - fed.server_beta2) * delta * delta)
        else:  # fedyogi
            v = v0 - ((1 - fed.server_beta2) * delta * delta
                      * jnp.sign(v0 - delta * delta))
        new_params = (params - fed.server_lr * m
                      / (jnp.sqrt(v) + fed.server_eps))
        return {**state,
                "params": new_params.astype(state["params"].dtype),
                "server_opt": {"m": m.astype(so["m"].dtype),
                               "v": v.astype(so["v"].dtype)}}
