"""Bit-exactness safety net of the flat-resident round engine.

The engine keeps all client-visible state in the packed (rows, cols)
wire layout end-to-end (docs/architecture.md "Memory layout"); that
refactor is only safe because the flat round computes the SAME
per-coordinate op sequence as the historical pytree engine for fp32
models — the flattening order is frozen and every hot-path op is
elementwise.  This file carries a faithful copy of the pre-refactor
tree-resident round (`TreeRoundRef`, built from the public
`repro.core.sophia` / `repro.core.gnb` / `repro.comm` pieces) and pins
the live engine against it across the

    {fed_sophia, fedavg} x {parallel, sequential}
        x {direct, uplink-only, bidir, EF-on}

matrix, including the persistent Sophia m/h state (compared row-by-row
through `flat.pack`).

One backend caveat bounds what "bitwise" can mean: XLA:CPU contracts
mul+add chains into FMAs *per fused loop*, so two structurally
different programs with identical math can disagree in the last ulp of
an EMA (verified: materializing the intermediate makes the difference
vanish).  Sophia's m-EMA feeds a division by near-zero curvature, so
under jit that single ulp is chaotically amplified across rounds.  The
matrix is therefore pinned BITWISE under op-by-op execution
(`jax.disable_jit`, where no cross-op fusion exists) — and bitwise
*under jit* wherever program structure cannot change contraction: the
fedavg matrix (no EMA chain) and the fused-Pallas fed_sophia path (the
kernel is one opaque unit in both engines).
"""
import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import downlink as cdown, flat as cflat
from repro.kernels import tuning as ktuning
from repro.comm.compressors import (make_compressor, make_stream_compressor,
                                    participation_indices,
                                    wants_error_feedback)
from repro.configs.base import CommConfig, FedConfig
from repro.core import sophia
from repro.core.fed import PARTICIPATION_SALT, FedEngine
from repro.core.gnb import gnb_estimate
from repro.core.schedules import lr_at_round
from repro.data import synthetic as syn
from repro.models.small import MLPTask
from repro.utils.tree import tree_sub, tree_zeros_like


def _vg(loss_fn, params, batch, rng=None):
    return jax.value_and_grad(loss_fn)(params, batch, rng)


class TreeRoundRef:
    """The pre-flat-refactor `FedEngine.round`, pytree-resident.

    Trimmed to the optimizers/paths the equivalence matrix covers
    (fed_sophia with persistent state, fedavg); rng folds, scan/vmap
    structure and op order mirror the historical engine exactly.
    """

    def __init__(self, task, fed: FedConfig):
        self.task = task
        self.fed = fed

    # ------------------------------------------------------------- state
    def init(self, key):
        fed = self.fed
        params = self.task.init(key)
        state = {"params": params, "round": jnp.zeros((), jnp.int32)}
        comm = fed.comm
        if fed.optimizer == "fed_sophia" and fed.persistent_client_state:
            opt = sophia.init_state(params)
            state["client_opt"] = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (fed.num_clients,) + x.shape).copy(), opt)
        if wants_error_feedback(comm):
            spec = cflat.flat_spec(params, cols=comm.quant_block)
            state["comm_ef"] = jnp.zeros(
                (fed.num_clients, spec.rows, spec.cols), jnp.float32)
        if comm.downlink_enabled:
            spec_dn = cflat.flat_spec(
                params, cols=comm.stream("downlink").quant_block)
            state.update(cdown.init_state(
                comm, spec_dn, cflat.pack(params, spec_dn),
                fed.num_clients))
        return state

    # ---------------------------------------------------- local training
    def _local_sophia(self, params, opt, batch, round_idx, rng, lr):
        fed = self.fed
        task = self.task
        round_mode = fed.hessian_every_unit == "round"
        if round_mode:
            do_h_round = (round_idx % fed.tau) == 0
            h_hat_round = jax.lax.cond(
                do_h_round,
                lambda: gnb_estimate(task, params, batch,
                                     jax.random.fold_in(rng, 0x7FFFFFFF),
                                     vg_fn=_vg),
                lambda: tree_zeros_like(params))

        def step(carry, j):
            p, st = carry
            loss, grads = _vg(task.loss, p, batch, None)
            if round_mode:
                do_h = do_h_round & (j == 0)
                h_hat = h_hat_round
            else:
                t = round_idx * fed.local_iters + j
                do_h = (t % fed.tau) == 0
                rng_j = jax.random.fold_in(rng, j)
                h_hat = jax.lax.cond(
                    do_h,
                    lambda: gnb_estimate(task, p, batch, rng_j, vg_fn=_vg),
                    lambda: tree_zeros_like(p))
            p, st = sophia.sophia_step(
                p, grads, st, h_hat, do_h,
                lr=lr, beta1=fed.beta1, beta2=fed.beta2, rho=fed.rho,
                eps=fed.eps, weight_decay=fed.weight_decay,
                use_pallas=fed.use_pallas)
            return (p, st), loss

        (params, opt), losses = jax.lax.scan(
            step, (params, opt), jnp.arange(fed.local_iters))
        return params, opt, jnp.mean(losses)

    def _local_sgd(self, params, batch, lr):
        def step(p, j):
            loss, grads = _vg(self.task.loss, p, batch, None)
            p = jax.tree.map(lambda t, g: (t - lr * g).astype(t.dtype),
                             p, grads)
            return p, loss
        params, losses = jax.lax.scan(
            step, params, jnp.arange(self.fed.local_iters))
        return params, jnp.mean(losses)

    def _local_update(self, params, opt, batch, crng, round_idx, lr):
        fed = self.fed
        if fed.optimizer == "fed_sophia":
            if opt is None:
                opt = sophia.init_state(params)
            p, o, loss = self._local_sophia(params, opt, batch, round_idx,
                                            crng, lr)
            return p, (o if fed.persistent_client_state else None), loss
        p, loss = self._local_sgd(params, batch, lr)
        return p, None, loss

    # ------------------------------------------------------------- round
    def uses_direct_path(self):
        comm = self.fed.comm
        C = self.fed.num_clients
        return (comm.lossless and comm.num_participants(C) == C
                and not comm.multi_stream)

    def round(self, state, batches, rng):
        fed = self.fed
        round_idx = state["round"]
        lr = lr_at_round(fed, round_idx)
        client_rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
            jnp.arange(fed.num_clients))
        if self.uses_direct_path():
            state, loss = self._round_direct(state, batches, client_rngs,
                                             round_idx, lr)
        else:
            state, loss = self._round_comm(state, batches, client_rngs,
                                           round_idx, lr, rng)
        return {**state, "round": round_idx + 1}, {"loss": loss}

    def _round_direct(self, state, batches, client_rngs, round_idx, lr):
        fed = self.fed
        params = state["params"]
        C = fed.num_clients
        stateful = (fed.optimizer == "fed_sophia"
                    and fed.persistent_client_state)
        opts = state.get("client_opt") if stateful else None
        if fed.strategy == "parallel":
            if stateful:
                new_p, new_opt, losses = jax.vmap(
                    lambda o, b, r: self._local_update(
                        params, o, b, r, round_idx, lr)
                )(opts, batches, client_rngs)
            else:
                new_p, new_opt, losses = jax.vmap(
                    lambda b, r: self._local_update(
                        params, None, b, r, round_idx, lr)
                )(batches, client_rngs)
            agg = jax.tree.map(lambda x: jnp.mean(x, axis=0), new_p)
        else:
            def scan_body(acc, xs):
                opt, batch, crng = xs
                p_i, opt_i, loss = self._local_update(
                    params, opt, batch, crng, round_idx, lr)
                acc = jax.tree.map(lambda a, x: a + x / C, acc, p_i)
                return acc, (opt_i, loss)
            agg, (new_opt, losses) = jax.lax.scan(
                scan_body, tree_zeros_like(params),
                (opts, batches, client_rngs))
            agg = jax.tree.map(lambda a, p: a.astype(p.dtype), agg, params)
        state = {**state, "params": agg}
        if stateful:
            state = {**state, "client_opt": new_opt}
        return state, jnp.mean(losses)

    def _comm_client_step(self, rt, params, packed_theta, round_idx, lr,
                          opt, ef_i, dnm_i, dnef_i, batch, crng):
        spec_dn, comp_dn, spec_h, comp_h = rt["dn"] + rt["h"]
        spec, comp = rt["up"]
        if comp_dn is not None:
            dnm_i, dnef_i = cdown.broadcast(
                comp_dn, jax.random.fold_in(crng, 0xD0),
                packed_theta, dnm_i, dnef_i)
            p_start = cflat.unpack(dnm_i, spec_dn)
        else:
            p_start = params
        p_i, opt_i, loss = self._local_update(
            p_start, opt, batch, crng, round_idx, lr)
        delta = cflat.pack(tree_sub(p_i, p_start), spec)
        if ef_i is not None:
            delta = delta + ef_i
        xhat, stat = comp.roundtrip(jax.random.fold_in(crng, 0xC0), delta)
        ef_new = None if ef_i is None else delta - xhat
        h_hat = h_stat = None
        if comp_h is not None:
            h_hat, h_stat = comp_h.roundtrip(
                jax.random.fold_in(crng, 0x4E),
                cflat.pack(opt_i.h, spec_h))
        return (xhat, stat, ef_new, opt_i, loss,
                dnm_i if comp_dn is not None else None, dnef_i,
                h_hat, h_stat)

    def _runtime(self, params):
        comm = self.fed.comm
        spec = cflat.flat_spec(params, cols=comm.quant_block)
        rt = {"up": (spec, make_compressor(comm, spec)),
              "dn": (None, None), "h": (None, None)}
        if comm.downlink_enabled:
            s = cflat.flat_spec(
                params, cols=comm.stream("downlink").quant_block)
            rt["dn"] = (s, make_stream_compressor(comm, "downlink", s))
        if comm.hessian_enabled:
            s = cflat.flat_spec(
                params, cols=comm.stream("hessian").quant_block)
            rt["h"] = (s, make_stream_compressor(comm, "hessian", s))
        return rt

    def _round_comm(self, state, batches, client_rngs, round_idx, lr, rng):
        fed = self.fed
        comm = fed.comm
        params = state["params"]
        C = fed.num_clients
        S = comm.num_participants(C)
        rt = self._runtime(params)
        spec, comp = rt["up"]
        spec_dn, comp_dn = rt["dn"]
        spec_h, comp_h = rt["h"]
        dn_on, h_on = comp_dn is not None, comp_h is not None
        packed_theta = cflat.pack(params, spec_dn) if dn_on else None
        idx = participation_indices(
            jax.random.fold_in(rng, PARTICIPATION_SALT + comm.seed), C, S)
        stateful = (fed.optimizer == "fed_sophia"
                    and fed.persistent_client_state)
        opts = state.get("client_opt") if stateful else None
        ef = state.get("comm_ef")
        dn_model = state.get(cdown.MODEL_KEY)
        dn_ef = state.get(cdown.EF_KEY)

        def take(tree):
            return (None if tree is None
                    else jax.tree.map(lambda x: x[idx], tree))

        opts_g, ef_g = take(opts), take(ef)
        dnm_g, dnef_g = take(dn_model), take(dn_ef)
        batches_g, rngs_g = take(batches), client_rngs[idx]
        client = functools.partial(self._comm_client_step, rt, params,
                                   packed_theta, round_idx, lr)

        if fed.strategy == "parallel":
            (wires, stats, ef_new_g, opt_new_g, losses, dnm_new_g,
             dnef_new_g, h_hat_g, h_stat_g) = jax.vmap(client)(
                opts_g, ef_g, dnm_g, dnef_g, batches_g, rngs_g)
            agg_flat = jnp.sum(wires, axis=0) / S
            wstat = jnp.sum(stats) / S
            if dn_on:
                dn_mean = jnp.sum(dnm_new_g, axis=0) / S
            if h_on:
                h_agg = jnp.sum(h_hat_g, axis=0) / S
                h_wstat = jnp.sum(h_stat_g) / S
        else:
            def scan_body(acc, xs):
                opt, ef_i, dnm_i, dnef_i, batch, crng = xs
                (wire, stat, ef_i_new, opt_i, loss, dnm_new, dnef_new,
                 h_hat, h_stat) = client(opt, ef_i, dnm_i, dnef_i,
                                         batch, crng)
                acc = {**acc, "w": acc["w"] + wire / S,
                       "s": acc["s"] + stat / S}
                if dn_on:
                    acc = {**acc, "dn": acc["dn"] + dnm_new / S}
                if h_on:
                    acc = {**acc, "h": acc["h"] + h_hat / S,
                           "hs": acc["hs"] + h_stat / S}
                return acc, (ef_i_new, opt_i, loss, dnm_new, dnef_new)
            acc0 = {"w": jnp.zeros((spec.rows, spec.cols), jnp.float32),
                    "s": jnp.zeros((), jnp.float32)}
            if dn_on:
                acc0["dn"] = jnp.zeros(
                    (spec_dn.rows, spec_dn.cols), jnp.float32)
            if h_on:
                acc0["h"] = jnp.zeros(
                    (spec_h.rows, spec_h.cols), jnp.float32)
                acc0["hs"] = jnp.zeros((), jnp.float32)
            acc, (ef_new_g, opt_new_g, losses, dnm_new_g, dnef_new_g) = \
                jax.lax.scan(scan_body, acc0,
                             (opts_g, ef_g, dnm_g, dnef_g,
                              batches_g, rngs_g))
            agg_flat, wstat = acc["w"], acc["s"]
            if dn_on:
                dn_mean = acc["dn"]
            if h_on:
                h_agg, h_wstat = acc["h"], acc["hs"]

        agg_flat = comp.server_combine(agg_flat, wstat)
        if dn_on:
            corr = dn_mean - packed_theta
            if spec_dn.cols != spec.cols:
                corr = cflat.repack(corr, spec_dn, spec)
            agg_flat = agg_flat + corr
        agg_delta = cflat.unpack(agg_flat, spec)
        agg = jax.tree.map(lambda p, d: (p + d).astype(p.dtype),
                           params, agg_delta)
        state = {**state, "params": agg}
        if stateful:
            new_opts = jax.tree.map(
                lambda full, g: full.at[idx].set(g), opts, opt_new_g)
            if h_on:
                h_down, _ = comp_h.roundtrip(
                    jax.random.fold_in(rng, 0x4D),
                    comp_h.server_combine(h_agg, h_wstat))
                h_avg = cflat.unpack(h_down, spec_h)
                new_h = jax.tree.map(
                    lambda full, v: full.at[idx].set(jnp.broadcast_to(
                        v[None], (S,) + v.shape).astype(full.dtype)),
                    new_opts.h, h_avg)
                new_opts = new_opts._replace(h=new_h)
            state = {**state, "client_opt": new_opts}
        if ef is not None:
            state = {**state, "comm_ef": ef.at[idx].set(ef_new_g)}
        if dn_model is not None:
            state = {**state, cdown.MODEL_KEY:
                     dn_model.at[idx].set(dnm_new_g)}
        if dn_ef is not None:
            state = {**state, cdown.EF_KEY: dn_ef.at[idx].set(dnef_new_g)}
        return state, jnp.mean(losses)


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    x, y = syn.make_image_data(key, 512, "mnist", noise=1.0)
    part = syn.dirichlet_partition(jax.random.PRNGKey(1), y, 4, alpha=0.5)
    tr, _ = syn.train_test_split(part)
    task = MLPTask(hidden=16)
    batches = syn.client_batches(key, x, y, tr, 16)
    return task, batches


COMMS = {
    "direct": lambda opt: CommConfig(),
    "uplink-int8": lambda opt: CommConfig(compressor="int8"),
    # bidir: compressed broadcast everywhere; the hessian stream only
    # exists for persistent fed_sophia
    "bidir": lambda opt: CommConfig(
        compressor="int8", downlink_compressor="int8",
        hessian_compressor="int4" if opt == "fed_sophia" else "off"),
    # EF-on (topk is biased -> "auto" materialises residuals), plus
    # partial participation to cover the gather/scatter path
    "ef-topk": lambda opt: CommConfig(compressor="topk", topk_ratio=0.05,
                                      participation=0.5),
}


def _run_both(task, fed, batches, rounds=2, jit=True):
    """(flat engine state, ref state, per-round losses) after ``rounds``.

    jit=False runs both engines op-by-op (`jax.disable_jit`): every
    primitive executes as its own kernel, so XLA's fusion-dependent
    FMA contraction cannot differ between the two program structures
    and bitwise comparison is meaningful.
    """
    eng = FedEngine(task, fed)
    ref = TreeRoundRef(task, fed)
    ctx = jax.disable_jit() if not jit else contextlib.nullcontext()
    with ctx:
        s_eng = eng.init(jax.random.PRNGKey(2))
        s_ref = ref.init(jax.random.PRNGKey(2))
        rf_eng = jax.jit(eng.round) if jit else eng.round
        rf_ref = jax.jit(ref.round) if jit else ref.round
        losses = []
        for r in range(rounds):
            rng = jax.random.PRNGKey(100 + r)
            s_eng, m_eng = rf_eng(s_eng, batches, rng)
            s_ref, m_ref = rf_ref(s_ref, batches, rng)
            losses.append((float(m_eng["loss"]), float(m_ref["loss"])))
    return eng, s_eng, s_ref, losses


def _assert_state_bit_identical(eng, s_eng, s_ref, atol=None):
    """Bitwise by default; ``atol`` switches to absolute-tolerance
    comparison (for the jitted configs where XLA's per-fusion FMA
    contraction forbids strict equality — see module docstring)."""
    def check(a, b):
        if atol is None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a, np.float64),
                                       np.asarray(b, np.float64),
                                       rtol=0, atol=atol)

    for a, b in zip(jax.tree.leaves(s_eng["params"]),
                    jax.tree.leaves(s_ref["params"])):
        check(a, b)
    # wire-layout comm state carries identical keys in both engines
    for k in ("comm_ef", cdown.MODEL_KEY, cdown.EF_KEY):
        assert (k in s_eng) == (k in s_ref)
        if k in s_eng:
            check(s_eng[k], s_ref[k])
    # persistent Sophia state: the engine stores (C, rows, cols) wire
    # buffers, the reference per-client pytrees — pack the reference
    # rows into the same layout and compare
    assert ("client_opt" in s_eng) == ("client_opt" in s_ref)
    if "client_opt" in s_eng:
        spec = eng.comm_runtime(s_eng["params"]).spec
        for flat_buf, tree_full in ((s_eng["client_opt"].m,
                                     s_ref["client_opt"].m),
                                    (s_eng["client_opt"].h,
                                     s_ref["client_opt"].h)):
            C = flat_buf.shape[0]
            for i in range(C):
                row_tree = jax.tree.map(lambda x, i=i: x[i], tree_full)
                check(flat_buf[i], cflat.pack(row_tree, spec))


@pytest.mark.parametrize("comm_name", sorted(COMMS))
@pytest.mark.parametrize("strategy", ["parallel", "sequential"])
def test_flat_round_bit_identical_jit_fedavg(setup, strategy, comm_name):
    """fedavg's local update has no EMA mul+add chain, so even jitted
    programs contract identically: bitwise under jit across the whole
    comm matrix, both strategies."""
    task, batches = setup
    fed = FedConfig(num_clients=4, local_iters=2, optimizer="fedavg",
                    strategy=strategy, lr=0.01, tau=2,
                    comm=COMMS[comm_name]("fedavg"))
    eng, s_eng, s_ref, losses = _run_both(task, fed, batches)
    for le, lr_ in losses:
        assert le == lr_, (comm_name, losses)
    _assert_state_bit_identical(eng, s_eng, s_ref)


SOPHIA_MATRIX = [
    pytest.param("parallel", "direct", id="parallel-direct"),
    pytest.param("parallel", "uplink-int8", id="parallel-uplink-int8"),
    pytest.param("parallel", "bidir", id="parallel-bidir",
                 marks=pytest.mark.slow),
    pytest.param("parallel", "ef-topk", id="parallel-ef-topk",
                 marks=pytest.mark.slow),
    pytest.param("sequential", "direct", id="sequential-direct",
                 marks=pytest.mark.slow),
    pytest.param("sequential", "uplink-int8", id="sequential-uplink-int8",
                 marks=pytest.mark.slow),
    pytest.param("sequential", "bidir", id="sequential-bidir",
                 marks=pytest.mark.slow),
    pytest.param("sequential", "ef-topk", id="sequential-ef-topk",
                 marks=pytest.mark.slow),
]


@pytest.mark.parametrize("strategy,comm_name", SOPHIA_MATRIX)
def test_flat_round_bit_identical_opbyop_sophia(setup, strategy,
                                                comm_name):
    """fed_sophia across the matrix, op-by-op: bitwise equal including
    the packed m/h state (the heavy off-diagonal combos carry the slow
    marker; two representatives stay in tier-1)."""
    task, batches = setup
    fed = FedConfig(num_clients=4, local_iters=2, optimizer="fed_sophia",
                    strategy=strategy, lr=0.01, tau=2,
                    comm=COMMS[comm_name]("fed_sophia"))
    eng, s_eng, s_ref, losses = _run_both(task, fed, batches, jit=False)
    for le, lr_ in losses:
        assert le == lr_, (comm_name, losses)
    _assert_state_bit_identical(eng, s_eng, s_ref)


def test_flat_round_jit_sophia_close(setup):
    """Jitted fed_sophia sanity net: XLA's per-fusion FMA contraction
    seeds last-ulp EMA differences that the near-zero-curvature divide
    amplifies, so jit-vs-jit across different program structures is
    allclose, not bitwise (op-by-op IS bitwise — see above)."""
    task, batches = setup
    fed = FedConfig(num_clients=4, local_iters=2, optimizer="fed_sophia",
                    strategy="parallel", lr=0.01, tau=2,
                    comm=CommConfig(compressor="int8"))
    eng, s_eng, s_ref, losses = _run_both(task, fed, batches)
    for le, lr_ in losses:
        assert le == pytest.approx(lr_, rel=1e-5), losses
    for a, b in zip(jax.tree.leaves(s_eng["params"]),
                    jax.tree.leaves(s_ref["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


@pytest.fixture
def default_kernel_geometry(monkeypatch, tmp_path):
    """Force the safe default launch geometry (one client per grid
    step) regardless of the committed tuning table.  Kernel VALUES are
    block-invariant (pinned per kernel x dtype x geometry by
    tests/test_kernel_conformance.py), but in interpret mode a
    different grid restructures the surrounding jitted program enough
    for XLA:CPU's per-fusion FMA contraction to seed a last-ulp
    difference vs the tree reference (the module-docstring caveat) —
    so the flat-vs-tree BITWISE pin runs on the fixed historical
    geometry."""
    monkeypatch.setattr(ktuning, "TUNING_PATH",
                        str(tmp_path / "absent.json"))
    ktuning.load_tuning.cache_clear()
    yield
    ktuning.load_tuning.cache_clear()


def test_flat_round_bit_identical_jit_pallas_kernels(
        setup, default_kernel_geometry):
    """The fused-kernel path: flat-resident state feeds the Sophia and
    quantize kernels directly; the reference packs/unpacks around the
    same kernels per iteration (the historical behaviour).  The kernel
    is one opaque unit in both programs, so this is bitwise even under
    jit — the production path carries the strongest guarantee.  Pinned
    on the default launch geometry (see `default_kernel_geometry`);
    the tuned batched geometry's value-equivalence is pinned by the
    kernel conformance suite and
    tests/test_residency.py::test_comm_client_step_batched_matches_vmap."""
    task, batches = setup
    fed = FedConfig(num_clients=4, local_iters=2, optimizer="fed_sophia",
                    strategy="parallel", lr=0.01, tau=2, use_pallas=True,
                    comm=CommConfig(compressor="int8", use_pallas=True))
    eng, s_eng, s_ref, losses = _run_both(task, fed, batches)
    for le, lr_ in losses:
        assert le == lr_, losses
    _assert_state_bit_identical(eng, s_eng, s_ref)


def test_flat_round_jit_pallas_fused_uplink_ef_close(setup):
    """Forced client EF for int8 routes the engine through the fused
    uplink encode kernel (`uplink_roundtrip_flat`).  The extra EF
    plumbing changes the surrounding XLA program enough for per-fusion
    contraction to seed a last-ulp difference in the per-row quant
    scale (observed max |diff| ~1e-10; interpret-mode Pallas cannot
    run under jax.disable_jit in this jax build, so the op-by-op
    escape hatch is unavailable here) — pinned allclose at 1e-8, three
    orders tighter than any training-relevant scale."""
    task, batches = setup
    fed = FedConfig(num_clients=4, local_iters=2, optimizer="fed_sophia",
                    strategy="parallel", lr=0.01, tau=2, use_pallas=True,
                    comm=CommConfig(compressor="int8", use_pallas=True,
                                    error_feedback=True))
    eng, s_eng, s_ref, losses = _run_both(task, fed, batches)
    for le, lr_ in losses:
        assert le == pytest.approx(lr_, rel=1e-6), losses
    assert "comm_ef" in s_eng and "comm_ef" in s_ref
    _assert_state_bit_identical(eng, s_eng, s_ref, atol=1e-8)


@pytest.mark.parametrize("kw", [
    {"hessian_every_unit": "round", "tau": 1},
    {"persistent_client_state": False},
], ids=["round-mode", "stateless"])
def test_flat_round_bit_identical_opbyop_variants(setup, kw):
    """hessian_every_unit='round' (hoisted GNB) and the stateless
    fed_sophia variant also ride the flat path bit-exactly."""
    task, batches = setup
    base = dict(num_clients=4, local_iters=2, optimizer="fed_sophia",
                lr=0.01, tau=2, comm=CommConfig(compressor="int8"))
    base.update(kw)
    fed = FedConfig(**base)
    eng, s_eng, s_ref, losses = _run_both(task, fed, batches, rounds=1,
                                          jit=False)
    for le, lr_ in losses:
        assert le == lr_, (kw, losses)
    _assert_state_bit_identical(eng, s_eng, s_ref)


# ------------------------------------------- leaf-layout local loops
class PerStepPackedLoops:
    """The local Sophia loops as they stood before they ran in the
    model's leaf layout: theta/m/h stay packed (rows, cols) buffers
    through the whole scan, and every step crosses the layout twice —
    ONE `unpack` gives `value_and_grad` its view and ONE `pack` lays
    the grads back (the GNB estimate is packed inside its `lax.cond`)
    — before one fused update over the packed buffers.  A frozen copy
    of `FedEngine._local_sophia_flat(_batched)` and
    `sophia.sophia_step_flat` of that engine, the reference of the
    leaf-layout loops below."""

    def __init__(self, eng: FedEngine):
        self.eng = eng
        self.fed = eng.fed
        self.task = eng.task

    @staticmethod
    def _pack(tree, spec):
        v = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                             for l in jax.tree.leaves(tree)])
        return jnp.pad(v, (0, spec.padded - spec.total)).reshape(
            spec.rows, spec.cols)

    @staticmethod
    def _unpack(flat, spec):
        v = flat.reshape(-1)[:spec.total]
        out, off = [], 0
        for sz, shp, dt in zip(spec.sizes, spec.shapes, spec.dtypes):
            out.append(v[off:off + sz].reshape(shp).astype(dt))
            off += sz
        return jax.tree_util.tree_unflatten(spec.treedef, out)

    def _value_and_grad(self, theta, batch, spec):
        pg = self.eng._gathered(self._unpack(theta, spec))
        loss, grads = self.eng._value_and_grad(self.task.loss, pg, batch)
        return loss, self._pack(grads, spec), pg

    def _update(self, theta, m, h, grads, h_hat, do_h, lr):
        fed = self.fed
        hp = dict(beta1=fed.beta1, beta2=fed.beta2, rho=fed.rho,
                  eps=fed.eps, weight_decay=fed.weight_decay)
        if fed.use_pallas:
            from repro.kernels.sophia_update import (sophia_update_batched,
                                                     sophia_update_flat)
            fn = (sophia_update_batched if theta.ndim == 3
                  else sophia_update_flat)
            return fn(theta, m, h, grads, h_hat, do_h, lr, **hp)
        out_dt = (theta.dtype, m.dtype, h.dtype)
        theta, m, h, grads, h_hat = (x.astype(jnp.float32)
                                     for x in (theta, m, h, grads, h_hat))
        m = hp["beta1"] * m + (1.0 - hp["beta1"]) * grads
        h = jnp.where(do_h, hp["beta2"] * h + (1.0 - hp["beta2"]) * h_hat,
                      h)
        theta = theta - lr * hp["weight_decay"] * theta
        step = sophia.clip(m / jnp.maximum(h, hp["eps"]), hp["rho"])
        return ((theta - lr * step).astype(out_dt[0]),
                m.astype(out_dt[1]), h.astype(out_dt[2]))

    def local_sophia(self, spec, theta, m, h, batch, round_idx, rng, lr):
        fed, task, vg = self.fed, self.task, self.eng._value_and_grad
        round_mode = fed.hessian_every_unit == "round"
        if round_mode:
            do_h_round = (round_idx % fed.tau) == 0
            h_hat_round = jax.lax.cond(
                do_h_round,
                lambda: self._pack(gnb_estimate(
                    task, self.eng._gathered(self._unpack(theta, spec)),
                    batch, jax.random.fold_in(rng, 0x7FFFFFFF),
                    vg_fn=vg), spec),
                lambda: cflat.zeros(spec))

        def step(carry, j):
            t, m_, h_ = carry
            loss, g, pg = self._value_and_grad(t, batch, spec)
            if round_mode:
                do_h = do_h_round & (j == 0)
                hh = h_hat_round
            else:
                do_h = ((round_idx * fed.local_iters + j) % fed.tau) == 0
                rng_j = jax.random.fold_in(rng, j)
                hh = jax.lax.cond(
                    do_h,
                    lambda: self._pack(gnb_estimate(
                        task, pg, batch, rng_j, vg_fn=vg), spec),
                    lambda: cflat.zeros(spec))
            return self._update(t, m_, h_, g, hh, do_h, lr), loss

        (theta, m, h), losses = jax.lax.scan(
            step, (theta, m, h), jnp.arange(fed.local_iters))
        return theta, m, h, jnp.mean(losses)

    def local_sophia_batched(self, spec, theta, m, h, batches, round_idx,
                             rngs, lr):
        fed, task, vg = self.fed, self.task, self.eng._value_and_grad
        N = rngs.shape[0]
        round_mode = fed.hessian_every_unit == "round"
        if round_mode:
            do_h_round = (round_idx % fed.tau) == 0
            if theta.ndim == 3:
                def gnb_round():
                    return jax.vmap(
                        lambda t, b, r: self._pack(gnb_estimate(
                            task, self.eng._gathered(self._unpack(t, spec)),
                            b, jax.random.fold_in(r, 0x7FFFFFFF),
                            vg_fn=vg), spec))(theta, batches, rngs)
            else:
                pg0 = self.eng._gathered(self._unpack(theta, spec))

                def gnb_round():
                    return jax.vmap(
                        lambda b, r: self._pack(gnb_estimate(
                            task, pg0, b, jax.random.fold_in(r, 0x7FFFFFFF),
                            vg_fn=vg), spec))(batches, rngs)
            h_hat_round = jax.lax.cond(do_h_round, gnb_round,
                                       lambda: cflat.zeros(spec, (N,)))

        def step(carry, j):
            t, m_, h_ = carry
            losses, g, pgs = jax.vmap(
                lambda tt, bb: self._value_and_grad(tt, bb, spec))(
                    t, batches)
            if round_mode:
                do_h = do_h_round & (j == 0)
                hh = h_hat_round
            else:
                do_h = ((round_idx * fed.local_iters + j) % fed.tau) == 0
                hh = jax.lax.cond(
                    do_h,
                    lambda: jax.vmap(
                        lambda pg, bb, r: self._pack(gnb_estimate(
                            task, pg, bb, jax.random.fold_in(r, j),
                            vg_fn=vg), spec))(pgs, batches, rngs),
                    lambda: cflat.zeros(spec, (N,)))
            return self._update(t, m_, h_, g, hh, do_h, lr), losses

        t0 = (theta if theta.ndim == 3
              else jnp.broadcast_to(theta[None], (N,) + theta.shape))
        (theta, m, h), losses = jax.lax.scan(
            step, (t0, m, h), jnp.arange(fed.local_iters))
        return theta, m, h, jnp.mean(losses, axis=0)


class BF16MLP(MLPTask):
    """The MLP with bfloat16 parameters: the engine's model view is then
    a real cast of the resident theta, as it is for the benchmarked
    language model."""

    def init(self, key):
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                            super().init(key))


#: resident state: (model dtype is bf16?, theta, m, h)
LOOP_STATES = {
    "fp32": (False, jnp.float32, jnp.float32, jnp.float32),
    "bf16-fp8": (True, jnp.float32, jnp.float8_e4m3fn, jnp.float8_e5m2),
}


def _loop_inputs(task, batches, state, clients):
    """(engine, spec, theta, m, h, batches, rngs) of a local loop over
    ``clients`` (None: the one-client loop); the start models, m and h
    differ between clients and carry a zero pad tail."""
    bf16_model, t_dt, m_dt, h_dt = LOOP_STATES[state]
    if bf16_model:
        task = BF16MLP(hidden=task.hidden)
    params = task.init(jax.random.PRNGKey(3))
    spec = cflat.flat_spec(params, cols=128)
    n = clients or 1
    ks = jax.random.split(jax.random.PRNGKey(4), 3 * n)

    def rand_tree(k, scale, absval=False):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        t = treedef.unflatten([
            scale * jax.random.normal(jax.random.fold_in(k, i), x.shape)
            for i, x in enumerate(leaves)])
        return jax.tree.map(jnp.abs, t) if absval else t

    theta = jnp.stack([cflat.pack(jax.tree.map(
        lambda p, d: p.astype(jnp.float32) + d, params,
        rand_tree(ks[i], 0.01)), spec, t_dt) for i in range(n)])
    m = jnp.stack([cflat.pack(rand_tree(ks[n + i], 1e-3), spec, m_dt)
                   for i in range(n)])
    h = jnp.stack([cflat.pack(rand_tree(ks[2 * n + i], 1e-3, True), spec,
                              h_dt) for i in range(n)])
    rngs = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(5), i))(
        jnp.arange(n))
    b = jax.tree.map(lambda x: x[:n], batches)
    if clients is None:
        theta, m, h, b, rngs = (jax.tree.map(lambda x: x[0], a)
                                for a in (theta, m, h, b, rngs))
    return task, spec, theta, m, h, b, rngs


@pytest.mark.parametrize("state", sorted(LOOP_STATES))
@pytest.mark.parametrize("clients", [None, 2], ids=["one", "two"])
@pytest.mark.parametrize("mode", ["step", "round"])
def test_leaf_loop_matches_per_step_packed_loop(setup, mode, clients,
                                                state):
    """The local Sophia loop that crosses into the leaf layout once per
    client-round is BITWISE the frozen loop that crossed at every step
    (op-by-op, so no fusion can differ): theta/m/h and the losses, in
    step and round GNB mode, for one client and for two clients with
    their own start models, fp32 and bf16/fp8 resident state."""
    task, batches = setup
    task, spec, theta, m, h, b, rngs = _loop_inputs(task, batches, state,
                                                    clients)
    fed = FedConfig(num_clients=4, local_iters=3, optimizer="fed_sophia",
                    lr=0.01, tau=2, hessian_every_unit=mode)
    eng = FedEngine(task, fed)
    ref = PerStepPackedLoops(eng)
    round_idx = jnp.asarray(0, jnp.int32)
    with jax.disable_jit():
        if clients is None:
            got = eng._local_sophia_flat(spec, theta, m, h, b, round_idx,
                                         rngs, 0.01)
            want = ref.local_sophia(spec, theta, m, h, b, round_idx, rngs,
                                    0.01)
        else:
            got = eng._local_sophia_flat_batched(spec, theta, m, h, b,
                                                 round_idx, rngs, 0.01)
            want = ref.local_sophia_batched(spec, theta, m, h, b,
                                            round_idx, rngs, 0.01)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the GNB refresh ran and moved h: the comparison saw curvature
    assert not np.array_equal(np.asarray(got[2], np.float32),
                              np.asarray(h, np.float32))


def test_leaf_loop_matches_per_step_packed_loop_pallas(setup):
    """The kernel path: per-leaf client-batched launches against one
    launch over the packed stacks, bitwise under jit (each launch is an
    opaque unit), bf16 model with fp8 m/h."""
    task, batches = setup
    task, spec, theta, m, h, b, rngs = _loop_inputs(task, batches,
                                                    "bf16-fp8", 2)
    fed = FedConfig(num_clients=4, local_iters=3, optimizer="fed_sophia",
                    lr=0.01, tau=2, use_pallas=True)
    eng = FedEngine(task, fed)
    ref = PerStepPackedLoops(eng)
    args = (spec, theta, m, h, b, jnp.asarray(0, jnp.int32), rngs, 0.01)
    got = jax.jit(eng._local_sophia_flat_batched, static_argnums=0)(*args)
    want = jax.jit(ref.local_sophia_batched, static_argnums=0)(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _crossings(jaxpr, spec, in_scan=False, out=None):
    """Layout crossings in ``jaxpr``, keyed by whether they sit inside
    a scan body: packs (a concatenate to the packed buffer or to its
    ``spec.total`` flat coordinates) and unpacks (a reshape that
    flattens rows of ``spec.cols`` packed coordinates)."""
    out = out if out is not None else {True: 0, False: 0}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        shapes_in = [getattr(v.aval, "shape", ()) for v in eqn.invars]
        shape_out = getattr(eqn.outvars[0].aval, "shape", ())
        packs = (name == "concatenate" and shape_out
                 and (shape_out[-1] == spec.total
                      or shape_out[-2:] == (spec.rows, spec.cols)))
        unpacks = (name == "reshape" and shapes_in
                   and len(shapes_in[0]) >= 2
                   and shapes_in[0][-1] == spec.cols
                   and len(shape_out) == len(shapes_in[0]) - 1)
        out[in_scan] += bool(packs) + bool(unpacks)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _crossings(sub, spec, in_scan or name == "scan", out)
    return out


def test_local_scan_holds_no_layout_crossing(setup):
    """A packed-resident Sophia round, traced at J = 1 and J = 5: the
    local scan's body packs and unpacks nothing, and the round crosses
    the layout as often at J = 5 as at J = 1 (once a client-round)."""
    task, batches = setup
    counts = {}
    for J in (1, 5):
        fed = FedConfig(num_clients=4, local_iters=J,
                        optimizer="fed_sophia", lr=0.01, tau=J,
                        use_pallas=True,
                        comm=CommConfig(compressor="int8",
                                        downlink_compressor="int8",
                                        use_pallas=True,
                                        state_dtype="bfloat16",
                                        moment_dtype="float8_e4m3fn",
                                        hessian_dtype="float8_e5m2"))
        eng = FedEngine(task, fed)
        state = eng.pack_state(eng.init(jax.random.PRNGKey(2)))
        spec = eng.runtime_for(state["params"]).spec
        closed = jax.make_jaxpr(eng.round)(state, batches,
                                           jax.random.PRNGKey(7))
        scans = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
        assert [e.params["length"] for e in scans] == [J]
        counts[J] = _crossings(closed.jaxpr, spec)
        assert counts[J][True] == 0, counts
    assert counts[1][False] == counts[5][False] > 0, counts
