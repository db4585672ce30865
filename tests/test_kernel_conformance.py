"""Client-batched kernel conformance suite.

Pins the tentpole contract of the batched Pallas launches: for every
kernel in the `repro.kernels.KERNELS` registry, the ONE-launch batched
entry point over a packed (C, rows, cols) client stack is **bitwise
equal** to looping the per-client (rows, cols) launch — for fp32,
bf16 and both fp8 resident formats (e4m3/e5m2; the in-VMEM upcast
load path), at ragged sizes where no axis divides the block shape,
under both the committed tuning geometry (blocks=None) and explicit
overrides.

Against the pure-jnp oracles (`repro.kernels.ref`) the pins are
allclose: exact for fp32, one-ulp-class for the narrow formats (the
store rounds once per output, so the band is 2^-mantissa_bits: bf16
2^-8, e4m3 2^-3, e5m2 2^-2).

`stale_accum` is special-cased: its tuned path pins block_k=1 (the
bitwise per-step add order); block_k > 1 folds several wires inside
one kernel invocation, which the backend may contract into FMAs —
allclose, never promised bitwise (see stale_accum_flat's docstring).

The full shape x block sweep is `slow`-marked; the fast tier runs the
ragged base case only.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import flat as cflat
from repro.kernels import ref
from repro.kernels.quantize import (broadcast_roundtrip_batched,
                                    broadcast_roundtrip_flat,
                                    quant_roundtrip_batched,
                                    quant_roundtrip_flat,
                                    sign_roundtrip_batched,
                                    sign_roundtrip_flat,
                                    topk_threshold_batched,
                                    topk_threshold_flat,
                                    uplink_roundtrip_batched,
                                    uplink_roundtrip_flat)
from repro.kernels.sophia_update import (sophia_update_batched,
                                         sophia_update_flat,
                                         sophia_update_leaf)
from repro.kernels.stale_accum import stale_accum_flat

DTYPES = [jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn,
          jnp.float8_e5m2]
DTYPE_IDS = ["fp32", "bf16", "e4m3", "e5m2"]
#: ragged base case: no axis of (3, 20, 100) divides (2, 8, 96)
N, R, C = 3, 20, 100
RAGGED = (2, 8, 96)
#: None exercises the committed tuning.json lookup at trace time
FAST_BLOCKS = [None, RAGGED]
QMAX = 7
HP = dict(beta1=0.9, beta2=0.95, rho=0.04, eps=1e-12, weight_decay=1e-4)
LR = 3e-3


#: model leaves of the per-leaf Sophia launches: a 3-D leaf, a width
#: that is not a multiple of 1024 and spans more than one tuned tile
#: (its column block is snapped to 1152), a vector, an odd matrix
LEAF_SHAPES = [(2, 24, 384), (256, 2304), (100,), (3, 7)]


def _leaf_operands(dtype, n):
    """(spec, packed (n, rows, cols) theta, m, h, g, h_hat) over
    `LEAF_SHAPES` as the engine holds them: theta in ``dtype``, m e4m3,
    h e5m2, gradients and GNB estimate bf16, zero pad tail."""
    spec = cflat.flat_spec([jax.ShapeDtypeStruct(s, jnp.float32)
                            for s in LEAF_SHAPES])
    live = (jnp.arange(spec.padded) < spec.total).reshape(spec.rows,
                                                         spec.cols)
    ks = jax.random.split(jax.random.PRNGKey(9), 5)

    def nrm(k, s, dt, pos=False):
        x = s * jax.random.normal(k, (n, spec.rows, spec.cols))
        return jnp.where(live, jnp.abs(x) if pos else x, 0).astype(dt)

    return spec, (nrm(ks[0], 1.0, dtype),
                  nrm(ks[1], 0.1, jnp.float8_e4m3fn),
                  nrm(ks[2], 0.01, jnp.float8_e5m2, pos=True),
                  nrm(ks[3], 0.5, jnp.bfloat16),
                  nrm(ks[4], 0.02, jnp.bfloat16, pos=True))


def _by_leaf(spec, outs):
    """(theta, m, h) packed results -> [(theta, m, h) of each leaf]."""
    return list(zip(*(cflat.unpack_leaves(o, spec) for o in outs)))


def _leaf_cases(dtype, n):
    """The per-leaf launches of the engine's local loop (each leaf
    viewed 2-D, with and without the client axis) against one launch
    per client over the packed buffer, and the oracle over it."""
    def per_leaf(batched):
        spec, packed = _leaf_operands(dtype, n)
        leaves = [cflat.unpack_leaves(x, spec) for x in packed]
        launch = lambda *xs: sophia_update_leaf(
            *xs, 1.0, LR, batched=batched, interpret=True, **HP)
        if batched:
            return [launch(*xs) for xs in zip(*leaves)]
        return [tuple(jnp.stack(o) for o in zip(*(
            launch(*(x[i] for x in xs)) for i in range(n))))
            for xs in zip(*leaves)]

    def packed_per_client():
        spec, packed = _leaf_operands(dtype, n)
        outs = [sophia_update_flat(*(x[i] for x in packed), 1.0, LR,
                                   interpret=True, **HP)
                for i in range(n)]
        return _by_leaf(spec, [jnp.stack(o) for o in zip(*outs)])

    def oracle():
        spec, packed = _leaf_operands(dtype, n)
        return _by_leaf(spec, ref.sophia_update_ref(*packed, 1.0, lr=LR,
                                                    **HP))

    return {
        "sophia_update_leaf": (lambda b: per_leaf(True),
                               packed_per_client, oracle),
        "sophia_update_leaf_2d": (lambda b: per_leaf(False),
                                  packed_per_client, oracle),
    }


def _leaves(out):
    return jax.tree.leaves(out)


def _bitwise(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype
        np.testing.assert_array_equal(xa, ya)


#: one-ulp-class band per storage format (2^-mantissa_bits): the
#: narrow stores round each output once; fp32 runs the identical fp32
#: ops, but the compiled batched graph may contract mul+add into FMAs
#: where the oracle graph doesn't -> a few fp32 ulps absolute on
#: near-zero residuals
ULP_TOL = {jnp.dtype(jnp.bfloat16): 2 ** -8,
           jnp.dtype(jnp.float8_e4m3fn): 2 ** -3,
           jnp.dtype(jnp.float8_e5m2): 2 ** -2}


def _close_to_ref(out, refd, dtype):
    """Each output within the band of its storage format, or of the
    case's ``dtype`` where that is the narrower (the per-leaf Sophia
    case stores m e4m3 and h e5m2 whatever theta's dtype)."""
    for a, b in zip(_leaves(out), _leaves(refd)):
        band = max(ULP_TOL.get(jnp.dtype(dtype), 0),
                   ULP_TOL.get(jnp.dtype(a.dtype), 0))
        tol = (dict(rtol=band, atol=band) if band
               else dict(rtol=1e-6, atol=1e-6))
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def _cases(dtype, n, r, c):
    """kernel name -> (batched fn(blocks), looped fn(), ref fn()); the
    looped twin stacks n per-client 2D launches, the oracle is the
    pure-jnp ref with identical dtype contract."""
    ks = jax.random.split(jax.random.PRNGKey(7), 10)
    f32 = jnp.float32

    def nrm(k, shape, s=1.0, dt=dtype):
        return (s * jax.random.normal(k, shape)).astype(dt)

    x = nrm(ks[0], (n, r, c))
    y = nrm(ks[1], (n, r, c))
    efr = nrm(ks[2], (n, r, c), 0.01)
    g = nrm(ks[3], (n, r, c), 0.5, f32)
    hh = jnp.abs(nrm(ks[4], (n, r, c), 0.02, f32))
    m = nrm(ks[5], (n, r, c), 0.1)
    h = jnp.abs(nrm(ks[6], (n, r, c), 0.01))
    noise = jax.random.uniform(ks[7], (n, r, c), f32)
    theta2 = nrm(ks[8], (r, c))

    xf = x.astype(f32)
    scales = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / QMAX
    d_dn = (theta2.astype(f32) - y.astype(f32)) + efr.astype(f32)
    s_dn = jnp.max(jnp.abs(d_dn), axis=-1, keepdims=True) / QMAX
    d_up = (xf - theta2.astype(f32)) + efr.astype(f32)
    s_up = jnp.max(jnp.abs(d_up), axis=-1, keepdims=True) / QMAX
    cscale = jnp.linspace(0.9, 1.2, n)
    thr = jnp.percentile(jnp.abs(xf).reshape(n, -1), 70.0, axis=1)

    def stackmap(fn):
        def looped():
            outs = [fn(i) for i in range(n)]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return looped

    return {
        **_leaf_cases(dtype, n),
        "quant_roundtrip": (
            lambda b: quant_roundtrip_batched(
                x, noise, scales, qmax=QMAX, interpret=True, blocks=b),
            stackmap(lambda i: quant_roundtrip_flat(
                x[i], noise[i], scales[i], qmax=QMAX, interpret=True)),
            lambda: ref.quant_roundtrip_ref(x, noise, scales, qmax=QMAX),
        ),
        # the one server model shared (2D) across the client grid axis
        "broadcast_roundtrip": (
            lambda b: broadcast_roundtrip_batched(
                theta2, y, efr, noise, s_dn, qmax=QMAX, interpret=True,
                blocks=b),
            stackmap(lambda i: broadcast_roundtrip_flat(
                theta2, y[i], efr[i], noise[i], s_dn[i], qmax=QMAX,
                interpret=True)),
            lambda: ref.broadcast_roundtrip_ref(
                theta2[None], y, efr, noise, s_dn, qmax=QMAX),
        ),
        # per-client theta stacks (3D everywhere)
        "broadcast_roundtrip_stacked": (
            lambda b: broadcast_roundtrip_batched(
                x, y, efr, noise, s_dn, qmax=QMAX, interpret=True,
                blocks=b),
            stackmap(lambda i: broadcast_roundtrip_flat(
                x[i], y[i], efr[i], noise[i], s_dn[i], qmax=QMAX,
                interpret=True)),
            lambda: ref.broadcast_roundtrip_ref(
                x, y, efr, noise, s_dn, qmax=QMAX),
        ),
        # shared 2D start: every client trained from the same broadcast
        "uplink_roundtrip": (
            lambda b: uplink_roundtrip_batched(
                x, theta2, efr, noise, s_up, qmax=QMAX, interpret=True,
                blocks=b),
            stackmap(lambda i: uplink_roundtrip_flat(
                x[i], theta2, efr[i], noise[i], s_up[i], qmax=QMAX,
                interpret=True)),
            lambda: ref.uplink_roundtrip_ref(
                x, theta2[None], efr, noise, s_up, qmax=QMAX),
        ),
        "uplink_roundtrip_stacked": (
            lambda b: uplink_roundtrip_batched(
                x, y, efr, noise, s_up, qmax=QMAX, interpret=True,
                blocks=b),
            stackmap(lambda i: uplink_roundtrip_flat(
                x[i], y[i], efr[i], noise[i], s_up[i], qmax=QMAX,
                interpret=True)),
            lambda: ref.uplink_roundtrip_ref(
                x, y, efr, noise, s_up, qmax=QMAX),
        ),
        "sign_roundtrip": (
            lambda b: sign_roundtrip_batched(
                x, cscale, interpret=True, blocks=b),
            stackmap(lambda i: sign_roundtrip_flat(
                x[i], cscale[i], interpret=True)),
            lambda: ref.sign_roundtrip_ref(x, cscale),
        ),
        "topk_threshold": (
            lambda b: topk_threshold_batched(
                x, thr, interpret=True, blocks=b),
            stackmap(lambda i: topk_threshold_flat(
                x[i], thr[i], interpret=True)),
            lambda: ref.topk_threshold_ref(x, thr),
        ),
        "sophia_update": (
            lambda b: sophia_update_batched(
                x, m, h, g, hh, 1.0, LR, interpret=True, blocks=b,
                **HP),
            stackmap(lambda i: sophia_update_flat(
                x[i], m[i], h[i], g[i], hh[i], 1.0, LR, interpret=True,
                **HP)),
            lambda: ref.sophia_update_ref(x, m, h, g, hh, 1.0, lr=LR,
                                          **HP),
        ),
    }


CASE_NAMES = sorted(_cases(jnp.float32, 2, 4, 8))


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("blocks", FAST_BLOCKS, ids=["tuned", "ragged"])
@pytest.mark.parametrize("kernel", CASE_NAMES)
def test_batched_bitwise_equals_looped(kernel, blocks, dtype):
    """ONE batched launch == N per-client launches, bit for bit, for
    both load dtypes, under tuned and ragged-override geometry."""
    batched, looped, _ = _cases(dtype, N, R, C)[kernel]
    _bitwise(batched(blocks), looped())


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("kernel", CASE_NAMES)
def test_batched_matches_ref(kernel, dtype):
    """Batched launch vs the pure-jnp oracle: exact for fp32, one
    bf16 ulp for bf16 resident state."""
    batched, _, oracle = _cases(dtype, N, R, C)[kernel]
    _close_to_ref(batched(None), oracle(), dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
def test_stale_accum_conformance(dtype):
    """Tuned path (block_k pinned 1) is bitwise equal to any explicit
    (1, br, bc) geometry and allclose to the oracle; an indivisible
    block_k falls back to 1 (still bitwise)."""
    K = 6
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    wires = jax.random.normal(ks[0], (K, R, C)).astype(dtype)
    weights = jnp.linspace(0.25, 1.0, K)
    inv = jnp.float32(1.0) / jnp.sum(weights)
    base = stale_accum_flat(wires, weights, inv, interpret=True)
    ragged = stale_accum_flat(wires, weights, inv, interpret=True,
                              blocks=(1, 8, 96))
    _bitwise(base, ragged)
    # K=6 is not divisible by 4 -> block_k falls back to 1
    indiv = stale_accum_flat(wires, weights, inv, interpret=True,
                             blocks=(4, 8, 96))
    _bitwise(base, indiv)
    refd = ref.stale_accum_ref(wires, weights, inv)
    np.testing.assert_allclose(np.asarray(base), np.asarray(refd),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bk", [2, 3])
def test_stale_accum_blocked_k_is_allclose_not_promised_bitwise(bk):
    """block_k > 1 (explicit opt-in) keeps the add order but allows
    FMA contraction inside the kernel — the contract is allclose."""
    K = 6
    wires = jax.random.normal(jax.random.PRNGKey(13), (K, R, C))
    weights = jnp.linspace(0.25, 1.0, K)
    inv = jnp.float32(1.0) / jnp.sum(weights)
    base = stale_accum_flat(wires, weights, inv, interpret=True)
    blocked = stale_accum_flat(wires, weights, inv, interpret=True,
                               blocks=(bk, 8, 96))
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(base),
                               rtol=1e-6, atol=1e-6)


def test_tuning_fallback_and_clamp(tmp_path):
    """Missing/malformed tuning tables resolve to the safe defaults;
    resolved blocks never exceed the operand dims."""
    from repro.kernels import tuning
    assert tuning.load_tuning(str(tmp_path / "nope.json")) == {}
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert tuning.load_tuning(str(bad)) == {}
    wrong = tmp_path / "wrong.json"
    wrong.write_text('{"version": 99, "entries": {}}')
    assert tuning.load_tuning(str(wrong)) == {}
    assert tuning.blocks_for("quant_roundtrip", 2, 10, 50,
                             override=(8, 999, 999)) == (2, 10, 50)
    br, bc = tuning.blocks_2d("quant_roundtrip", 10, 50,
                              override=(999, 999))
    assert (br, bc) == (10, 50)


def test_tuning_dtype_chunk_key_precedence(monkeypatch):
    """Suffixed tuning keys resolve most-specific-first —
    ``<kernel>@<dtype>@n<chunk>`` over ``<kernel>@<dtype>`` over the
    bare ``<kernel>`` fallback (which a dtype with no suffixed entry
    also lands on)."""
    from repro.kernels import tuning
    table = {
        "quant_roundtrip": {"block_n": 1, "block_r": 11, "block_c": 13},
        "quant_roundtrip@bfloat16": {"block_n": 2, "block_r": 17,
                                     "block_c": 19},
        "quant_roundtrip@bfloat16@n3": {"block_n": 3, "block_r": 23,
                                        "block_c": 29},
    }
    monkeypatch.setattr(tuning, "load_tuning", lambda path=None: table)
    # chunk-size match wins
    assert tuning.blocks_for("quant_roundtrip", 3, 100, 100,
                             dtype=jnp.bfloat16) == (3, 23, 29)
    # no @n4 entry -> the per-dtype key
    assert tuning.blocks_for("quant_roundtrip", 4, 100, 100,
                             dtype=jnp.bfloat16) == (2, 17, 19)
    # un-suffixed dtype -> bare fallback
    assert tuning.blocks_for("quant_roundtrip", 4, 100, 100,
                             dtype=jnp.float8_e5m2) == (1, 11, 13)
    # no dtype supplied -> bare fallback (the pre-suffix behaviour)
    assert tuning.blocks_for("quant_roundtrip", 4, 100, 100) \
        == (1, 11, 13)
    # the 2D slice resolves per-dtype too
    assert tuning.blocks_2d("quant_roundtrip", 100, 100,
                            dtype=jnp.bfloat16) == (17, 19)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("blocks", [(1, 256, 1024), (2, 64, 256),
                                    (4, 100, 333)])
@pytest.mark.parametrize("shape", [(4, 54, 1024), (5, 257, 1000)])
def test_sweep_batched_equals_looped(shape, blocks, dtype):
    """The full geometry sweep at benchmark-sized stacks: every
    kernel, every block candidate, both dtypes — always bitwise."""
    for kernel, (batched, looped, _) in _cases(dtype, *shape).items():
        _bitwise(batched(blocks), looped())


@pytest.mark.parametrize("R,C", [(122880, 2304), (4608, 5760),
                                 (11520, 2304), (4608, 2304), (2, 2304),
                                 (1, 2304), (300, 1000)])
def test_sophia_leaf_blocks(R, C):
    """The Sophia kernel's block on a leaf's (R, C) view: a leaf within
    one tuned tile is one block; a wider one takes the lane-aligned
    divisor of its width nearest the tuned column block (1152 for
    minicpm's 2304 and 5760), so no block is ragged; a width that is
    not lane-aligned keeps the tuned block."""
    from repro.kernels import sophia_update as su, tuning
    _, tr, tc = tuning.tile("sophia_update", dtype=jnp.float32)
    br, bc = tuning.blocks_2d("sophia_update", R, C, dtype=jnp.float32)
    br, bc = su._snap_blocks(R, C, br, bc, tr * tc)
    if R * C <= tr * tc:
        assert (br, bc) == (R, C)
    elif C % su.LANES:
        assert (br, bc) == (min(tr, R), min(tc, C))
    else:
        assert C % bc == 0 and bc % su.LANES == 0 and br == min(tr, R)
        assert bc == (1152 if C in (2304, 5760) else tc)
