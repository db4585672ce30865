"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sophia import sophia_step_flat
from repro.kernels.ref import sophia_update_ref, uplink_roundtrip_ref
from repro.kernels.sophia_update import sophia_update_flat

HP = dict(beta1=0.9, beta2=0.95, rho=0.04, eps=1e-12, weight_decay=1e-4)


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


@pytest.mark.parametrize("shape", [(8, 128), (256, 1024), (300, 1024),
                                   (1, 1024), (257, 1000), (1024, 2048)])
@pytest.mark.parametrize("do_h", [0.0, 1.0])
def test_flat_kernel_matches_ref_shapes(shape, do_h):
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    ks = jax.random.split(key, 5)
    theta = _rand(ks[0], shape)
    m = _rand(ks[1], shape, scale=0.1)
    h = jnp.abs(_rand(ks[2], shape, scale=0.01))
    g = _rand(ks[3], shape, scale=0.5)
    hh = jnp.abs(_rand(ks[4], shape, scale=0.02))
    lr = 3e-3
    out = sophia_update_flat(theta, m, h, g, hh, do_h, lr, interpret=True,
                             **HP)
    ref = sophia_update_ref(theta, m, h, g, hh, do_h, lr=lr, **HP)
    for a, b in zip(out, ref):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pytree_fused_step_matches_core(dtype):
    from repro.core import sophia as core_sophia
    key = jax.random.PRNGKey(0)
    params = {"a": _rand(key, (33, 65), dtype),
              "b": {"c": _rand(jax.random.fold_in(key, 1), (7,), dtype),
                    "d": _rand(jax.random.fold_in(key, 2), (4, 5, 6), dtype)}}
    grads = jax.tree.map(lambda x: 0.1 * jnp.ones_like(x), params)
    st = core_sophia.init_state(params)
    h_hat = jax.tree.map(lambda x: 0.2 * jnp.ones_like(x), params)
    kwargs = dict(lr=1e-2, **HP)
    ref_p, ref_st = core_sophia.sophia_step(
        params, grads, st, h_hat, jnp.asarray(True), use_pallas=False,
        **kwargs)
    out_p, out_st = core_sophia.sophia_step(
        params, grads, st, h_hat, jnp.asarray(True), use_pallas=True,
        **kwargs)
    tol = dict(rtol=2e-2, atol=1e-3) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-7)
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(out_p)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)
    for a, b in zip(jax.tree.leaves(ref_st.h), jax.tree.leaves(out_st.h)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_fused_step_traced_lr_and_flag():
    """lr and do_h arrive as tracers from the schedule/round index."""
    key = jax.random.PRNGKey(1)
    params = {"w": _rand(key, (130, 70))}
    grads = jax.tree.map(jnp.ones_like, params)
    h_hat = jax.tree.map(jnp.ones_like, params)

    @jax.jit
    def step(p, lr, do_h):
        return sophia_step_flat(p, jax.tree.map(jnp.zeros_like, p),
                                jax.tree.map(jnp.zeros_like, p),
                                grads, h_hat, do_h, lr=lr, **HP,
                                use_pallas=True)

    p1, m1, h1 = step(params, jnp.asarray(1e-2), jnp.asarray(1.0))
    p2, m2, h2 = step(params, jnp.asarray(0.0), jnp.asarray(0.0))
    assert not np.allclose(p1["w"], params["w"])
    np.testing.assert_allclose(p2["w"], params["w"])   # lr=0 -> no-op
    np.testing.assert_allclose(h2["w"], 0.0)           # do_h=0 -> h frozen


@pytest.mark.parametrize("qmax", [127, 7])
@pytest.mark.parametrize("with_ef", [False, True])
def test_uplink_roundtrip_kernel_matches_ref(qmax, with_ef):
    """Fused uplink encode (delta + EF + quant round-trip + residual)
    == pure-jnp reference, and consistent with the unfused
    quantize-a-precomputed-delta path."""
    from repro.kernels.quantize import (quant_roundtrip_flat,
                                        uplink_roundtrip_flat)
    key = jax.random.PRNGKey(3)
    theta = _rand(key, (300, 130))
    start = theta + 0.05 * _rand(jax.random.fold_in(key, 1), (300, 130))
    ef = (0.01 * _rand(jax.random.fold_in(key, 2), (300, 130))
          if with_ef else jnp.zeros_like(theta))
    delta = theta - start + ef
    u = jax.random.uniform(jax.random.fold_in(key, 3), delta.shape)
    scale = jnp.max(jnp.abs(delta), axis=1, keepdims=True) / qmax
    xhat, resid = uplink_roundtrip_flat(theta, start, ef, u, scale,
                                        qmax=qmax, interpret=True)
    ref_x, ref_r = uplink_roundtrip_ref(theta, start, ef, u, scale,
                                        qmax=qmax)
    np.testing.assert_allclose(np.asarray(xhat), np.asarray(ref_x),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(resid), np.asarray(ref_r),
                               rtol=1e-6, atol=1e-7)
    unfused = quant_roundtrip_flat(delta, u, scale, qmax=qmax,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(xhat), np.asarray(unfused),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(xhat + resid), np.asarray(delta),
                               rtol=1e-6, atol=1e-6)
