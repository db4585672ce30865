"""Compile rehearsals of the Pallas kernels for a TPU v5e.

Every kernel family of `repro.kernels.KERNELS` is lowered through
Mosaic (``interpret=False``) and compiled for one chip of a described
``v5e:2x2`` topology at the packed wire geometry of minicpm-2b cut to
2 layers — the model the chip smoke test trains — with the committed
tuning geometry, at fp32, bf16 and e4m3 resident state; the Sophia
kernel also at each of that model's leaves, as the local loop launches
it.  The compiler
refuses what the interpreter never checks: blocks that break the
(8, 128) tiling rule, and tiles whose double-buffered operands exceed
the scoped VMEM limit.  Nothing runs, so values are the conformance
suite's business (tests/test_kernel_conformance.py).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the test
workers all import this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.comm import flat as cflat
from repro.kernels import KERNELS
from repro.kernels.quantize import (broadcast_roundtrip_batched,
                                    quant_roundtrip_batched,
                                    sign_roundtrip_batched,
                                    topk_threshold_batched,
                                    uplink_roundtrip_batched)
from repro.kernels.robust_agg import robust_agg_flat
from repro.kernels.sophia_update import (sophia_update_batched,
                                         sophia_update_leaf)
from repro.kernels.stale_accum import stale_accum_flat
from repro.models import transformer as T

DTYPES = [jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn]
DTYPE_IDS = ["fp32", "bf16", "e4m3"]
#: arrivals of the scheduler kernels (robust_agg trims 1 per side)
K = 4
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def wire_geometry():
    """(rows, cols) of the packed minicpm-2b 2-layer model."""
    cfg = configs.get_model_config("minicpm-2b").with_depth(2)
    params = jax.eval_shape(T.LMTask(cfg).init, jax.random.PRNGKey(0))
    spec = cflat.flat_spec(params, cols=1024)
    return spec.rows, spec.cols


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _launch(kernel, dtype, R, C, put):
    """(fn, args) of one client's batched launch: resident state in
    ``dtype``, gradients/noise/scales fp32 as in the engine."""
    x = put((1, R, C), dtype)
    u = put((1, R, C), F32)
    sc = put((1, R, 1), F32)
    per_client = put((1,), F32)
    wires, weights = put((K, R, C), dtype), put((K,), F32)
    return {
        "quant_roundtrip": (lambda x, u, s: quant_roundtrip_batched(
            x, u, s, qmax=127, interpret=False), (x, u, sc)),
        "broadcast_roundtrip": (
            lambda t, r, e, u, s: broadcast_roundtrip_batched(
                t, r, e, u, s, qmax=127, interpret=False),
            (put((R, C), F32), x, x, u, sc)),
        "uplink_roundtrip": (
            lambda t, st, e, u, s: uplink_roundtrip_batched(
                t, st, e, u, s, qmax=127, interpret=False),
            (put((1, R, C), F32), x, x, u, sc)),
        "sign_roundtrip": (lambda x, s: sign_roundtrip_batched(
            x, s, interpret=False), (x, per_client)),
        "topk_threshold": (lambda x, s: topk_threshold_batched(
            x, s, interpret=False), (x, per_client)),
        "sophia_update": (
            lambda t, m, h, g, hh: sophia_update_batched(
                t, m, h, g, hh, True, 1e-3, beta1=0.9, beta2=0.95,
                rho=0.04, eps=1e-12, weight_decay=1e-4,
                interpret=False),
            (put((1, R, C), F32), x, x, u, u)),
        "stale_accum": (lambda w, ww: stale_accum_flat(
            w, ww, jnp.float32(0.5), interpret=False),
            (wires, weights)),
        "robust_agg": (lambda w, ww, s: robust_agg_flat(
            w, ww, s, trim=1, normalize=False, interpret=False),
            (wires, weights, put((K,), F32))),
    }[kernel]


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, dtype, one_chip, wire_geometry,
                                 no_persistent_cache):
    R, C = wire_geometry
    if kernel == "sophia_update" and dtype == jnp.float32:
        # fp32 m/h make all eight operands 1.5 GB each at this size:
        # 12 GB of program plus 7.5 GB of arguments exceed the chip's
        # 16 GB of HBM whatever the kernel does.  Tiles and grid rules
        # do not depend on the row count, so half the rows suffice.
        R //= 2

    def put(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn, args = _launch(kernel, dtype, R, C, put)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wire_geometry_is_minicpm_2_layers(wire_geometry):
    """The rehearsed geometry is the full-width model: 405,220,608
    coordinates in 1024-wide rows."""
    rows, cols = wire_geometry
    assert cols == 1024 and rows == -(-405_220_608 // 1024)
    cfg = configs.get_model_config("minicpm-2b").with_depth(2)
    assert dataclasses.replace(cfg, num_layers=40) == \
        configs.get_model_config("minicpm-2b")


#: minicpm-2b's leaves at 2 layers; the local loop launches the Sophia
#: kernel once per leaf, viewed (R, C) by collapsing the leading dims
MINICPM_LEAVES = {
    "embed": (122880, 2304),
    "w_gate_up": (2, 2304, 5760),
    "w_down": (2, 5760, 2304),
    "wq_wk_wv_wo": (2, 2304, 2304),
    "ln1_ln2": (2, 2304),
    "final_norm": (2304,),
}


@pytest.mark.parametrize("leaf", sorted(MINICPM_LEAVES))
def test_sophia_leaf_launch_compiles_for_v5e(leaf, one_chip,
                                             no_persistent_cache):
    """One client's per-leaf Sophia launch as the benchmarked round
    makes it: fp32 theta, e4m3 m, e5m2 h, bf16 gradients and GNB
    estimate, each leaf with the client axis in front."""
    shape = (1,) + MINICPM_LEAVES[leaf]

    def put(dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = (put(F32), put(jnp.float8_e4m3fn), put(jnp.float8_e5m2),
            put(jnp.bfloat16), put(jnp.bfloat16))
    compiled = jax.jit(lambda t, m, h, g, hh: sophia_update_leaf(
        t, m, h, g, hh, True, 1e-3, batched=True, beta1=0.9, beta2=0.95,
        rho=0.04, eps=1e-12, weight_decay=1e-4, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
