"""Fused Sophia parameter update as a Pallas TPU kernel.

The Sophia local iteration is an elementwise state machine over theta/m/h/g
(Alg. 1 lines 8, 11, 15-16). Left to XLA it becomes ~8 HBM-bound
elementwise ops (m-EMA, h-EMA select, max, div, clip, decay, axpy); fusing
them into one VMEM pass reads each of the 4 input streams once and writes
3 output streams once — the HBM-roofline optimum for this op.

TPU mapping: the engine launches the kernel once per model leaf
(`sophia_update_leaf`), each leaf viewed as (R, C) by collapsing its
leading dimensions — a bitcast, since the TPU tiles only the last two
(fp32 VREG tiling is (8,128)).  Each grid step owns one (block_r,
block_c) tile in VMEM: the tuned tile, with its column block snapped
to a divisor of the leaf's width (`_snap_blocks`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode, tuning

BLOCK_R = 256
BLOCK_C = 1024
#: lane width of a TPU vreg: a column block is a multiple of it or the
#: whole width
LANES = 128


def _snap_blocks(R: int, C: int, br: int, bc: int, tile: int):
    """Fit a tuned (br, bc) block (clamped to the operand) to an (R, C)
    leaf of ``tile`` = the tuned tile's element count.  A leaf no
    larger than one tile is one block.  Otherwise the column block is
    the multiple of `LANES` nearest ``bc`` that divides C, so no block
    is ragged: minicpm's widths 2304 and 5760 both take 1152 where the
    tuned 1024 divides neither.  A width that is not a multiple of
    `LANES` keeps ``bc`` (the last block is masked)."""
    if R * C <= tile:
        return R, C
    if C % bc and C % LANES == 0:
        bc = min((d for d in range(LANES, C + 1, LANES) if C % d == 0),
                 key=lambda d: (abs(d - bc), -d))
    return br, bc


def _sophia_kernel(theta_ref, m_ref, h_ref, g_ref, hhat_ref, flags_ref,
                   theta_out, m_out, h_out, *, beta1, beta2, rho, eps,
                   weight_decay):
    """One VMEM tile of the fused update.

    flags_ref: (1, 2) scalars — [do_h_update (0/1), lr]. Runtime inputs
    (lr is schedule-driven and traced).  Loads upcast to fp32, stores
    downcast to each output's dtype (bf16 resident state computes in
    fp32; no-op casts for fp32 state).
    """
    do_h = flags_ref[0, 0]
    lr = flags_ref[0, 1]
    g = g_ref[...].astype(jnp.float32)
    h0 = h_ref[...].astype(jnp.float32)
    m = beta1 * m_ref[...].astype(jnp.float32) + (1.0 - beta1) * g  # Eq. 9
    h_new = beta2 * h0 + (1.0 - beta2) * hhat_ref[...].astype(
        jnp.float32)                                               # Eq. 10
    h = do_h * h_new + (1.0 - do_h) * h0
    theta = theta_ref[...].astype(jnp.float32)
    theta = theta - lr * weight_decay * theta                      # line 15
    step = m / jnp.maximum(h, eps)
    step = jnp.clip(step, -rho, rho)                               # Eq. 11
    theta_out[...] = (theta - lr * step).astype(theta_out.dtype)   # line 16
    m_out[...] = m.astype(m_out.dtype)
    h_out[...] = h.astype(h_out.dtype)


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "rho",
                                             "eps", "weight_decay",
                                             "interpret"))
def sophia_update_flat(theta, m, h, g, h_hat, do_h, lr, *, beta1, beta2,
                       rho, eps, weight_decay, interpret=None):
    """Fused update over a flat (R, C) view. Returns (theta, m, h),
    each in its input's storage dtype (fp32, bf16 or fp8 resident
    state — m and h may each carry their own dtype via
    `CommConfig.moment_dtype` / `hessian_dtype`; compute is fp32
    in-kernel either way).

    interpret: None follows the platform (the interpreter on CPU,
    Mosaic on a TPU); pass a bool to force either.
    """
    R, C = theta.shape
    br, bc = tuning.blocks_2d("sophia_update", R, C, dtype=theta.dtype)
    br, bc = _snap_blocks(R, C, br, bc, math.prod(tuning.tile(
        "sophia_update", dtype=theta.dtype)[1:]))
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc))
    flags = jnp.stack([jnp.asarray(do_h, jnp.float32).reshape(()),
                       jnp.asarray(lr, jnp.float32).reshape(())]
                      ).reshape(1, 2)

    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    smem = pl.BlockSpec((1, 2), lambda i, j: (0, 0))

    kernel = functools.partial(
        _sophia_kernel, beta1=beta1, beta2=beta2, rho=rho, eps=eps,
        weight_decay=weight_decay)
    out_shape = [jax.ShapeDtypeStruct((R, C), x.dtype)
                 for x in (theta, m, h)]
    # named scope: the kernel launch shows up as an annotated span in
    # jax.profiler traces (--profile-dir); metadata only, the lowered
    # computation is unchanged
    with jax.named_scope("pallas:sophia_update_flat"):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[tile, tile, tile, tile, tile, smem],
            out_specs=[tile, tile, tile],
            out_shape=out_shape,
            interpret=interpret_mode(interpret),
        )(theta, m, h, g, h_hat, flags)


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "rho",
                                             "eps", "weight_decay",
                                             "interpret", "blocks"))
def sophia_update_batched(theta, m, h, g, h_hat, do_h, lr, *, beta1,
                          beta2, rho, eps, weight_decay,
                          interpret=None, blocks=None):
    """`sophia_update_flat` over packed (N, R, C) client stacks in ONE
    launch with a leading client grid dimension.  Reuses the same
    elementwise kernel body over 3D blocks, so results are bitwise
    equal to N per-client launches (tests/test_kernel_conformance.py).
    do_h / lr stay shared scalars — every client steps the same local
    iteration of the same round.  blocks: optional static (bn, br, bc)
    override of the tuned geometry."""
    N, R, C = theta.shape
    bn, br, bc = tuning.blocks_for("sophia_update", N, R, C,
                                   override=blocks, dtype=theta.dtype)
    if blocks is None:
        br, bc = _snap_blocks(R, C, br, bc, math.prod(tuning.tile(
            "sophia_update", N, dtype=theta.dtype)[1:]))
    grid = (pl.cdiv(N, bn), pl.cdiv(R, br), pl.cdiv(C, bc))
    flags = jnp.stack([jnp.asarray(do_h, jnp.float32).reshape(()),
                       jnp.asarray(lr, jnp.float32).reshape(())]
                      ).reshape(1, 2)

    tile3 = pl.BlockSpec((bn, br, bc), lambda n, i, j: (n, i, j))
    smem = pl.BlockSpec((1, 2), lambda n, i, j: (0, 0))

    kernel = functools.partial(
        _sophia_kernel, beta1=beta1, beta2=beta2, rho=rho, eps=eps,
        weight_decay=weight_decay)
    out_shape = [jax.ShapeDtypeStruct((N, R, C), x.dtype)
                 for x in (theta, m, h)]
    with jax.named_scope("pallas:sophia_update_batched"):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[tile3, tile3, tile3, tile3, tile3, smem],
            out_specs=[tile3, tile3, tile3],
            out_shape=out_shape,
            interpret=interpret_mode(interpret),
        )(theta, m, h, g, h_hat, flags)


def sophia_update_leaf(theta, m, h, g, h_hat, do_h, lr, *, batched,
                       beta1, beta2, rho, eps, weight_decay,
                       interpret=None):
    """The fused update over one model leaf of any rank, in the leaf's
    own shape: the leaf is viewed as (R, C) by collapsing its leading
    dimensions — (N, R, C) behind a leading client axis when
    ``batched`` — and launched through `sophia_update_flat` (or
    `sophia_update_batched`).  A vector leaf is one row, a scalar one
    element.  Per coordinate the same kernel body as one launch over
    the packed buffer, so the values are bitwise those
    (tests/test_kernel_conformance.py)."""
    shape = theta.shape
    lead, body = (shape[:1], shape[1:]) if batched else ((), shape)
    view = lead + (math.prod(body[:-1]), body[-1] if body else 1)
    fn = sophia_update_batched if batched else sophia_update_flat
    outs = fn(*(x.reshape(view) for x in (theta, m, h, g, h_hat)), do_h,
              lr, beta1=beta1, beta2=beta2, rho=rho, eps=eps,
              weight_decay=weight_decay, interpret=interpret)
    return tuple(o.reshape(shape) for o in outs)
