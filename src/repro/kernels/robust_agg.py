"""Fused sort-free robust-aggregation Pallas TPU kernel.

The robust combine of `repro.robust.aggregators` over a (K, R, C)
arrival stack:

    x'_k  = scales[k] * wires[k]                      (norm-clip rescale)
    mask  = survivors after dropping the `trim` per-coordinate
            extremes per side
    num   = sum_k  mask_k * weights[k] * x'_k
    out   = num / sum_k mask_k * weights[k]           (normalize=True)
          = num                                       (normalize=False)

Left to XLA, per-coordinate trimming is a (K, R, C) sort — O(K log K)
passes and several HBM-sized temporaries.  The kernel is *sort-free*:
each (br, bc) tile holds the full K axis in VMEM and extracts one
extreme per pass with an argmax/iota mask (``trim`` is small — the
trim count is capped at ``(K-1)//2`` — so 2*trim statically-unrolled
passes beat a sort for every real buffer size), reading every wire
from HBM exactly once.  ``coordinate_median`` is the same kernel at
the maximal trim: the surviving one (odd K) or two (even K) middle
values ARE the median.

Ties break to the lowest arrival index (argmax semantics), as in the
oracle `repro.kernels.ref.robust_agg_ref`; kernel vs ref is pinned
per-dtype by tests/test_robust.py to one fp32 rounding per add.  Layout matches
`repro.comm.flat`: fp32/bf16/fp8 (K, rows, cols) stacks, loads
upcast to fp32 in VMEM, fp32 out.  ``interpret`` defaults to the
platform (`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode, tuning

#: elements of one (K, br, bc) wire block: the body keeps about six
#: fp32 temporaries of the block beside its double-buffered input, so
#: 2^18 elements (1 MiB at fp32) keeps a launch inside the 16 MiB
#: scoped-VMEM limit of a TPU v5e at any K the block shrinks for
BLOCK_ELEMS = 1 << 18
#: row tile of the narrowest wire dtype (fp8 packs (32, 128) tiles)
SUBLANES = 32
LANES = 128


def _tile(K, R, C, br, bc):
    """Shrink the tuned (br, bc) tile so the whole K axis of a block
    fits `BLOCK_ELEMS`: rows first (whole fp8 row tiles), then lanes
    (whole 128-lane columns).  VMEM use then stays flat as the
    arrival count grows, up to K = BLOCK_ELEMS / (32 * 128) = 64."""
    rows = max(SUBLANES, BLOCK_ELEMS // (K * bc) // SUBLANES * SUBLANES)
    br = min(br, rows, R)
    if K * br * bc > BLOCK_ELEMS:
        bc = max(LANES, BLOCK_ELEMS // (K * br) // LANES * LANES)
    return br, min(bc, C)


def _survivor_mask(x, trim: int):
    """(K, br, bc) bool survivor mask after removing `trim` extremes
    per side per coordinate — one occurrence per pass, first arrival
    index wins ties."""
    mask = jnp.ones(x.shape, jnp.bool_)
    if trim == 0:
        return mask
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    for sign in (1.0, -1.0):
        for _ in range(trim):
            cand = jnp.where(mask, jnp.float32(sign) * x, -big)
            hit = jnp.argmax(cand, axis=0)
            mask = mask & (iota != hit[None])
    return mask


def _robust_agg_kernel(x_ref, w_ref, s_ref, out_ref, *, trim,
                       normalize):
    """One (br, bc) output tile; the whole K axis lives in the block."""
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].reshape(-1, 1, 1)
    s = s_ref[...].reshape(-1, 1, 1)
    xs = s * x
    wm = jnp.where(_survivor_mask(xs, trim), w, jnp.float32(0.0))
    num = jnp.sum(xs * wm, axis=0)
    if normalize:
        num = num / jnp.sum(wm, axis=0)
    out_ref[...] = num


@functools.partial(jax.jit, static_argnames=("trim", "normalize",
                                             "interpret", "blocks"))
def robust_agg_flat(wires, weights, scales, *, trim: int,
                    normalize: bool = True, interpret=None,
                    blocks=None):
    """Fused sort-free trimmed-mean/clip combine of K arrival wires.

    wires: (K, R, C) packed contributions (fp32, bf16 or fp8 — loads
    upcast in-kernel); weights: (K,) arrival weights; scales: (K,)
    per-arrival value rescales (the norm-clip factors; ones when
    unused).  ``trim`` extremes are dropped per coordinate per side
    (static; requires ``2*trim < K``).  Returns the (R, C) fp32
    robust aggregate.  blocks: optional static (br, bc) override of
    the tuned tile, which is otherwise shrunk to the K-wide VMEM
    budget (`_tile`).
    """
    K, R, C = wires.shape
    if not 2 * trim < K:
        raise ValueError(f"trim={trim} must satisfy 2*trim < K={K}")
    if blocks is not None:
        br, bc = blocks
        br, bc = min(br, R), min(bc, C)
    else:
        br, bc = _tile(K, R, C, *tuning.blocks_2d(
            "robust_agg", R, C, dtype=wires.dtype))
    # 2D grid — no tile revisits: trimming needs all K wires at once,
    # so K is a block axis, not a grid axis
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc))
    w2 = jnp.asarray(weights, jnp.float32).reshape(K, 1)
    s2 = jnp.asarray(scales, jnp.float32).reshape(K, 1)
    with jax.named_scope("pallas:robust_agg_flat"):
        return pl.pallas_call(
            functools.partial(_robust_agg_kernel, trim=trim,
                              normalize=normalize),
            grid=grid,
            in_specs=[pl.BlockSpec((K, br, bc),
                                   lambda i, j: (0, i, j)),
                      pl.BlockSpec((K, 1), lambda i, j: (0, 0)),
                      pl.BlockSpec((K, 1), lambda i, j: (0, 0))],
            out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
            interpret=interpret_mode(interpret),
        )(wires, w2, s2)
