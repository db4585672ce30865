"""Fused staleness-weighted delta-accumulate Pallas TPU kernel.

The virtual-time scheduler (`repro.sched`) aggregates a buffer of K
arrival wires with per-arrival staleness weights:

    agg = inv_norm * sum_k weights[k] * wires[k]

(`inv_norm = 1/sum(weights)` for the semisync weighted mean, 1.0 for
the async unnormalized apply).  Left to XLA this is a broadcast
multiply materialising a (K, R, C) temporary plus a reduction — two
HBM passes over the K wires.  The kernel walks the K axis innermost
over each (R, C) tile, accumulating in VMEM: every wire is read once
and the aggregate written once, the same HBM-roofline argument as the
quantize round-trips in `repro.kernels.quantize`.

Layout matches `repro.comm.flat`: fp32 (rows, cols) tiles.  The
reference oracle is `repro.kernels.ref.stale_accum_ref`;
``interpret`` defaults to the platform (`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode, tuning

BLOCK_R = 256
BLOCK_C = 1024


def _stale_accum_kernel(x_ref, w_ref, s_ref, out_ref, *, num_steps,
                        block_k):
    """One (br, bc) output tile, revisited across the K-axis grid
    steps.  Each step folds ``block_k`` wires into the tile with the
    same left-to-right fp32 adds as block_k=1 grid steps would (the
    in-kernel loop unrolls statically), so the blocked launch is
    bitwise equal to the unblocked one.  Loads upcast to fp32 in VMEM
    (bf16 wires stream at half the HBM bandwidth; the accumulator is
    always fp32)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref[...]
    for kk in range(block_k):
        acc = acc + w_ref[kk, 0, 0] * x_ref[kk, ...].astype(jnp.float32)
    out_ref[...] = acc

    @pl.when(k == num_steps - 1)
    def _scale():
        out_ref[...] *= s_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret", "blocks"))
def stale_accum_flat(wires, weights, inv_norm, *, interpret=None,
                     blocks=None):
    """Fused weighted accumulate over K arrival wires.

    wires: (K, R, C) packed deltas (fp32, bf16 or fp8 — loads upcast
    in-kernel, so narrow wires never materialize an fp32 copy in HBM);
    weights: (K,) staleness weights; inv_norm: scalar final scale
    (traced).  Returns the (R, C) fp32 aggregate
    ``inv_norm * sum_k weights[k] * wires[k]``.  blocks: optional
    static (bk, br, bc) override of the tuned geometry.

    The committed tuning only resizes (br, bc): folding several wires
    inside one kernel invocation (bk > 1) keeps the fp32 add order
    but lets the backend contract mul+add into FMAs, which is
    allclose- but not bitwise-equal to per-step accumulation — so
    bk > 1 is opt-in via ``blocks`` and never chosen by the tuned
    path (tests/test_kernel_conformance.py pins both behaviours).
    """
    K, R, C = wires.shape
    if blocks is not None:
        bk, br, bc = tuning.blocks_for("stale_accum", K, R, C,
                                       override=blocks)
    else:
        bk = 1
        br, bc = tuning.blocks_2d("stale_accum", R, C,
                                  dtype=wires.dtype)
    # accumulation revisits the output tile across K-axis steps, so a
    # partial tail block would double-count padding: only block K when
    # it divides exactly
    if K % bk != 0:
        bk = 1
    # K innermost: each output tile is revisited on consecutive grid
    # steps (the TPU-legal accumulation pattern)
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc), K // bk)
    # (K, 1, 1): a (bk, 1, 1) block keeps its last two dims equal to
    # the array's, which Mosaic's (8, 128) block rule requires — a
    # (bk, 1) block over a (K, 1) array is refused for K > bk
    w3 = jnp.asarray(weights, jnp.float32).reshape(K, 1, 1)
    s2 = jnp.asarray(inv_norm, jnp.float32).reshape(1, 1)
    # named scope: annotated span in jax.profiler traces; metadata only
    with jax.named_scope("pallas:stale_accum_flat"):
        return pl.pallas_call(
            functools.partial(_stale_accum_kernel, num_steps=K // bk,
                              block_k=bk),
            grid=grid,
            in_specs=[pl.BlockSpec((bk, br, bc),
                                   lambda i, j, k: (k, i, j)),
                      pl.BlockSpec((bk, 1, 1), lambda i, j, k: (k, 0, 0)),
                      pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))],
            out_specs=pl.BlockSpec((br, bc), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
            interpret=interpret_mode(interpret),
        )(wires, w3, s2)
