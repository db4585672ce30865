"""Fused compress->decompress ("wire round-trip") Pallas TPU kernels.

The comm layer simulates the uplink in-graph: quantize the packed
(rows, cols) delta buffer and immediately dequantize it, because the
server-side aggregation consumes the *reconstruction*.  Left to XLA the
round-trip is ~5 HBM-bound elementwise ops (scale-div, add-noise, floor,
clip, scale-mul); fusing them reads each input stream once and writes
the reconstruction once — the same HBM-roofline argument as
`sophia_update`.

Layout matches `repro.comm.flat`: (rows, cols) tiles, one quantization
scale per row.  Stochastic-rounding noise is generated outside the
kernel with `jax.random` and streamed in, so the reference path
(`repro.kernels.ref`) sees the identical noise and the Pallas-vs-ref
equivalence is exact.  ``interpret`` defaults to the platform
(`repro.kernels.interpret_mode`): the interpreter on CPU, Mosaic on a
TPU.

Dtype contract (`CommConfig.state_dtype` / `moment_dtype` /
`hessian_dtype`): the state tiles (model / replica / EF streams) may
be stored in a narrower resident format — bf16, or the fp8 formats
float8_e4m3fn / float8_e5m2 — and every kernel upcasts its loads to
fp32 in VMEM, computes in fp32, and stores each output in that
output's declared dtype (the first state input's dtype), so a bf16
buffer costs half and an fp8 buffer a quarter of the fp32 HBM traffic
without changing the arithmetic.  Noise and scales are always fp32.
With fp32 inputs the casts are no-ops and the kernels are
bit-identical to their pre-dtype versions.  Launch geometry resolves
per (kernel, storage dtype, client-chunk size) through
`repro.kernels.tuning`.

Client batching: each round-trip also has a ``*_batched`` entry point
over the packed (N, rows, cols) client stack — ONE launch with a
leading client grid dimension instead of N per-client launches.  The
batched launches reuse the same elementwise kernel bodies over 3D
blocks, so they are bitwise equal to the looped per-client results
(tests/test_kernel_conformance.py).  Block shapes come from the
committed `repro.kernels.tuning` table (``blocks=`` overrides, for
the autotuner sweep).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode, tuning

BLOCK_R = 256
BLOCK_C = 1024


def _grid_specs(R, C, kernel="quant_roundtrip", dtype=None):
    br, bc = tuning.blocks_2d(kernel, R, C, dtype=dtype)
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc))
    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    rowcol = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return grid, tile, rowcol, scalar


def _grid_specs3(N, R, C, kernel, blocks, dtype=None):
    """Launch geometry of a client-batched (N, R, C) kernel: the grid
    gains a leading client axis; ``shared2`` maps an unbatched (R, C)
    operand (e.g. the one server model every client receives) into the
    same (br, bc) block for every client grid step, where the kernel
    body broadcasts it against the (bn, br, bc) stacks.  ``dtype`` is
    the primary state operand's storage dtype — the tuning table may
    commit per-dtype / per-chunk-size winners."""
    bn, br, bc = tuning.blocks_for(kernel, N, R, C, override=blocks,
                                   dtype=dtype)
    grid = (pl.cdiv(N, bn), pl.cdiv(R, br), pl.cdiv(C, bc))
    tile3 = pl.BlockSpec((bn, br, bc), lambda n, i, j: (n, i, j))
    rowcol3 = pl.BlockSpec((bn, br, 1), lambda n, i, j: (n, i, 0))
    client3 = pl.BlockSpec((bn, 1, 1), lambda n, i, j: (n, 0, 0))
    shared2 = pl.BlockSpec((br, bc), lambda n, i, j: (i, j))
    return grid, tile3, rowcol3, client3, shared2


# ------------------------------------------------- stochastic quantization
def _quant_kernel(x_ref, u_ref, s_ref, out_ref, *, qmax):
    """q = clip(floor(x/scale + u), ±qmax); out = q * scale (one pass).
    Loads upcast to fp32, the store downcasts to the output dtype."""
    s = s_ref[...]                                   # (br, 1) row scales
    safe = jnp.where(s > 0, s, 1.0)
    q = jnp.floor(x_ref[...].astype(jnp.float32) / safe + u_ref[...])
    q = jnp.clip(q, -qmax, qmax)
    out_ref[...] = (q * s).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("qmax", "interpret"))
def quant_roundtrip_flat(x, noise, scale, *, qmax: int,
                         interpret=None):
    """Fused stochastic quantize->dequantize over a (R, C) fp32 buffer.

    noise: U[0,1) fp32 array of x.shape; scale: (R, 1) fp32 per-row
    scales.  Returns the dequantized reconstruction (R, C) in ``x``'s
    dtype (fp32 compute in-kernel; see the module dtype contract).
    """
    R, C = x.shape
    grid, tile, rowcol, _ = _grid_specs(R, C, dtype=x.dtype)
    return pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=grid,
        in_specs=[tile, tile, rowcol],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, noise, scale)


# ---------------------------------------------- fused downlink broadcast
def _broadcast_kernel(t_ref, r_ref, e_ref, u_ref, s_ref, m_ref, d_ref,
                      *, qmax):
    """Delta-code + stochastic quant round-trip + apply + residual:
    d = (theta - ref) + ef; xhat = clip(floor(d/s + u)) * s;
    model' = ref + xhat; resid' = d - xhat — one pass over 4 streams
    instead of the ~8 HBM-bound elementwise ops XLA would emit.
    Loads upcast to fp32, stores downcast to each output's dtype."""
    s = s_ref[...]
    safe = jnp.where(s > 0, s, 1.0)
    t = t_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    d = (t - r) + e_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.floor(d / safe + u_ref[...]), -qmax, qmax)
    xhat = q * s
    m_ref[...] = (r + xhat).astype(m_ref.dtype)
    d_ref[...] = (d - xhat).astype(d_ref.dtype)


@functools.partial(jax.jit, static_argnames=("qmax", "interpret"))
def broadcast_roundtrip_flat(theta, ref, ef, noise, scale, *, qmax: int,
                             interpret=None):
    """Fused downlink step over (R, C) fp32 buffers (see
    `repro.comm.downlink.broadcast`).

    theta: packed server model; ref: the client's last-received model;
    ef: server-side EF residual (zeros when EF is off); noise: U[0,1)
    of theta.shape; scale: (R, 1) per-row scales of the corrected
    delta.  Returns (new client model, new EF residual).
    """
    R, C = theta.shape
    grid, tile, rowcol, _ = _grid_specs(R, C, "broadcast_roundtrip",
                                        dtype=theta.dtype)
    return pl.pallas_call(
        functools.partial(_broadcast_kernel, qmax=qmax),
        grid=grid,
        in_specs=[tile, tile, tile, tile, rowcol],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((R, C), theta.dtype),
                   jax.ShapeDtypeStruct((R, C), theta.dtype)],
        interpret=interpret_mode(interpret),
    )(theta, ref, ef, noise, scale)


# ------------------------------------------------ fused uplink encode
def _uplink_kernel(t_ref, s_ref, e_ref, u_ref, sc_ref, x_ref, r_ref,
                   *, qmax):
    """Delta-code + EF + stochastic quant round-trip + residual:
    d = (theta_i - theta_i^rx) + ef; xhat = clip(floor(d/s + u)) * s;
    resid' = d - xhat — the uplink twin of `_broadcast_kernel`, one
    VMEM pass over 3 input streams instead of the subtract/add/quant
    chain XLA would emit.  Loads upcast to fp32, stores downcast to
    each output's dtype."""
    sc = sc_ref[...]
    safe = jnp.where(sc > 0, sc, 1.0)
    d = (t_ref[...].astype(jnp.float32) - s_ref[...].astype(jnp.float32)
         + e_ref[...].astype(jnp.float32))
    q = jnp.clip(jnp.floor(d / safe + u_ref[...]), -qmax, qmax)
    xhat = q * sc
    x_ref[...] = xhat.astype(x_ref.dtype)
    r_ref[...] = (d - xhat).astype(r_ref.dtype)


@functools.partial(jax.jit, static_argnames=("qmax", "interpret"))
def uplink_roundtrip_flat(theta, start, ef, noise, scale, *, qmax: int,
                          interpret=None):
    """Fused uplink encode over (R, C) fp32 buffers (see
    `repro.comm.compressors.Compressor.encode_delta`).

    theta: the client's locally-trained packed model; start: the packed
    model it trained from (its received replica); ef: client-side EF
    residual (zeros when EF is off); noise: U[0,1) of theta.shape;
    scale: (R, 1) per-row scales of the corrected delta.  Returns
    (decoded wire reconstruction, new EF residual).
    """
    R, C = theta.shape
    grid, tile, rowcol, _ = _grid_specs(R, C, "uplink_roundtrip",
                                        dtype=theta.dtype)
    return pl.pallas_call(
        functools.partial(_uplink_kernel, qmax=qmax),
        grid=grid,
        in_specs=[tile, tile, tile, tile, rowcol],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((R, C), theta.dtype),
                   jax.ShapeDtypeStruct((R, C), theta.dtype)],
        interpret=interpret_mode(interpret),
    )(theta, start, ef, noise, scale)


# --------------------------------------------------------------- sign sgd
def _sign_kernel(x_ref, f_ref, out_ref):
    out_ref[...] = (f_ref[0, 0]
                    * jnp.sign(x_ref[...].astype(jnp.float32))
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_roundtrip_flat(x, scale, *, interpret=None):
    """out = scale * sign(x); scale is a traced scalar."""
    R, C = x.shape
    grid, tile, _, scalar = _grid_specs(R, C, "sign_roundtrip",
                                        dtype=x.dtype)
    flags = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _sign_kernel,
        grid=grid,
        in_specs=[tile, scalar],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, flags)


# ------------------------------------------------------ top-k sparsify
def _thresh_kernel(x_ref, f_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.where(jnp.abs(x) >= f_ref[0, 0], x,
                             0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_threshold_flat(x, thr, *, interpret=None):
    """Magnitude sparsifier: keep x where |x| >= thr (the k-th largest
    magnitude, computed outside), zero elsewhere."""
    R, C = x.shape
    grid, tile, _, scalar = _grid_specs(R, C, "topk_threshold",
                                        dtype=x.dtype)
    flags = jnp.asarray(thr, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _thresh_kernel,
        grid=grid,
        in_specs=[tile, scalar],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, flags)


# -------------------------------------------- client-batched launches
#
# One pallas_call over the packed (N, R, C) client stack.  The 2D
# kernel bodies above are elementwise with numpy broadcasting, so
# feeding them (bn, br, bc) blocks computes the identical value per
# coordinate — batched == looped per-client bitwise by construction.


@functools.partial(jax.jit, static_argnames=("qmax", "interpret",
                                             "blocks"))
def quant_roundtrip_batched(x, noise, scale, *, qmax: int,
                            interpret=None, blocks=None):
    """`quant_roundtrip_flat` over an (N, R, C) client stack in one
    launch.  scale: (N, R, 1) per-client per-row scales; blocks: an
    optional static (bn, br, bc) override of the tuned geometry."""
    N, R, C = x.shape
    grid, tile3, rowcol3, _, _ = _grid_specs3(
        N, R, C, "quant_roundtrip", blocks, dtype=x.dtype)
    with jax.named_scope("pallas:quant_roundtrip_batched"):
        return pl.pallas_call(
            functools.partial(_quant_kernel, qmax=qmax),
            grid=grid,
            in_specs=[tile3, tile3, rowcol3],
            out_specs=tile3,
            out_shape=jax.ShapeDtypeStruct((N, R, C), x.dtype),
            interpret=interpret_mode(interpret),
        )(x, noise, scale)


@functools.partial(jax.jit, static_argnames=("qmax", "interpret",
                                             "blocks"))
def broadcast_roundtrip_batched(theta, ref, ef, noise, scale, *,
                                qmax: int, interpret=None,
                                blocks=None):
    """`broadcast_roundtrip_flat` over (N, R, C) per-client replica /
    EF stacks in one launch.  theta may stay (R, C) — the one server
    model is shared across the client grid axis (broadcast in-VMEM)
    — or be a (N, R, C) stack; scale: (N, R, 1)."""
    N, R, C = ref.shape
    grid, tile3, rowcol3, _, shared2 = _grid_specs3(
        N, R, C, "broadcast_roundtrip", blocks, dtype=theta.dtype)
    t_spec = shared2 if theta.ndim == 2 else tile3
    with jax.named_scope("pallas:broadcast_roundtrip_batched"):
        return pl.pallas_call(
            functools.partial(_broadcast_kernel, qmax=qmax),
            grid=grid,
            in_specs=[t_spec, tile3, tile3, tile3, rowcol3],
            out_specs=[tile3, tile3],
            out_shape=[jax.ShapeDtypeStruct((N, R, C), theta.dtype),
                       jax.ShapeDtypeStruct((N, R, C), theta.dtype)],
            interpret=interpret_mode(interpret),
        )(theta, ref, ef, noise, scale)


@functools.partial(jax.jit, static_argnames=("qmax", "interpret",
                                             "blocks"))
def uplink_roundtrip_batched(theta, start, ef, noise, scale, *,
                             qmax: int, interpret=None,
                             blocks=None):
    """`uplink_roundtrip_flat` over (N, R, C) locally-trained client
    stacks in one launch.  start may stay (R, C) — every client
    trained from the same broadcast model (downlink replicas off) —
    or be a (N, R, C) per-client replica stack; scale: (N, R, 1)."""
    N, R, C = theta.shape
    grid, tile3, rowcol3, _, shared2 = _grid_specs3(
        N, R, C, "uplink_roundtrip", blocks, dtype=theta.dtype)
    s_spec = shared2 if start.ndim == 2 else tile3
    with jax.named_scope("pallas:uplink_roundtrip_batched"):
        return pl.pallas_call(
            functools.partial(_uplink_kernel, qmax=qmax),
            grid=grid,
            in_specs=[tile3, s_spec, tile3, tile3, rowcol3],
            out_specs=[tile3, tile3],
            out_shape=[jax.ShapeDtypeStruct((N, R, C), theta.dtype),
                       jax.ShapeDtypeStruct((N, R, C), theta.dtype)],
            interpret=interpret_mode(interpret),
        )(theta, start, ef, noise, scale)


def _sign_kernel_batched(x_ref, f_ref, out_ref):
    # per-client scale block (bn, 1, 1) broadcasts over (bn, br, bc)
    out_ref[...] = (f_ref[...]
                    * jnp.sign(x_ref[...].astype(jnp.float32))
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "blocks"))
def sign_roundtrip_batched(x, scale, *, interpret=None,
                           blocks=None):
    """`sign_roundtrip_flat` over an (N, R, C) stack in one launch;
    scale: (N,) per-client scales."""
    N, R, C = x.shape
    grid, tile3, _, client3, _ = _grid_specs3(
        N, R, C, "sign_roundtrip", blocks, dtype=x.dtype)
    flags = jnp.asarray(scale, jnp.float32).reshape(N, 1, 1)
    return pl.pallas_call(
        _sign_kernel_batched,
        grid=grid,
        in_specs=[tile3, client3],
        out_specs=tile3,
        out_shape=jax.ShapeDtypeStruct((N, R, C), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, flags)


def _thresh_kernel_batched(x_ref, f_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.where(jnp.abs(x) >= f_ref[...], x,
                             0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "blocks"))
def topk_threshold_batched(x, thr, *, interpret=None,
                           blocks=None):
    """`topk_threshold_flat` over an (N, R, C) stack in one launch;
    thr: (N,) per-client magnitude thresholds."""
    N, R, C = x.shape
    grid, tile3, _, client3, _ = _grid_specs3(
        N, R, C, "topk_threshold", blocks, dtype=x.dtype)
    flags = jnp.asarray(thr, jnp.float32).reshape(N, 1, 1)
    return pl.pallas_call(
        _thresh_kernel_batched,
        grid=grid,
        in_specs=[tile3, client3],
        out_specs=tile3,
        out_shape=jax.ShapeDtypeStruct((N, R, C), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, flags)
