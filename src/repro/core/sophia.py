"""The Sophia update (Liu et al. 2023) as used by Fed-Sophia (Alg. 1).

Two twins with identical per-coordinate semantics:

* the pytree form (`sophia_step` and friends) — the reference the
  paper-facing tests pin, still selectable onto the fused Pallas
  kernel via ``use_pallas``;
* the flat form (`sophia_step_flat`) — one packed (rows, cols) fp32
  buffer per state stream, consumed by the flat-resident round engine
  (`repro.core.fed`), where the kernel path needs **zero** layout
  conversion because the engine already holds theta/m/h in the wire
  layout (docs/architecture.md "Memory layout").
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SophiaState(NamedTuple):
    m: object   # EMA of gradients       (Eq. 9)
    h: object   # EMA of Hessian diag    (Eq. 10)


def init_state(params) -> SophiaState:
    zeros = jax.tree.map(jnp.zeros_like, params)
    return SophiaState(m=zeros, h=jax.tree.map(jnp.zeros_like, params))


def update_m(m, grads, beta1: float):
    """Eq. 9: m <- b1 m + (1-b1) g."""
    return jax.tree.map(lambda mm, g: beta1 * mm + (1.0 - beta1) * g, m, grads)


def update_h(h, h_hat, beta2: float):
    """Eq. 10: h <- b2 h + (1-b2) h_hat."""
    return jax.tree.map(lambda hh, e: beta2 * hh + (1.0 - beta2) * e, h, h_hat)


def clip(z, rho: float):
    """Eq. 11: elementwise clip to [-rho, rho]."""
    return jnp.clip(z, -rho, rho)


def apply_update(params, m, h, *, lr: float, rho: float, eps: float,
                 weight_decay: float):
    """Alg. 1 lines 15-16: decoupled weight decay then clipped
    pre-conditioned step  theta <- theta - lr*clip(m / max(h, eps), rho)."""
    def leaf(theta, mm, hh):
        dtype = theta.dtype
        theta = theta - lr * weight_decay * theta
        step = clip(mm / jnp.maximum(hh, eps), rho)
        return (theta - lr * step).astype(dtype)
    return jax.tree.map(leaf, params, m, h)


def sophia_step(params, grads, state: SophiaState, h_hat, do_h_update,
                *, lr, beta1, beta2, rho, eps, weight_decay,
                use_pallas: bool = False):
    """One full local iteration of Alg. 1 (lines 7-16).

    h_hat: GNB estimate pytree (only consumed when do_h_update).
    do_h_update: traced bool — h-EMA applied under lax.cond-style select.
    """
    if use_pallas:
        # single fused Pallas pass: m-EMA, gated h-EMA, decay, clip, update
        from repro.kernels.ops import sophia_fused_step
        params, m, h = sophia_fused_step(
            params, state.m, state.h, grads, h_hat, do_h_update,
            lr=lr, beta1=beta1, beta2=beta2, rho=rho, eps=eps,
            weight_decay=weight_decay)
        return params, SophiaState(m=m, h=h)
    m = update_m(state.m, grads, beta1)
    h_new = update_h(state.h, h_hat, beta2)
    h = jax.tree.map(
        lambda new, old: jnp.where(do_h_update, new, old), h_new, state.h)
    params = apply_update(params, m, h, lr=lr, rho=rho, eps=eps,
                          weight_decay=weight_decay)
    return params, SophiaState(m=m, h=h)


def sophia_step_flat(theta, m, h, grads, h_hat, do_h_update, *, lr, beta1,
                     beta2, rho, eps, weight_decay,
                     use_pallas: bool = False):
    """`sophia_step` over packed (rows, cols) wire buffers.

    Bit-identical per coordinate to the pytree form for fp32 buffers
    (the ops are all elementwise; the zero pad tail is a fixed point,
    so packed state stays valid wire buffers across iterations).
    With ``use_pallas`` the buffers feed the fused kernel directly —
    no pack/unpack.  Follows the kernel layer's dtype contract: bf16
    resident buffers (`CommConfig.state_dtype`) are upcast to fp32
    for the arithmetic and the results stored back in each input's
    dtype (no-op casts for fp32).  Returns ``(theta, m, h)``.

    Also accepts packed (clients, rows, cols) stacks: the pure path
    is elementwise and shape-agnostic, and the kernel path dispatches
    to the client-batched launch (`sophia_update_batched`) — ONE
    kernel call for the whole cohort, bitwise equal to per-client
    calls.
    """
    if use_pallas:
        from repro.kernels.sophia_update import (sophia_update_batched,
                                                 sophia_update_flat)
        fn = sophia_update_batched if theta.ndim == 3 else sophia_update_flat
        return fn(
            theta, m, h, grads, h_hat, do_h_update, lr, beta1=beta1,
            beta2=beta2, rho=rho, eps=eps, weight_decay=weight_decay)
    out_dt = (theta.dtype, m.dtype, h.dtype)
    theta, m, h, grads, h_hat = (x.astype(jnp.float32)
                                 for x in (theta, m, h, grads, h_hat))
    m = beta1 * m + (1.0 - beta1) * grads                          # Eq. 9
    h = jnp.where(do_h_update,
                  beta2 * h + (1.0 - beta2) * h_hat, h)            # Eq. 10
    theta = theta - lr * weight_decay * theta                      # line 15
    step = clip(m / jnp.maximum(h, eps), rho)                      # Eq. 11
    return ((theta - lr * step).astype(out_dt[0]),                 # line 16
            m.astype(out_dt[1]), h.astype(out_dt[2]))
