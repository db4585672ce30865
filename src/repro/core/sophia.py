"""The Sophia update (Liu et al. 2023) as used by Fed-Sophia (Alg. 1).

Two twins with identical per-coordinate semantics:

* the pytree form (`sophia_step` and friends) — the reference the
  paper-facing tests pin, still selectable onto the fused Pallas
  kernel via ``use_pallas``;
* the flat form (`sophia_step_flat`) — the flat-resident round
  engine's local step (`repro.core.fed`): theta/m/h in the leaf layout
  the local loop carries for a whole client-round, each leaf in its
  resident dtype, so the kernel path (one launch per leaf) needs no
  layout conversion per step (docs/architecture.md "Memory layout").
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
        # one fused Pallas pass per leaf: m-EMA, gated h-EMA, decay,
        # clip, update
        params, m, h = sophia_step_flat(
            params, state.m, state.h, grads, h_hat, do_h_update,
            lr=lr, beta1=beta1, beta2=beta2, rho=rho, eps=eps,
            weight_decay=weight_decay, use_pallas=True)
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
                     use_pallas: bool = False, batched: bool = False):
    """`sophia_step` over the flat engine's local-loop state, one array
    leaf at a time: the local loops carry theta/m/h as the model's
    leaves in their resident dtypes, and a packed (rows, cols) buffer
    is a one-leaf tree.  ``batched``: every leaf carries a leading
    client axis.

    Bit-identical per coordinate to the pytree form and to one update
    over the packed buffer (the ops are all elementwise; the packed
    zero pad tail is a fixed point).  With ``use_pallas`` each leaf
    feeds the fused kernel (`sophia_update_leaf`: viewed 2-D, no
    relayout; the client-batched launch when ``batched``).  Follows
    the kernel layer's dtype contract: narrow resident leaves (bf16,
    fp8) are upcast to fp32 for the arithmetic and the results stored
    back in each input's dtype (no-op casts for fp32).  Returns
    ``(theta, m, h)`` as trees shaped like ``theta``.
    """
    if use_pallas:
        from repro.kernels.sophia_update import sophia_update_leaf

        def leaf(t, mm, hh, g, e):
            return sophia_update_leaf(
                t, mm, hh, g, e, do_h_update, lr, batched=batched,
                beta1=beta1, beta2=beta2, rho=rho, eps=eps,
                weight_decay=weight_decay)
    else:
        def leaf(t, mm, hh, g, e):
            out_dt = (t.dtype, mm.dtype, hh.dtype)
            t, mm, hh, g, e = (x.astype(jnp.float32)
                               for x in (t, mm, hh, g, e))
            mm = beta1 * mm + (1.0 - beta1) * g                    # Eq. 9
            hh = jnp.where(do_h_update,
                           beta2 * hh + (1.0 - beta2) * e, hh)     # Eq. 10
            t = t - lr * weight_decay * t                          # line 15
            step = clip(mm / jnp.maximum(hh, eps), rho)            # Eq. 11
            return ((t - lr * step).astype(out_dt[0]),             # line 16
                    mm.astype(out_dt[1]), hh.astype(out_dt[2]))
    treedef = jax.tree.structure(theta)
    outs = [leaf(*xs) for xs in zip(*(jax.tree.leaves(x) for x in
                                      (theta, m, h, grads, h_hat)))]
    return tuple(jax.tree.unflatten(treedef, list(o)) for o in zip(*outs))
