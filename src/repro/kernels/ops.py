"""Pytree-level wrapper around the fused Sophia Pallas kernel.

``sophia_fused_step`` packs every leaf of the param pytree into one
flat (R, C) buffer, runs the fused kernel once, and unpacks.  It is
the *pytree-boundary* form kept for `repro.core.sophia.sophia_step`
(the reference twin) and its tests; the round engine itself is
flat-resident (`repro.core.fed`) and calls
`repro.kernels.sophia_update.sophia_update_flat` directly on wire-
layout state — zero pack/unpack per local iteration.

The dead apply-only wrapper (``sophia_apply_fused``) that allocated a
full zeros gradient buffer to run the complete kernel was removed;
use `repro.core.sophia.apply_update` for apply-only semantics.
"""
from __future__ import annotations

from repro.comm.flat import flat_spec, pack, unpack
from repro.kernels.sophia_update import BLOCK_C, sophia_update_flat


def _pack(trees):
    """Pack each tree into the shared wire layout -> (flat_2d list, spec)."""
    spec = flat_spec(trees[0], cols=BLOCK_C)
    return [pack(t, spec) for t in trees], spec


def _unpack(flat2d, spec):
    return unpack(flat2d, spec)


def sophia_fused_step(params, m, h, grads, h_hat, do_h, *, lr, beta1, beta2,
                      rho, eps, weight_decay, interpret=None):
    """Fused m-EMA + h-EMA-select + decay + clip + update over a pytree.

    Returns (new_params, new_m, new_h).
    """
    (t2, m2, h2, g2, hh2), meta = _pack([params, m, h, grads, h_hat])
    t2, m2, h2 = sophia_update_flat(
        t2, m2, h2, g2, hh2, do_h, lr, beta1=beta1, beta2=beta2,
        rho=rho, eps=eps, weight_decay=weight_decay, interpret=interpret)
    return _unpack(t2, meta), _unpack(m2, meta), _unpack(h2, meta)
