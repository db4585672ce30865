"""Pluggable robust server-side aggregation (repro.robust).

Every federated combine in this repo is a weighted reduction of a
packed ``(K, rows, cols)`` stack of client contributions — the engine
rounds reduce the cohort axis, the virtual-time scheduler reduces its
arrival buffer with staleness weights.  This module swaps that
reduction for a byzantine-robust one without touching the layout:

* ``trimmed_mean`` — per coordinate, drop the ``trim_count`` largest
  and smallest surviving values, then take the weighted mean of the
  survivors (normalizing by the *surviving* weight, which varies per
  coordinate).
* ``coordinate_median`` — the maximal trim ``(K-1)//2`` per side: one
  survivor for odd K (the median), the two middle values for even K
  (their weighted mean).  A special case of the same kernel.
* ``norm_clip`` — rescale each arrival to L2 norm at most
  ``clip_norm`` (``x_k * min(1, clip/||x_k||)``), then the usual
  weighted mean.  Values shrink, weights do not.

Degenerate parameterizations (`resolve` returns ``"mean"``) mean the
caller keeps its existing weighted-mean code path — the *same traced
graph* as today, hence bitwise-identical outputs (the contract of
docs/robustness.md, pinned by tests/test_robust.py).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import AGGREGATORS


def trim_count(robust, K: int) -> int:
    """Static per-side trim count for a K-arrival stack.

    ``trimmed_mean`` trims ``floor(trim_fraction * K)`` per side,
    capped so at least one coordinate survives; ``coordinate_median``
    is the maximal trim.  0 for everything else.
    """
    if robust.aggregator == "trimmed_mean":
        return min(int(robust.trim_fraction * K), max(0, (K - 1) // 2))
    if robust.aggregator == "coordinate_median":
        return (K - 1) // 2
    return 0


def resolve(robust, K: int) -> str:
    """Effective aggregator for a K-arrival stack.

    Degenerate parameterizations resolve to ``"mean"`` — the caller
    then keeps today's weighted-mean path untouched (bitwise):
    ``trimmed_mean`` whose trim count rounds to 0, ``coordinate_median``
    of a single arrival, ``norm_clip`` with the clip disabled.
    """
    agg = robust.aggregator
    if agg not in AGGREGATORS:
        raise ValueError(
            f"unknown aggregator {agg!r} (want one of {AGGREGATORS})")
    if agg == "trimmed_mean" and trim_count(robust, K) == 0:
        return "mean"
    if agg == "coordinate_median" and K <= 1:
        return "mean"
    if agg == "norm_clip" and robust.clip_norm <= 0.0:
        return "mean"
    return agg


def clip_scales(wires, clip_norm) -> jnp.ndarray:
    """(K,) fp32 rescale factors ``min(1, clip_norm / ||x_k||_2)``.

    Idempotent by construction: an arrival already inside the norm
    ball (``||x_k|| <= clip_norm``) gets the factor exactly 1.0 — the
    ``where`` form, not a ``min`` of rounded quotients — so clipping
    an in-ball stack is a bitwise no-op (pinned by
    tests/test_property.py).
    """
    x = wires.astype(jnp.float32)
    nrm = jnp.sqrt(jnp.sum(x * x, axis=(1, 2)))
    return jnp.where(nrm <= clip_norm, jnp.float32(1.0),
                     clip_norm / jnp.maximum(nrm, jnp.float32(1e-30)))


def aggregate_stack(robust, wires, weights, *, normalize: bool = True,
                    use_pallas: bool = False, interpret=None):
    """Robust combine of a (K, rows, cols) stack -> (rows, cols) fp32.

    ``weights`` are the caller's per-arrival weights (ones for an
    engine cohort, staleness weights in the scheduler).  With
    ``normalize`` the result is the weighted mean of the per-coordinate
    survivors; without it (the scheduler's async apply) the surviving
    ``sum_k w_k x_k`` is returned raw — trimmed-away arrivals simply
    never contribute.  ``use_pallas`` routes through the fused
    sort-free kernel (`repro.kernels.robust_agg`), interpreted or not
    as ``interpret`` says (None follows the platform); the jnp path is
    the conformance oracle `repro.kernels.ref.robust_agg_ref` itself.
    """
    from repro.kernels import ref as kref
    K = wires.shape[0]
    eff = resolve(robust, K)
    w = jnp.asarray(weights, jnp.float32)
    if eff == "mean":
        # degenerate call — mirror the callers' weighted-mean semantics
        num = jnp.sum(wires.astype(jnp.float32) * w[:, None, None],
                      axis=0)
        return num / jnp.sum(w) if normalize else num
    if eff == "norm_clip":
        s = clip_scales(wires, robust.clip_norm)
        t = 0
    else:
        s = jnp.ones((K,), jnp.float32)
        t = trim_count(robust, K)
    if use_pallas:
        from repro.kernels.robust_agg import robust_agg_flat
        return robust_agg_flat(wires, w, s, trim=t, normalize=normalize,
                               interpret=interpret)
    return kref.robust_agg_ref(wires, w, s, trim=t, normalize=normalize)
