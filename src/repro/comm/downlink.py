"""Compressed server->client broadcast (the ``downlink`` stream).

PR 1 broadcast the raw fp32 global model.  This module delta-codes the
broadcast instead: the server tracks, per client, the model that client
last received (``model`` replicas, wire layout) and transmits the
compressed delta ``theta_server - theta_i^rx``, with **server-side
per-client error feedback** for biased compressors.  Unbiased
quantizers need no EF here — any reconstruction error lands in the
client's model replica and is cancelled by the next round's delta
(closed-loop delta coding) — so ``downlink_error_feedback="auto"``
mirrors the uplink policy and materialises residuals only for
``topk``/``signsgd``.

Everything operates on the shared packed (rows, cols) layout of
`repro.comm.flat`; `FedEngine._round_comm` calls `broadcast` once per
participant (under vmap or scan), and non-participants keep their
replicas frozen until they are next sampled.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm.compressors import (Compressor, StochasticQuant,
                                    wants_error_feedback)
from repro.comm.flat import FlatSpec
from repro.configs.base import CommConfig


#: engine state keys owned by this module
MODEL_KEY = "comm_dn_model"
EF_KEY = "comm_dn_ef"


def wants_downlink_ef(comm: CommConfig) -> bool:
    """Server-side per-client EF residuals, under the same "auto"
    policy as the uplink (biased compressors only)."""
    return comm.downlink_enabled and wants_error_feedback(
        comm.stream("downlink"))


def init_state(comm: CommConfig, spec: FlatSpec, packed_params,
               num_clients: int, dtype=jnp.float32) -> dict:
    """Server-side downlink state: every client starts exactly in sync
    (the initial model is assumed distributed out-of-band), with zero
    EF residual.  ``dtype`` is the resident storage dtype of the
    replicas/residuals (`CommConfig.state_dtype`); the engine upcasts
    gathered rows to fp32 before `broadcast` sees them."""
    if not comm.downlink_enabled:
        return {}
    state = {MODEL_KEY: jnp.broadcast_to(
        packed_params[None].astype(dtype),
        (num_clients,) + packed_params.shape).copy()}
    if wants_downlink_ef(comm):
        state[EF_KEY] = jnp.zeros(
            (num_clients, spec.rows, spec.cols), dtype)
    return state


def broadcast(comp: Compressor, key, packed_theta: jnp.ndarray,
              model_row: jnp.ndarray,
              ef_row: Optional[jnp.ndarray]
              ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """One client's broadcast step.

    Encodes ``theta_server - theta_i^rx`` (+ EF residual), applies the
    reconstruction to the client's replica, and returns
    ``(new_model_row, new_ef_row)``.  The compressed payload itself is
    what crosses the wire — `repro.comm.accounting.stream_bytes(...,
    "downlink", ...)` prices it.
    """
    cfg = comp.cfg
    if cfg.use_pallas and isinstance(comp, StochasticQuant):
        # fused Pallas path: delta-code + quant round-trip + apply +
        # residual in one HBM pass (scales need one reduction first)
        from repro.kernels.quantize import broadcast_roundtrip_flat
        ef = jnp.zeros_like(model_row) if ef_row is None else ef_row
        delta = packed_theta - model_row + ef
        u = jax.random.uniform(key, delta.shape)
        new_model, resid = broadcast_roundtrip_flat(
            packed_theta, model_row, ef, u, comp._scales(delta),
            qmax=comp.qmax)
        return new_model, (None if ef_row is None else resid)
    delta = packed_theta - model_row
    if ef_row is not None:
        delta = delta + ef_row
    xhat, _ = comp.roundtrip(key, delta)
    new_model = model_row + xhat
    new_ef = None if ef_row is None else delta - xhat
    return new_model, new_ef


def broadcast_batched(comp: Compressor, keys, packed_theta: jnp.ndarray,
                      model_rows: jnp.ndarray,
                      ef_rows: Optional[jnp.ndarray]
                      ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """`broadcast` for the whole cohort in one pass.

    keys: (N,) per-client rng keys; model_rows / ef_rows: the gathered
    (N, rows, cols) replica / residual stacks (resident dtype — the
    kernels upcast loads in-VMEM); packed_theta stays the one (rows,
    cols) server model, shared across the client grid axis.  The
    Pallas path is ONE client-batched launch; otherwise a vmap of the
    per-client step (graph-identical to looping)."""
    cfg = comp.cfg
    if cfg.use_pallas and isinstance(comp, StochasticQuant):
        from repro.kernels.quantize import broadcast_roundtrip_batched
        ef = (jnp.zeros_like(model_rows) if ef_rows is None else ef_rows)
        delta = packed_theta - model_rows + ef
        u = jax.vmap(
            lambda k: jax.random.uniform(k, delta.shape[1:]))(keys)
        new_models, resid = broadcast_roundtrip_batched(
            packed_theta, model_rows, ef, u,
            jax.vmap(comp._scales)(delta), qmax=comp.qmax)
        return new_models, (None if ef_rows is None else resid)
    return jax.vmap(
        lambda k, m, e: broadcast(comp, k, packed_theta, m, e)
    )(keys, model_rows, ef_rows)
