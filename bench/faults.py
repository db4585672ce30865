"""Faults planted under the timed path, to show that the output check
catches them (``bench/tests/test_bench_check.py``, ``bench/calibrate.py``).
Each is a ``plant(system, wire) -> round_fn`` for `harness.Program`; the
benchmark's own runs plant nothing."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def unchanged(sysm, wire=None):
    """A round that returns the state it was given."""
    fn = sysm.engine.round_fn(donate=False)

    def round_fn(state, batches, key):
        _, metrics = fn(state, batches, key)
        return state, metrics
    return round_fn


class _HalfBatch:
    """The task with half of every batch left out: the loss, the
    gradient and the curvature are means over the first half of the
    batch's rows, or of each sequence where a batch holds one row."""

    def __init__(self, task):
        self._task = task

    @staticmethod
    def _half(batch):
        def half(a):
            if a.shape[0] > 1:
                return a[: a.shape[0] // 2]
            return a[..., : a.shape[-1] // 2]
        return jax.tree.map(half, batch)

    def init(self, key):
        return self._task.init(key)

    def loss(self, params, batch, rng=None):
        return self._task.loss(params, self._half(batch), rng)

    def sampled_loss(self, params, batch, rng):
        return self._task.sampled_loss(params, self._half(batch), rng)

    def gnb_batch_size(self, batch):
        return self._task.gnb_batch_size(self._half(batch))


def half_batch(sysm, wire=None):
    """Half of the round's batch left out, the mean taken over the
    rest: with two or more clients, half of the cohort (the program's
    own partial participation, each round's mean over the clients that
    trained); with one client, half of every batch."""
    engine = sysm.engine
    fed = engine.fed
    if fed.num_clients >= 2:
        engine.fed = dataclasses.replace(fed, comm=dataclasses.replace(
            fed.comm, participation=0.5))
    else:
        engine.task = _HalfBatch(engine.task)
    return engine.round_fn(donate=True)


def altered(sysm, wire, leaf=0):
    """A round whose answer is altered where it is produced: the update
    of the first leaf in wire order (the second FFN matrix) is applied
    twice."""
    size = wire.sizes[leaf]

    @jax.jit
    def round_fn(state, batches, key):
        new, metrics = sysm.engine.round(state, batches, key)
        old = state["params"]
        p = new["params"]
        rows = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        first = rows * p.shape[1] + cols < size
        moved = p.astype(jnp.float32) - old.astype(jnp.float32)
        twice = jnp.where(first, p.astype(jnp.float32) + moved,
                          p.astype(jnp.float32)).astype(p.dtype)
        return {**new, "params": twice}, metrics
    return round_fn


PLANTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered}
