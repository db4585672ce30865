"""The token generator of the benchmark's traffic.

A copy of ``repro.data.synthetic.make_token_batch`` as it stood when the
benchmark was defined, kept here so that the yardstick does not move
when the program's own generator does.  Tokens follow a seeded
permutation with 15% uniform noise (a learnable next-token structure);
labels are the tokens shifted by one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def token_batch(key, num_clients: int, batch: int, seq_len: int,
                vocab_size: int):
    """``{"tokens", "labels"}``, each int32 ``(num_clients, batch,
    seq_len)`` with ids in ``[0, vocab_size)``."""
    kperm, kinit, knoise, _ = jax.random.split(key, 4)
    perm = jax.random.permutation(kperm, vocab_size)
    t0 = jax.random.randint(kinit, (num_clients, batch, 1), 0, vocab_size)

    def step(tok, k):
        nxt = perm[tok]
        flip = jax.random.bernoulli(k, 0.15, tok.shape)
        rnd = jax.random.randint(k, tok.shape, 0, vocab_size)
        tok = jnp.where(flip, rnd, nxt)
        return tok, tok

    keys = jax.random.split(knoise, seq_len)
    _, rest = jax.lax.scan(step, t0[..., 0], keys[1:])
    tokens = jnp.concatenate([t0, jnp.moveaxis(rest, 0, -1)], axis=-1)
    labels = jnp.concatenate([tokens[..., 1:], tokens[..., :1]], axis=-1)
    return {"tokens": tokens, "labels": labels}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def batch_pool(key, pool: int, num_clients: int, batch: int, seq_len: int,
               vocab_size: int):
    """``pool`` distinct per-round batches, stacked on a leading axis,
    made on the device in one call: round ``i`` of a run reads entry
    ``i % pool``."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(pool))
    return jax.vmap(lambda k: token_batch(k, num_clients, batch, seq_len,
                                          vocab_size))(keys)
