"""Offline synthetic datasets.

The container has no MNIST/FMNIST; we generate seeded class-conditional
image data with the same shape/cardinality (28x28x1, 10 classes) plus a
non-IID Dirichlet partitioner (the paper's setting: 32 devices, non-IID).
A synthetic token stream feeds the LM-family architectures.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

IMAGE_SIZE = 28
NUM_CLASSES = 10


def make_image_data(key, n: int, dataset: str = "mnist",
                    noise: float = 0.35) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Class-conditional smooth prototypes + Gaussian noise.

    'fmnist' uses a different seed-space and higher intra-class variation
    (it is the harder dataset, as in the paper).
    """
    salt = 0 if dataset == "mnist" else 1
    key = jax.random.fold_in(key, salt)
    kp, ky, kn, ka = jax.random.split(key, 4)
    # smooth prototypes: random low-res patterns upsampled
    low = jax.random.normal(kp, (NUM_CLASSES, 7, 7, 1))
    protos = jax.image.resize(low, (NUM_CLASSES, IMAGE_SIZE, IMAGE_SIZE, 1),
                              "cubic")
    protos = protos / (jnp.std(protos, axis=(1, 2, 3), keepdims=True) + 1e-6)
    y = jax.random.randint(ky, (n,), 0, NUM_CLASSES)
    amp = 1.0 + (0.35 if dataset == "fmnist" else 0.15) * \
        jax.random.normal(ka, (n, 1, 1, 1))
    x = amp * protos[y] + noise * jax.random.normal(
        kn, (n, IMAGE_SIZE, IMAGE_SIZE, 1))
    return x.astype(jnp.float32), y


def dirichlet_partition(key, labels, num_clients: int,
                        alpha: float = 0.5) -> np.ndarray:
    """Non-IID split: per-client class mixture ~ Dirichlet(alpha).

    Returns an (C, n_per_client) int index matrix (equalized with
    replacement so it stacks/jits cleanly).
    """
    labels = np.asarray(labels)
    n = labels.shape[0]
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    by_class = [np.where(labels == c)[0] for c in range(NUM_CLASSES)]
    n_per = n // num_clients
    out = np.zeros((num_clients, n_per), np.int32)
    for i in range(num_clients):
        mix = rng.dirichlet(alpha * np.ones(NUM_CLASSES))
        counts = rng.multinomial(n_per, mix)
        idx = np.concatenate([
            rng.choice(by_class[c], size=k, replace=len(by_class[c]) < k)
            for c, k in enumerate(counts) if k > 0])
        rng.shuffle(idx)
        out[i] = idx[:n_per]
    return out


def train_test_split(part: np.ndarray, test_frac: float = 0.25):
    """Per-client 75/25 split (paper §V-A)."""
    n_test = int(part.shape[1] * test_frac)
    return part[:, n_test:], part[:, :n_test]


def client_batches(key, x, y, part: np.ndarray, batch_size: int):
    """Sample one round of per-client minibatches -> leaves (C, b, ...)."""
    C, n_per = part.shape
    b = min(batch_size, n_per)
    cols = jax.random.randint(key, (C, b), 0, n_per)
    idx = jnp.take_along_axis(jnp.asarray(part), cols, axis=1)   # (C,b)
    return {"x": x[idx], "y": y[idx]}


# --------------------------------------------------------------------------
# synthetic token streams for the LM-family architectures
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def make_token_batch(key, num_clients: int, batch: int, seq_len: int,
                     vocab_size: int, num_pos_channels: int = 0):
    """Markov-ish token stream: y_t depends on y_{t-1} through a seeded
    permutation plus noise — learnable structure for the LM loss.
    One compiled scan over positions, built on the default device."""
    kperm, kinit, knoise, kmask = jax.random.split(key, 4)
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
    tokens = jnp.concatenate([t0, jnp.moveaxis(rest, 0, -1)],
                             axis=-1)                  # (C,B,S)
    labels = jnp.concatenate([tokens[..., 1:], tokens[..., :1]], axis=-1)
    out = {"tokens": tokens, "labels": labels}
    return out
