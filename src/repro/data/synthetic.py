"""Synthetic data pipeline.

Real EMNIST / Poker-hand files are unavailable offline; we generate
class-conditional Gaussian-mixture tasks with matched dimensionality and
class counts, plus Dirichlet non-iid federated partitions — the paper's
claims being validated are *relative* (method ordering, Psi trends).

Also provides deterministic LM token streams for the production trainer.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.layers import cross_entropy, dense_init, top1_accuracy


def classification_task(key, n_samples: int, input_dim: int, num_classes: int,
                        noise: float = 0.6, anchors=None):
    """Gaussian mixture: one anchor per class + noise. Returns (x, y, anchors)."""
    k1, k2, k3 = jax.random.split(key, 3)
    if anchors is None:
        anchors = jax.random.normal(k1, (num_classes, input_dim))
    y = jax.random.randint(k2, (n_samples,), 0, num_classes)
    x = anchors[y] + noise * jax.random.normal(k3, (n_samples, input_dim))
    return x, y, anchors


def dirichlet_partition(key, y, num_clients: int, num_classes: int,
                        alpha: float = 0.5, per_client: int = 1000):
    """Non-iid split: per-client class distribution ~ Dirichlet(alpha).

    Returns (num_clients, per_client) indices into the dataset (sampling
    with replacement from class pools weighted by the client's mixture)."""
    kd, ks = jax.random.split(key)
    props = jax.random.dirichlet(kd, alpha * jnp.ones((num_classes,)), (num_clients,))
    class_logp = jnp.log(jnp.maximum(props, 1e-9))  # (C, K)
    # per-sample logits per client: logp of its class
    sample_logits = class_logp[:, y]  # (C, n_samples)
    keys = jax.random.split(ks, num_clients)
    idx = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg, shape=(per_client,))
    )(keys, sample_logits)
    return idx


def federated_classification(key, num_clients: int, input_dim: int,
                             num_classes: int, per_client: int = 1000,
                             alpha: float = 0.5, test_size: int = 2000,
                             noise: float = 0.6):
    """Full federated task: per-client train shards + common test set."""
    kt, kp, ke = jax.random.split(key, 3)
    pool_x, pool_y, anchors = classification_task(kt, 20_000, input_dim, num_classes, noise)
    idx = dirichlet_partition(kp, pool_y, num_clients, num_classes, alpha, per_client)
    xs = pool_x[idx]  # (N, per_client, dim)
    ys = pool_y[idx]
    test_x, test_y, _ = classification_task(
        ke, test_size, input_dim, num_classes, noise, anchors=anchors
    )
    return (xs, ys), (test_x, test_y)


def lm_token_batches(key, num_clients: int, per_client: int, seq_len: int,
                     vocab: int):
    """Deterministic synthetic token shards (N, per_client, seq_len)."""
    return jax.random.randint(key, (num_clients, per_client, seq_len), 0, vocab)


# ---------------------------------------------------------------------------
# Paper-scale model (the ~0.57 MB CNN stand-in): 2-hidden-layer MLP
# ---------------------------------------------------------------------------


def make_mlp(key, input_dim: int, hidden: tuple, num_classes: int):
    dims = (input_dim,) + tuple(hidden) + (num_classes,)
    keys = jax.random.split(key, len(dims) - 1)
    params = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        params[f"w{i}"] = dense_init(keys[i], (a, b), a)
        params[f"b{i}"] = jnp.zeros((b,))
    n_layers = len(dims) - 1

    def apply(p, x):
        h = x
        for i in range(n_layers):
            h = h @ p[f"w{i}"] + p[f"b{i}"]
            if i < n_layers - 1:
                h = jax.nn.relu(h)
        return h

    def loss(p, x, y):
        return cross_entropy(apply(p, x), y)

    def accuracy(p, x, y):
        return top1_accuracy(apply(p, x), y)

    return params, apply, loss, accuracy
