"""Plain reference of the emnist-n25 workload: its data and its model.

The MLP and the data generator are the workload that the benchmark
hands the system under test (`simulate` takes a bare loss callable), so
the program and the reference run the same model function; what is
compared is the protocol the program wraps around it. Imports nothing
of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_data(key, cfg):
    """Class-conditional Gaussian data of EMNIST's shape with Dirichlet
    non-iid client shards, in one jitted call on the device.
    Returns ((xs (N, S, D), ys (N, S)), (test_x (T, D), test_y (T,)))."""
    d = cfg["data"]
    n = cfg["num_clients"]

    @jax.jit
    def build(key):
        ka, kp, kd, kc, kt = jax.random.split(key, 5)
        c, dim, noise = d["num_classes"], d["input_dim"], d["noise"]
        anchors = jax.random.normal(ka, (c, dim))

        def draw(k, size):
            ky, kx = jax.random.split(k)
            y = jax.random.randint(ky, (size,), 0, c)
            return anchors[y] + noise * jax.random.normal(kx, (size, dim)), y

        pool_x, pool_y = draw(kp, d["pool_samples"])
        props = jax.random.dirichlet(
            kd, d["dirichlet_alpha"] * jnp.ones((c,)), (n,))
        logits = jnp.log(jnp.maximum(props, 1e-9))[:, pool_y]  # (N, pool)
        idx = jax.vmap(lambda k, lg: jax.random.categorical(
            k, lg, shape=(d["samples_per_client"],)))(
                jax.random.split(kc, n), logits)
        test = draw(kt, d["test_samples"])
        return (pool_x[idx], pool_y[idx]), test

    return build(key)


def init_params(key, cfg):
    """Dense layers with N(0, 1/fan_in) weights and zero biases, f32."""
    dims = cfg["model"]["dims"]
    keys = jax.random.split(key, len(dims) - 1)
    p = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        p[f"w{i}"] = jax.random.normal(keys[i], (a, b), jnp.float32) / jnp.sqrt(
            jnp.float32(a))
        p[f"b{i}"] = jnp.zeros((b,), jnp.float32)
    return p


def apply(p, x, mm=jnp.matmul):
    """Logits of the MLP; `mm` is the matrix product (the reference
    passes one of a stated precision)."""
    n_layers = len(p) // 2
    h = x
    for i in range(n_layers):
        h = mm(h, p[f"w{i}"]) + p[f"b{i}"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


def loss(p, x, y, mm=jnp.matmul):
    """Mean cross-entropy."""
    logits = apply(p, x, mm).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


def accuracy(p, x, y, mm=jnp.matmul):
    """Share of rows whose first maximal logit is the label, written as a
    max and a min reduction (no argmax)."""
    logits = apply(p, x, mm)
    m = logits.max(-1, keepdims=True)
    c = logits.shape[-1]
    first = jnp.min(jnp.where(logits == m, jnp.arange(c), c), axis=-1)
    return (first == y).mean()
