"""Shared layer primitives: init, RMSNorm, RoPE, SwiGLU MLP."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dense_init(key, shape, in_dim=None, dtype=jnp.float32):
    in_dim = in_dim if in_dim is not None else shape[0]
    scale = 1.0 / jnp.sqrt(jnp.maximum(in_dim, 1)).astype(jnp.float32)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x, scale, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def init_mlp(key, d_model: int, d_ff: int, dtype):
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(kg, (d_model, d_ff), d_model, dtype),
        "w_up": dense_init(ku, (d_model, d_ff), d_model, dtype),
        "w_down": dense_init(kd, (d_ff, d_model), d_ff, dtype),
    }


def mlp(params, x, constrain_fn=None):
    """SwiGLU MLP. x: (..., d)."""
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    if constrain_fn is not None:
        h = constrain_fn(h)
    return h @ params["w_down"]


def cross_entropy(logits, labels, mask=None):
    """Mean token-level CE. logits (..., V) f32-safe; labels (...,) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def top1_accuracy(logits, labels):
    """Share of rows whose first maximal logit is at the label: `argmax`
    semantics, written as a max and a min reduction.

    On four TPU v5e chips the variadic (value, index) reduce that
    `argmax` lowers to gave wrong indices inside the client-sharded
    sweep scan (accuracy 0.0495 where the one-device grid and an eval
    outside the scan read 0.0933, for bit-identical params), while the
    plain reductions of `cross_entropy` agreed."""
    m = logits.max(-1, keepdims=True)
    c = logits.shape[-1]
    first = jnp.min(jnp.where(logits == m, jnp.arange(c), c), axis=-1)
    return (first == labels).mean()
