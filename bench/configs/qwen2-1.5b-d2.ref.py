"""Plain reference of Qwen2 (arXiv:2407.10671) at the sizes of
qwen2-1.5b-d2.json: its initial weights, forward pass and loss.

Decoder layer: x + Attn(RMSNorm(x)), then x + SwiGLU(RMSNorm(x)).
Attention has q/k/v biases, grouped KV heads (12 query heads share 2 KV
heads), rotary embeddings (theta 1e6, halves rotated) and a causal mask.
The LM head is the transposed token embedding (tied). The loss is the
mean next-token cross-entropy over all positions but the last.
RMSNorm scales are stored as offsets from 1, as the trainer under test
stores them; they start at zero.

Weights are drawn from the seed as the trainer draws them (the layout
and key derivation of its initializer: N(0, 1/fan_in) in f32, rounded
to bf16), so the reference needs none of the program's arrays.
Everything is computed in f32 at HIGHEST; `quant="fp8"`, the control
one precision below bf16, computes every projection and the head in
float8 as fp8 training does: operands rounded to e4m3 and, in the
backward pass, the incoming gradient to e5m2, each under a per-tensor
scale, products summed in f32. Imports
nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _normal(key, shape, fan_in, dtype):
    scale = 1.0 / jnp.sqrt(jnp.float32(fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(key, cfg):
    """The trainer's initial weights for one client, in the stated dtype."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    n_layers = cfg["num_hidden_layers"]
    keys = jax.random.split(key, 8)
    dt = jnp.dtype(cfg["torch_dtype"])
    zeros = lambda *s: jnp.zeros(s, dt)  # noqa: E731
    layers = []
    for gkey in jax.random.split(keys[0], n_layers):
        k_attn, k_mlp = jax.random.split(gkey, 2)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        kg, ku, kd = jax.random.split(k_mlp, 3)
        layers.append({
            "attn_norm": zeros(d),
            "wq": _normal(kq, (d, hq * hd), d, dt), "bq": zeros(hq * hd),
            "wk": _normal(kk, (d, hkv * hd), d, dt), "bk": zeros(hkv * hd),
            "wv": _normal(kv, (d, hkv * hd), d, dt), "bv": zeros(hkv * hd),
            "wo": _normal(ko, (hq * hd, d), hq * hd, dt),
            "mlp_norm": zeros(d),
            "w_gate": _normal(kg, (d, ff), d, dt),
            "w_up": _normal(ku, (d, ff), d, dt),
            "w_down": _normal(kd, (ff, d), ff, dt),
        })
    return {"embed": _normal(keys[1], (v, d), d, dt), "layers": layers,
            "final_norm": zeros(d)}


E4M3 = (jnp.float8_e4m3fn, 448.0)  # operands
E5M2 = (jnp.float8_e5m2, 57344.0)  # gradients
HI = jax.lax.Precision.HIGHEST


def _round8(x, dtype, top):
    """`x` rounded to the float8 `dtype` under a per-tensor scale (the
    largest magnitude maps to `top`), back in f32."""
    amax = jnp.maximum(jnp.abs(x).max(), 1e-30)
    s = top / amax
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _mm_fp8(x, w):
    return jnp.matmul(_round8(x, *E4M3), _round8(w, *E4M3), precision=HI)


def _mm_fp8_fwd(x, w):
    x8, w8 = _round8(x, *E4M3), _round8(w, *E4M3)
    return jnp.matmul(x8, w8, precision=HI), (x8, w8)


def _mm_fp8_bwd(res, g):
    x8, w8 = res
    g8 = _round8(g, *E5M2)
    return (jnp.einsum("...n,kn->...k", g8, w8, precision=HI),
            jnp.einsum("...k,...n->kn", x8, g8, precision=HI))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(x, w, quant):
    if quant == "fp8":
        return _mm_fp8(x, w)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x (B, S, H, hd): rotate the two halves by position * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, cfg, quant):
    b, s, d = x.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    q = (_mm(x, p["wq"], quant) + p["bq"]).reshape(b, s, hq, hd)
    k = (_mm(x, p["wk"], quant) + p["bk"]).reshape(b, s, hkv, hd)
    v = (_mm(x, p["wv"], quant) + p["bv"]).reshape(b, s, hkv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    rep = hq // hkv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    hi = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("bshd,bthd->bhst", q, k, precision=hi) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v, precision=hi)
    return _mm(out.reshape(b, s, hq * hd), p["wo"], quant)


def loss(params, tokens, cfg, quant=None):
    """Mean next-token cross-entropy of one client's batch (B, S); the
    label of position t is token t+1, the last position is not scored."""
    eps = cfg["rms_norm_eps"]
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    h = p["embed"][tokens]
    for lp in p["layers"]:
        h = h + _attention(lp, _rms(h, lp["attn_norm"], eps), cfg, quant)
        x = _rms(h, lp["mlp_norm"], eps)
        gate = _mm(x, lp["w_gate"], quant)
        h = h + _mm(jax.nn.silu(gate) * _mm(x, lp["w_up"], quant),
                    lp["w_down"], quant)
    logits = _mm(_rms(h, p["final_norm"], eps), p["embed"].T, quant)
    labels = tokens[:, 1:]
    logz = jax.scipy.special.logsumexp(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logits[:, :-1], labels[..., None], -1)[..., 0]
    return (logz - gold).mean()
