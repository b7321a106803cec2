"""Unified decoder covering all assigned families.

A model is a repeating *pattern* of sub-blocks scanned over ``n_groups``
(scan-over-layers keeps HLO size and compile time flat in depth):

  dense/audio : ['attn','mlp']                      x L
  moe         : ['attn','moe']                      x L
  ssm         : ['ssm']                             x L
  hybrid      : ['ssm']*k + ['shared']              x L/k   (zamba2)
  vlm         : (['attn','mlp']*(k-1)) + ['cross','mlp']  x L/k

'shared' is a weight-shared transformer block (single param copy applied
every group, Zamba2-style). 'cross' attends to stub patch embeddings.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models.attention import KVCache
from repro.models.layers import dense_init, init_mlp, mlp, rms_norm
from repro.models.moe import init_moe, moe_block
from repro.models.ssm import SSMState, init_ssm, ssm_block, ssm_decode_step
from repro.sharding.axes import constrain


# ---------------------------------------------------------------------------
# Pattern
# ---------------------------------------------------------------------------


def block_pattern(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int]:
    if cfg.family in ("dense", "audio"):
        return ("attn", "mlp"), cfg.num_layers
    if cfg.family == "moe":
        return ("attn", "moe"), cfg.num_layers
    if cfg.family == "ssm":
        return ("ssm",), cfg.num_layers
    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        assert cfg.num_layers % k == 0, (cfg.num_layers, k)
        return tuple(["ssm"] * k + ["shared"]), cfg.num_layers // k
    if cfg.family == "vlm":
        k = cfg.cross_attn_every
        assert cfg.num_layers % k == 0, (cfg.num_layers, k)
        pat = tuple(["attn", "mlp"] * (k - 1) + ["cross", "mlp"])
        return pat, cfg.num_layers // k
    raise ValueError(cfg.family)


# sequence length from which self-attention takes the blocked
# online-softmax path (`attention.flash_self_attention`)
BLOCKED_ATTN_THRESHOLD = 8192


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    pattern, n_groups = block_pattern(cfg)
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    keys = jax.random.split(key, 8)

    def init_group(gkey):
        gk = jax.random.split(gkey, len(pattern))
        gp = {}
        for i, kind in enumerate(pattern):
            name = f"{i}:{kind}"
            if kind == "attn":
                gp[name] = {
                    "norm": jnp.zeros((d,), dtype),
                    "attn": attn_lib.init_attention(gk[i], cfg),
                }
            elif kind == "cross":
                gp[name] = {
                    "norm": jnp.zeros((d,), dtype),
                    "attn": attn_lib.init_attention(gk[i], cfg, cross=True),
                    "gate": jnp.zeros((), dtype),  # llama3.2-vision tanh gate
                }
            elif kind == "mlp":
                gp[name] = {
                    "norm": jnp.zeros((d,), dtype),
                    "mlp": init_mlp(gk[i], d, cfg.d_ff, dtype),
                }
            elif kind == "moe":
                gp[name] = {"norm": jnp.zeros((d,), dtype), "moe": init_moe(gk[i], cfg)}
            elif kind == "ssm":
                gp[name] = {"norm": jnp.zeros((d,), dtype), "ssm": init_ssm(gk[i], cfg)}
            elif kind == "shared":
                gp[name] = {}  # weights live in params['shared']
        return gp

    gkeys = jax.random.split(keys[0], n_groups)
    groups = jax.vmap(init_group)(gkeys)

    params = {
        "embed": dense_init(keys[1], (cfg.vocab_size, d), d, dtype),
        "groups": groups,
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[2], (d, cfg.vocab_size), d, dtype)
    if cfg.family == "hybrid":
        ka, km = jax.random.split(keys[3])
        params["shared"] = {
            "norm_attn": jnp.zeros((d,), dtype),
            "attn": attn_lib.init_attention(ka, cfg),
            "norm_mlp": jnp.zeros((d,), dtype),
            "mlp": init_mlp(km, d, cfg.d_ff, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg, batch) -> jax.Array:
    if cfg.embeds_in:
        h = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        h = params["embed"][batch["tokens"]]
    return h


def _logits(params, cfg, h):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return h @ w


def _apply_block(kind, bp, h, cfg, *, mode, shared, cross_embeds, sliding_window, use_blocked):
    if kind == "attn":
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        if use_blocked:
            y = attn_lib.flash_self_attention(bp["attn"], x, cfg, sliding_window=sliding_window)
        else:
            y = attn_lib.full_attention(bp["attn"], x, cfg, sliding_window=sliding_window)
        return h + y
    if kind == "mlp":
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        return h + mlp(bp["mlp"], x, lambda t: constrain(t, "batch", "seq", "ff"))
    if kind == "moe":
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        y, aux = moe_block(bp["moe"], x, cfg)
        return h + y, aux
    if kind == "ssm":
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        return h + ssm_block(bp["ssm"], x, cfg)
    if kind == "cross":
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        y = attn_lib.full_attention(bp["attn"], x, cfg, kv_x=cross_embeds, cross=True)
        return h + jnp.tanh(bp["gate"].astype(jnp.float32)).astype(y.dtype) * y
    if kind == "shared":
        x = rms_norm(h, shared["norm_attn"], cfg.norm_eps)
        if use_blocked:
            y = attn_lib.flash_self_attention(shared["attn"], x, cfg, sliding_window=sliding_window)
        else:
            y = attn_lib.full_attention(shared["attn"], x, cfg, sliding_window=sliding_window)
        h = h + y
        x = rms_norm(h, shared["norm_mlp"], cfg.norm_eps)
        return h + mlp(shared["mlp"], x, lambda t: constrain(t, "batch", "seq", "ff"))
    raise ValueError(kind)


def apply_model(params, cfg: ModelConfig, batch):
    """Full-sequence forward. Returns (logits (B,S,V), aux scalar)."""
    pattern, _ = block_pattern(cfg)
    h = _embed_inputs(params, cfg, batch)
    B, S, _ = h.shape
    h = constrain(h, "batch", "seq", None)
    cross_embeds = batch.get("cross_embeds") if cfg.family == "vlm" else None
    if cross_embeds is not None:
        cross_embeds = cross_embeds.astype(h.dtype)
    use_blocked = S >= BLOCKED_ATTN_THRESHOLD and cfg.family != "ssm"
    sw = cfg.sliding_window
    shared = params.get("shared")

    def group_fn(h, gp):
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(pattern):
            out = _apply_block(
                kind, gp[f"{i}:{kind}"], h, cfg, mode="full", shared=shared,
                cross_embeds=cross_embeds, sliding_window=sw, use_blocked=use_blocked,
            )
            if kind == "moe":
                h, a = out
                aux = aux + a
            else:
                h = out
            h = constrain(h, "batch", "seq", None)
        return h, aux

    if cfg.remat:
        group_fn = jax.checkpoint(group_fn)
    h, auxs = jax.lax.scan(group_fn, h, params["groups"])
    aux = auxs.sum()
    return _logits(params, cfg, h), aux


def _labels_and_mask(batch):
    if "labels" in batch:
        labels = batch["labels"]
    else:
        labels = jnp.concatenate(
            [batch["tokens"][:, 1:], batch["tokens"][:, :1]], axis=1
        )
    mask = jnp.ones(labels.shape, jnp.float32)
    mask = mask.at[:, -1].set(0.0)
    return labels, mask


def lm_loss(params, cfg, batch):
    """Next-token CE loss (labels = inputs shifted, or batch['labels'])."""
    from repro.models.layers import cross_entropy

    labels, mask = _labels_and_mask(batch)
    logits, aux = apply_model(params, cfg, batch)
    return cross_entropy(logits, labels, mask) + aux


# ---------------------------------------------------------------------------
# Decode (one token, KV/SSM caches)
# ---------------------------------------------------------------------------


class DecodeState(NamedTuple):
    caches: Any  # per-group stacked cache pytree
    pos: jax.Array  # scalar int32 current position


def init_decode_state(cfg: ModelConfig, batch: int, seq_len: int) -> DecodeState:
    """Cache shapes for serving `seq_len` context. Ring buffer if sliding."""
    pattern, n_groups = block_pattern(cfg)
    dtype = jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    ring = cfg.sliding_window > 0 and seq_len > cfg.sliding_window
    cache_len = cfg.sliding_window if ring else seq_len

    def one_group():
        c = {}
        for i, kind in enumerate(pattern):
            if kind in ("attn", "shared"):
                c[f"{i}:{kind}"] = KVCache.init(batch, cache_len, cfg.num_kv_heads, hd, dtype)
            elif kind == "ssm":
                c[f"{i}:{kind}"] = SSMState.init(batch, cfg, dtype)
        return c

    caches = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), one_group()
    )
    return DecodeState(caches=caches, pos=jnp.zeros((), jnp.int32))


def init_cross_kv(params, cfg, patch_embeds):
    """Precompute cross-attn K/V from patch embeddings, stacked per group."""
    pattern, n_groups = block_pattern(cfg)
    hd = cfg.resolved_head_dim
    idx = [i for i, k in enumerate(pattern) if k == "cross"]
    if not idx:
        return None
    (i,) = idx

    def per_group(gp):
        ap = gp[f"{i}:cross"]["attn"]
        x = patch_embeds.astype(ap["wk"].dtype)
        k = (x @ ap["wk"]).reshape(*x.shape[:-1], cfg.num_kv_heads, hd)
        v = (x @ ap["wv"]).reshape(*x.shape[:-1], cfg.num_kv_heads, hd)
        return {"k": k, "v": v}

    return jax.vmap(per_group)(params["groups"])


def decode_step(params, cfg: ModelConfig, token_or_embed, state: DecodeState,
                cross_kv=None):
    """One decode step. token (B,) int32 or embed (B,1,d). Returns
    (logits (B,V), new DecodeState)."""
    pattern, n_groups = block_pattern(cfg)
    if cfg.embeds_in:
        h = token_or_embed.astype(jnp.dtype(cfg.dtype))
    else:
        h = params["embed"][token_or_embed][:, None, :]
    ring = cfg.sliding_window > 0
    pos = state.pos
    shared = params.get("shared")

    def group_fn(h, xs):
        gp, gc, gcross = xs
        new_gc = dict(gc)
        for i, kind in enumerate(pattern):
            name = f"{i}:{kind}"
            if kind in ("attn", "shared"):
                bp = shared if kind == "shared" else gp[name]
                nrm = bp["norm_attn"] if kind == "shared" else gp[name]["norm"]
                ap = bp["attn"]
                x = rms_norm(h, nrm, cfg.norm_eps)
                cache = KVCache(*gc[name])
                y, new_cache = attn_lib.decode_attention(
                    ap, x, cache, pos, cfg, ring=cfg.sliding_window > 0 and cache.k.shape[1] == cfg.sliding_window
                )
                h = h + y
                new_gc[name] = new_cache
                if kind == "shared":
                    x = rms_norm(h, shared["norm_mlp"], cfg.norm_eps)
                    h = h + mlp(shared["mlp"], x)
            elif kind == "mlp":
                x = rms_norm(h, gp[name]["norm"], cfg.norm_eps)
                h = h + mlp(gp[name]["mlp"], x)
            elif kind == "moe":
                x = rms_norm(h, gp[name]["norm"], cfg.norm_eps)
                y, _ = moe_block(gp[name]["moe"], x, cfg)
                h = h + y
            elif kind == "ssm":
                x = rms_norm(h, gp[name]["norm"], cfg.norm_eps)
                y, new_s = ssm_decode_step(gp[name]["ssm"], x, SSMState(*gc[name]), cfg)
                h = h + y
                new_gc[name] = new_s
            elif kind == "cross":
                x = rms_norm(h, gp[name]["norm"], cfg.norm_eps)
                y = attn_lib.cross_decode_attention(
                    gp[name]["attn"], x, gcross["k"], gcross["v"], cfg
                )
                g = jnp.tanh(gp[name]["gate"].astype(jnp.float32)).astype(y.dtype)
                h = h + g * y
        return h, new_gc

    if cross_kv is None:
        pattern_has_cross = any(k == "cross" for k in pattern)
        assert not pattern_has_cross, "vlm decode needs cross_kv"
        cross_dummy = jax.tree_util.tree_map(lambda x: x, {"k": jnp.zeros((n_groups, 1)), "v": jnp.zeros((n_groups, 1))})
    else:
        cross_dummy = cross_kv
    xs = (params["groups"], state.caches, cross_dummy)
    h, new_caches = jax.lax.scan(group_fn, h, xs)
    logits = _logits(params, cfg, h)[:, 0, :]
    return logits, DecodeState(caches=new_caches, pos=pos + 1)
