"""Production-plane step functions + abstract input specs.

``make_train_step``: one DRACO superposition window on the mesh — every
client (= model-shard group on the ("pod","data") axes) runs a local
grad step, forms Delta, and the row-stochastic gossip mix is applied as a
collective over the client axis. Event masks / channel masks arrive as
the per-window effective Q (q_eff) input, so the compiled step is purely
data-dependent (no host control flow).

``make_serve_step`` / ``make_prefill_step``: decode one token against a
KV/SSM cache; prefill a full prompt. Serving uses the *unified* model
(single param copy), per DESIGN.md §4.

``input_specs``: ShapeDtypeStruct stand-ins for every model input of an
(arch x shape) pair — weak-type-correct, shardable, no allocation.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import mixing
from repro.launch import mesh as mesh_lib
from repro.models import model as M
from repro.sharding.axes import default_rules, train_rules, use_rules
from repro.sharding.specs import tree_param_specs


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------


def train_batch_specs(cfg: ModelConfig, shape: ShapeConfig, n_clients: int):
    """Per-client-stacked batch: leaves lead with (N, b, ...)."""
    assert shape.global_batch % n_clients == 0, (shape.global_batch, n_clients)
    b = shape.global_batch // n_clients
    S = shape.seq_len
    specs: Dict[str, Any] = {}
    if cfg.embeds_in:
        specs["embeds"] = jax.ShapeDtypeStruct((n_clients, b, S, cfg.d_model), jnp.bfloat16)
        specs["labels"] = jax.ShapeDtypeStruct((n_clients, b, S), jnp.int32)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((n_clients, b, S), jnp.int32)
    if cfg.family == "vlm":
        specs["cross_embeds"] = jax.ShapeDtypeStruct(
            (n_clients, b, cfg.num_patch_tokens, cfg.d_model), jnp.bfloat16
        )
    return specs


def serve_input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Decode-step inputs: current token + cache state (+ cross KV)."""
    B, S = shape.global_batch, shape.seq_len
    serve_cfg = serve_config(cfg, shape)
    state = jax.eval_shape(lambda: M.init_decode_state(serve_cfg, B, S))
    if cfg.embeds_in:
        tok = jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.bfloat16)
    else:
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    cross = None
    if cfg.family == "vlm":
        pe = jax.ShapeDtypeStruct((B, cfg.num_patch_tokens, cfg.d_model), jnp.bfloat16)
        params_s = jax.eval_shape(lambda k: M.init_params(k, serve_cfg), jax.random.PRNGKey(0))
        cross = jax.eval_shape(
            lambda p, e: M.init_cross_kv(p, serve_cfg, e), params_s, pe
        )
    return tok, state, cross


def prefill_batch_specs(cfg: ModelConfig, shape: ShapeConfig):
    B, S = shape.global_batch, shape.seq_len
    specs: Dict[str, Any] = {}
    if cfg.embeds_in:
        specs["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.family == "vlm":
        specs["cross_embeds"] = jax.ShapeDtypeStruct((B, cfg.num_patch_tokens, cfg.d_model), jnp.bfloat16)
    return specs


def param_specs_abstract(cfg: ModelConfig):
    return jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))


def stack_clients_abstract(params_abs, n_clients: int):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n_clients,) + tuple(l.shape), l.dtype), params_abs
    )


def serve_config(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """Serving variant: attention archs get a sliding window at 500k ctx
    (sub-quadratic requirement); ssm/hybrid decode natively."""
    if shape.name == "long_500k" and cfg.family in ("dense", "moe", "vlm", "audio"):
        return cfg.with_(sliding_window=8192)
    if shape.name == "long_500k" and cfg.family == "hybrid":
        # hybrid: SSM layers are O(1); the shared attn block uses a window
        return cfg.with_(sliding_window=8192)
    return cfg


# ---------------------------------------------------------------------------
# Sharding specs
# ---------------------------------------------------------------------------


def make_shardings(mesh, cfg: ModelConfig, shape: ShapeConfig):
    """(param_shardings (client-stacked), batch_shardings, q_sharding)."""
    caxes = mesh_lib.client_axes(mesh)
    cax = caxes if len(caxes) > 1 else caxes[0]
    n_clients = mesh_lib.num_clients(mesh)
    params_abs = stack_clients_abstract(param_specs_abstract(cfg), n_clients)
    pspecs = tree_param_specs(params_abs, prefix=(cax,), mesh=mesh)
    param_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)

    def batch_sh(leaf_spec):
        return NamedSharding(mesh, leaf_spec)

    bspecs = {}
    for name, sds in train_batch_specs(cfg, shape, n_clients).items():
        spec = P(cax, *([None] * (len(sds.shape) - 1)))
        bspecs[name] = batch_sh(spec)
    q_sh = NamedSharding(mesh, P(None, None))
    return param_sh, bspecs, q_sh


def serve_shardings(mesh, cfg: ModelConfig, shape: ShapeConfig,
                    cache_shard: str = "kv_heads"):
    """Shardings for (params single-copy, token, decode state, cross_kv).

    cache_shard: 'kv_heads' shards the KV-head axis over "model"
    (baseline; falls back to replicated when kv_heads % 16 != 0 — the
    GQA pathology measured in §Roofline). 'head_dim' shards the head_dim
    axis instead (always divisible; attention contracts over it with a
    psum — Megatron-style reduction split). 'seq' shards the cache
    length axis over "model"."""
    caxes = mesh_lib.client_axes(mesh)
    cax = caxes if len(caxes) > 1 else caxes[0]
    B = shape.global_batch
    batch_shardable = B % mesh_lib.num_clients(mesh) == 0
    batch_ax = cax if batch_shardable else None
    # long-context batch=1: shard the cache sequence axis over 'data'
    seq_ax = None if batch_shardable else "data"

    scfg = serve_config(cfg, shape)
    params_abs = param_specs_abstract(scfg)
    pspecs = tree_param_specs(params_abs, prefix=(), mesh=mesh)
    param_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)

    tok, state, cross = serve_input_specs(cfg, shape)
    if cfg.embeds_in:
        tok_sh = NamedSharding(mesh, P(batch_ax, None, None))
    else:
        tok_sh = NamedSharding(mesh, P(batch_ax))

    from repro.sharding.specs import filter_divisible

    def cache_spec(path, leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)
        nd = len(leaf.shape)
        if nd == 0:
            spec = P()
        elif "ssm" in name and nd == 4:  # conv state (n_groups, B, W-1, ch)
            spec = P(None, batch_ax, None, "model")
        elif "ssm" in name and nd == 5:  # h (n_groups, B, H, N, P)
            spec = P(None, batch_ax, "model", None, None)
        elif nd == 5:  # KV cache (n_groups, B, C, Hkv, hd)
            if cache_shard == "head_dim":
                spec = P(None, batch_ax, seq_ax, None, "model")
            elif cache_shard == "seq":
                spec = P(None, batch_ax, "model", None, None)
            else:
                spec = P(None, batch_ax, seq_ax, "model", None)
        else:
            spec = P(*([None] * nd))
        return filter_divisible(spec, leaf.shape, mesh)

    state_sh = jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, cache_spec(p, l)), state,
    )
    cross_sh = None
    if cross is not None:
        cross_sh = jax.tree_util.tree_map(
            lambda l: NamedSharding(
                mesh, filter_divisible(P(None, batch_ax, None, "model", None), l.shape, mesh)
            ),
            cross,
        )
    return param_sh, tok_sh, state_sh, cross_sh, scfg


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def depth_config(cfg: ModelConfig, k: int) -> ModelConfig:
    """Same width, depth reduced to k layer-groups (cost-correction compiles)."""
    from repro.models.model import block_pattern

    _, n_groups = block_pattern(cfg)
    unit = cfg.num_layers // n_groups
    return cfg.with_(num_layers=unit * k)


def make_train_step(cfg: ModelConfig, mesh, *, lr: float = 1e-3,
                    mix_mode: str = "dense", psi: int = 0,
                    unroll: bool = False, cost_variant: bool = False,
                    mix_dtype=None, blocked_threshold: int = 8192,
                    vocab_chunk: int = 0, seq_parallel: bool = False):
    """One DRACO window: local grad -> Delta -> gossip mix -> apply.

    mix_mode: 'dense' (paper-faithful row-stochastic einsum over the
    client axis), 'ring' (collective_permute cycle lowering), or 'none'
    (no gossip — isolates local compute for roofline attribution).
    mix_dtype: gossip accumulation dtype (f32 faithful; bf16 halves
    collective bytes). blocked_threshold: seq length at which training
    attention switches to the blocked online-softmax path (memory knob).
    cost_variant disables inner-loop attention so XLA cost_analysis sees
    every flop (see dryrun depth-correction).
    """
    caxes = mesh_lib.client_axes(mesh)
    rules = train_rules(mesh, seq_parallel=seq_parallel)
    bat = 10**9 if cost_variant else blocked_threshold
    spmd_axis = caxes if len(caxes) > 1 else caxes[0]
    # the partitioner splits the einsum mix; it cannot split the Pallas
    # kernel, which runs only where the mesh is one device
    use_kernel = None if mesh.size == 1 else False

    def train_step(params, batch, q_eff):
        obs.count("repro.trace.train_step")

        def client_loss(p_i, b_i):
            return M.lm_loss(p_i, cfg, b_i, blocked_attn_threshold=bat,
                             unroll_groups=unroll, vocab_chunk=vocab_chunk)

        with use_rules(rules):
            with jax.named_scope("train.grad"):
                loss, grads = jax.vmap(
                    jax.value_and_grad(client_loss), spmd_axis_name=spmd_axis
                )(params, batch)
                delta = jax.tree_util.tree_map(lambda g: (-lr * g).astype(g.dtype), grads)
            if mix_mode == "dense":
                with jax.named_scope("train.mix"):
                    add = mixing.mix_dense(q_eff, delta,
                                           compute_dtype=mix_dtype or jnp.float32,
                                           use_kernel=use_kernel)
            elif mix_mode == "ring":
                with jax.named_scope("train.mix"):
                    add = mixing.mix_ring_shardmap(mesh, caxes, delta)
            elif mix_mode == "none":
                add = delta
            else:
                raise ValueError(mix_mode)
            with jax.named_scope("train.apply"):
                new_params = jax.tree_util.tree_map(
                    lambda p, a: p + a.astype(p.dtype), params, add
                )
        return new_params, loss.mean()

    return train_step


def make_unify_step(cfg: ModelConfig, mesh):
    """Periodic unification: hub's params broadcast to every client."""

    def unify_step(params, hub):
        obs.count("repro.trace.unify_step")
        with jax.named_scope("train.unify"):
            return jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(
                    jax.lax.dynamic_index_in_dim(p, hub, 0, keepdims=True), p.shape
                ),
                params,
            )

    return unify_step


def make_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                      unroll: bool = False, cost_variant: bool = False):
    scfg = serve_config(cfg, shape)
    rules = default_rules(mesh)
    bat = 10**9 if cost_variant else 8192

    def prefill_step(params, batch):
        with use_rules(rules):
            logits, _ = M.apply_model(params, scfg, batch,
                                      blocked_attn_threshold=bat,
                                      unroll_groups=unroll)
        return logits[:, -1, :]

    return prefill_step


def make_serve_step(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                    unroll: bool = False):
    scfg = serve_config(cfg, shape)
    rules = default_rules(mesh)

    def serve_step(params, tok, state, cross_kv=None):
        with use_rules(rules):
            logits, state = M.decode_step(params, scfg, tok, state, cross_kv,
                                          unroll_groups=unroll)
        return logits, state

    return serve_step
