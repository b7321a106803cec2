"""The trainer's step functions and the abstract specs of their inputs.

``make_train_step``: one DRACO superposition window on the mesh — every
client (= model-shard group on the client axis) runs a local grad step,
forms Delta, and the row-stochastic gossip mix is applied over the
client axis. Event masks / channel masks arrive as the per-window
effective Q (q_eff) input, so the compiled step is purely data-dependent
(no host control flow). ``make_unify_step``: periodic unification.

``train_batch_specs``, ``param_specs_abstract``,
``stack_clients_abstract`` and ``make_shardings``: ShapeDtypeStruct
stand-ins and shardings for the step's inputs — no allocation.
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
from repro.sharding.axes import train_rules, use_rules
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


def param_specs_abstract(cfg: ModelConfig):
    return jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))


def stack_clients_abstract(params_abs, n_clients: int):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n_clients,) + tuple(l.shape), l.dtype), params_abs
    )


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


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def depth_config(cfg: ModelConfig, k: int) -> ModelConfig:
    """Same width, depth reduced to k layer-groups."""
    from repro.models.model import block_pattern

    _, n_groups = block_pattern(cfg)
    unit = cfg.num_layers // n_groups
    return cfg.with_(num_layers=unit * k)


def make_train_step(cfg: ModelConfig, mesh, *, lr: float = 1e-3,
                    mix_mode: str = "dense"):
    """One DRACO window: local grad -> Delta -> gossip mix -> apply.

    lr: the local step's learning rate (Delta = -lr * grad).
    mix_mode: 'dense' (paper-faithful row-stochastic mix over the client
    axis, `mixing.mix_dense`), 'ring' (collective_permute cycle
    lowering), or 'none' (no gossip: each client keeps its own Delta).
    """
    caxes = mesh_lib.client_axes(mesh)
    rules = train_rules(mesh)
    spmd_axis = caxes if len(caxes) > 1 else caxes[0]
    # the partitioner splits the einsum mix; it cannot split the Pallas
    # kernel, which runs only where the mesh is one device
    use_kernel = None if mesh.size == 1 else False

    def train_step(params, batch, q_eff):
        obs.count("repro.trace.train_step")

        def client_loss(p_i, b_i):
            return M.lm_loss(p_i, cfg, b_i)

        with use_rules(rules):
            with jax.named_scope("train.grad"):
                loss, grads = jax.vmap(
                    jax.value_and_grad(client_loss), spmd_axis_name=spmd_axis
                )(params, batch)
                delta = jax.tree_util.tree_map(lambda g: (-lr * g).astype(g.dtype), grads)
            if mix_mode == "dense":
                with jax.named_scope("train.mix"):
                    add = mixing.mix_dense(q_eff, delta, use_kernel=use_kernel)
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
