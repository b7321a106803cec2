"""Gossip aggregation: ``x_j += sum_i q[i,j] * delta_i``.

Three pieces of the same row-stochastic semantics:

  - ``mix_dense``   : Q^T against the full (N, N) Q, one client-stacked
    leaf at a time in the leaf's own shape and dtype: the Pallas
    ``gossip_mix`` kernel on one chip (a bf16 leaf read where it lies,
    f32 sums), an einsum elsewhere. With the client axis sharded over
    ("pod","data") the einsum lowers to all-gather + local matmul — the
    paper-faithful baseline (arbitrary digraphs).
  - ``psi_cap_mask``: applies the paper's Psi cap by keeping only the
    top-Psi incoming weights per receiver before mixing. On the mesh this
    bounds collective bytes per window — the paper's communication-budget
    knob becomes an ICI-bandwidth knob.
  - ``mix_ring_shardmap``: shard_map + lax.ppermute for cycle/ring
    topologies — gossip edges map 1:1 onto ICI torus links (beyond-paper
    optimization; no all-gather, 2 neighbor permutes).

All operate on pytrees whose leaves have a leading client axis N.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.gossip import ops as gossip_ops


def receive_counts(q_mask) -> jax.Array:
    """Messages incoming per receiver j: count of nonzero column entries."""
    return (q_mask > 0).sum(axis=0)


def psi_cap_mask(key, q, psi: int):
    """Keep at most `psi` incoming edges per receiver (column-wise top-psi
    by weight with random tie-break), zeroing the rest. Returns masked q.

    Uses argsort ranking (strict order even under exact weight ties)."""
    n = q.shape[0]
    if psi >= n:
        return q
    noise = jax.random.uniform(key, q.shape, minval=0.0, maxval=1e-6)
    score = jnp.where(q > 0, q + noise, -jnp.inf)  # (sender, receiver)
    order = jnp.argsort(-score, axis=0)  # per receiver: best sender first
    rank = jnp.zeros((n, n), jnp.int32)
    rank = rank.at[order, jnp.arange(n)[None, :]].set(
        jnp.broadcast_to(jnp.arange(n)[:, None], (n, n))
    )
    keep = (rank < psi) & (q > 0)
    return jnp.where(keep, q, 0.0)


def mix_dense(q_eff, deltas, *, use_kernel=None, interpret=None,
              compute_dtype=jnp.float32):
    """x_add = Q^T @ deltas, leaf by leaf. q_eff (N,N) masked/weighted;
    deltas a pytree of client-stacked (N, ...) leaves.

    Each leaf is mixed in its own shape and comes back in its own dtype:
    by the Pallas gossip kernel on TPU (`use_kernel=None` auto-selects by
    backend), which reads the leaf where it lies and sums in f32, or by a
    HIGHEST-precision einsum elsewhere. No (N, Dflat) plane is built: at
    the trainer's width, widening the bf16 update into an f32 plane and
    slicing it back moved five times the bytes of the mix itself.

    compute_dtype: accumulation dtype of the einsum lowering (the kernel
    always sums in f32). f32 is the paper-faithful default; bf16 halves
    the all-gather bytes on the mesh (beyond-paper knob, see
    EXPERIMENTS.md §Perf). A leaf that compute_dtype cannot hold exactly
    is rounded to it first, on either lowering."""
    if use_kernel is None:
        use_kernel = gossip_ops.default_use_kernel()

    def mix(x):
        if not use_kernel:
            out = jnp.einsum("nm,n...->m...", q_eff.astype(compute_dtype),
                             x.astype(compute_dtype),
                             precision=jax.lax.Precision.HIGHEST)
        elif jnp.promote_types(x.dtype, compute_dtype) != compute_dtype:
            out = gossip_ops.gossip_mix(q_eff, x.astype(compute_dtype),
                                        interpret=interpret)
        else:
            out = gossip_ops.gossip_mix(q_eff, x, interpret=interpret)
        return out.astype(x.dtype)

    return jax.tree_util.tree_map(mix, deltas)


def apply_mix(params, q_eff, deltas, **kw):
    add = mix_dense(q_eff, deltas, **kw)
    return jax.tree_util.tree_map(lambda p, a: p + a.astype(p.dtype), params, add)


# ---------------------------------------------------------------------------
# Ring lowering (cycle topology -> ICI neighbor permutes)
# ---------------------------------------------------------------------------


def mix_ring_shardmap(mesh, client_axes, deltas, w_fwd: float = 0.5, w_bwd: float = 0.5,
                      gate_fwd=None, gate_bwd=None):
    """Cycle-gossip via collective_permute on the client mesh axes.

    Each client receives w_fwd * delta_{i-1} + w_bwd * delta_{i+1}
    (directed ring if one weight is 0). `gate_*` are optional per-client
    (N,) multipliers (event/Psi masks) applied at the *sender*.

    Lowering: two lax.ppermute ops — bytes per device = 2 * |delta|/TP,
    strictly neighbor traffic on the ICI torus (no all-gather). The
    in/out specs preserve each leaf's model-axis sharding (a naive
    P(clients, None, ...) spec forces an all-gather of expert/TP-sharded
    leaves over "model" before the permute — measured regression).
    """
    from repro.sharding.specs import param_spec

    axes = client_axes if isinstance(client_axes, tuple) else (client_axes,)
    ax0 = axes if len(axes) > 1 else axes[0]
    in_specs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(path, tuple(leaf.shape), mesh, prefix=(ax0,)),
        deltas,
    )
    gspec = P(ax0)

    n_clients = 1
    for a in axes:
        n_clients *= mesh.shape[a]
    fwd_perm = [(i, (i + 1) % n_clients) for i in range(n_clients)]
    bwd_perm = [(i, (i - 1) % n_clients) for i in range(n_clients)]

    if gate_fwd is None:
        gate_fwd = jnp.ones((n_clients,), jnp.float32)
    if gate_bwd is None:
        gate_bwd = jnp.ones((n_clients,), jnp.float32)

    axis_name = axes[0] if len(axes) == 1 else axes

    def body(d, gf, gb):
        # inside shard_map: leading client axis has local size 1
        def leaf(x, gfl, gbl):
            gfl = gfl.reshape((1,) + (1,) * (x.ndim - 1))
            gbl = gbl.reshape((1,) + (1,) * (x.ndim - 1))
            # fwd_perm: i -> i+1, so after the permute each client holds the
            # value its ring-predecessor sent (the forward edge j-1 -> j).
            xf = jax.lax.ppermute(x * gfl.astype(x.dtype), axis_name=axis_name, perm=fwd_perm)
            xb = jax.lax.ppermute(x * gbl.astype(x.dtype), axis_name=axis_name, perm=bwd_perm)
            return (w_fwd * xf + w_bwd * xb).astype(x.dtype)

        return jax.tree_util.tree_map(lambda x: leaf(x, gf, gb), d)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(in_specs, gspec, gspec),
        out_specs=in_specs,
    )
    return fn(deltas, gate_fwd, gate_bwd)
