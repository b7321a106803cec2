"""Pure-jnp oracles for the gossip kernels (f32 matmuls at HIGHEST)."""
import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def gossip_mix_ref(q, deltas):
    """out[m, ...] = sum_n q[n, m] * deltas[n, ...].

    q: (N, N) row-stochastic (sender, receiver), deltas: (N, K) or any
    client-stacked leaf (N, ...). Accumulation in f32, output in
    deltas.dtype.
    """
    out = jnp.einsum(
        "nm,n...->m...", q.astype(jnp.float32), deltas.astype(jnp.float32),
        precision=_HIGHEST)
    return out.astype(deltas.dtype)


def gossip_enqueue_ref(w_stack, pending, out_dtype=None):
    """Batched delay-bucketed mix: out[j] = w_stack[j]^T @ pending.

    w_stack: (J, N, N) per-bucket masked weights (Q ⊙ M_d), pending:
    (N, K).  f32 accumulation; output dtype defaults to pending.dtype.
    """
    out = jnp.einsum(
        "jnm,nk->jmk", w_stack.astype(jnp.float32), pending.astype(jnp.float32),
        precision=_HIGHEST)
    return out.astype(pending.dtype if out_dtype is None else out_dtype)


def gossip_drain_ref(w_stack, payloads, out_dtype=jnp.float32):
    """Fused multi-window drain: out = sum_j w_stack[j]^T @ payloads[j].

    w_stack: (J, N, N), payloads: (J, N, K), stacked oldest-first.
    f32 accumulation.
    """
    out = jnp.einsum(
        "jnm,jnk->mk", w_stack.astype(jnp.float32), payloads.astype(jnp.float32),
        precision=_HIGHEST)
    return out.astype(out_dtype)
