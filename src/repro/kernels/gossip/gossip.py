"""Pallas TPU kernel: row-stochastic gossip aggregation.

Computes ``out = Q^T @ deltas`` for a small (N, N) mixing matrix Q and a
huge (N, D) stacked-update matrix (D = flattened parameter count /
tensor-parallel shard — hundreds of MB in production).

TPU-native blocking rationale:
  - D is tiled into ``block_d`` lanes (multiple of 128 to match the MXU
    lane width); each grid step streams one (N, block_d) tile of deltas
    HBM->VMEM, multiplies by the resident (N, N) Q tile on the MXU and
    writes one (N, block_d) output tile. Every delta byte moves exactly
    once — the kernel is purely memory-bound, matching its roofline role.
  - Each block spans all N rows (the client axis, 16..64), so N needs
    no 8-sublane padding; accumulation is f32 regardless of input dtype
    (bf16 deltas are common).
  - Every dot runs at ``precision=HIGHEST``: the protocol mixes in f32,
    and the MXU's default single bf16 pass would round the f32 weights
    and payloads to 8 mantissa bits. The kernels are memory-bound, so
    the extra passes cost no time.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _gossip_kernel(q_ref, d_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)  # (N, N) resident
    d = d_ref[...].astype(jnp.float32)  # (N, block_d)
    o_ref[...] = jnp.dot(
        q.T, d, precision=_HIGHEST, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def gossip_mix_pallas(q, deltas, *, block_d: int = 512, interpret: bool = False):
    """q (N, N) f32; deltas (N, K), unpadded.

    Each block spans all N rows (a block dim equal to the array dim needs
    no 8-sublane multiple), so no padded copy of the (N, K) plane is made.
    A ragged last K tile is fine: output columns depend only on their own
    input columns, and its out-of-bounds lanes are never written."""
    n, d_total = deltas.shape
    assert q.shape == (n, n)
    grid = (pl.cdiv(d_total, block_d),)
    return pl.pallas_call(
        _gossip_kernel,
        name="gossip_mix",
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),  # Q resident in VMEM
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d_total), deltas.dtype),
        interpret=interpret,
    )(q, deltas)


def _enqueue_kernel(w_ref, p_ref, o_ref):
    """One (N, block_d) pending tile -> all J delay-bucket outputs."""
    p = p_ref[...].astype(jnp.float32)  # read the tile from HBM exactly once
    for j in range(w_ref.shape[0]):  # static unroll: J small (D-1)
        w = w_ref[j].astype(jnp.float32)
        o_ref[j] = jnp.dot(w.T, p, precision=_HIGHEST,
                           preferred_element_type=jnp.float32).astype(o_ref.dtype)


def gossip_enqueue_pallas(w_stack, pending, *, block_d: int = 512,
                          interpret: bool = False, out_dtype=None):
    """Batched delay-bucketed mixing: ``out[j] = w_stack[j]^T @ pending``.

    w_stack (J, N, N) f32 — the per-bucket masked weights (Q ⊙ M_d),
    stacked and resident in VMEM; pending (N, K) with K % block_d == 0.
    Each (N, block_d) pending tile moves HBM->VMEM once and feeds all J
    bucket outputs, vs J separate full passes for per-bucket einsums.
    """
    j_total, n, _ = w_stack.shape
    n2, k_total = pending.shape
    assert n == n2 and w_stack.shape == (j_total, n, n)
    assert k_total % block_d == 0, (k_total, block_d)
    out_dtype = pending.dtype if out_dtype is None else out_dtype
    grid = (k_total // block_d,)
    return pl.pallas_call(
        _enqueue_kernel,
        name="gossip_enqueue",
        grid=grid,
        in_specs=[
            pl.BlockSpec((j_total, n, n), lambda i: (0, 0, 0)),  # VMEM resident
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((j_total, n, block_d), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((j_total, n, k_total), out_dtype),
        interpret=interpret,
    )(w_stack, pending)


def _drain_kernel(w_ref, p_ref, o_ref):
    """Accumulate all J buckets' arrivals for one (N, block_d) tile."""
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(w_ref.shape[0]):  # static unroll; order = stack order
        w = w_ref[j].astype(jnp.float32)
        p = p_ref[j].astype(jnp.float32)  # each payload tile read once
        acc = acc + jnp.dot(w.T, p, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def gossip_drain_pallas(w_stack, payloads, *, block_d: int = 512,
                        interpret: bool = False, out_dtype=jnp.float32):
    """Fused multi-window drain: ``out = sum_j w_stack[j]^T @ payloads[j]``.

    w_stack (J, N, M) f32 — senders x receivers, square (M == N) on the
    single-device path, rectangular when a client shard drains its
    N-senders slice against all M receivers (`ops.gossip_drain_sharded`);
    payloads (J, N, K) with K % block_d == 0 — one stored broadcast per
    ring slot, in *chronological* (oldest-first) order so the f32
    accumulation matches the seed ring-buffer order. Every payload byte
    moves HBM->VMEM exactly once per window. Returns (M, K).
    """
    j_total, n, m = w_stack.shape
    assert payloads.shape[:2] == (j_total, n)
    k_total = payloads.shape[2]
    assert k_total % block_d == 0, (k_total, block_d)
    grid = (k_total // block_d,)
    return pl.pallas_call(
        _drain_kernel,
        name="gossip_drain",
        grid=grid,
        in_specs=[
            pl.BlockSpec((j_total, n, m), lambda i: (0, 0, 0)),  # VMEM resident
            pl.BlockSpec((j_total, n, block_d), lambda i: (0, 0, i)),
        ],
        out_specs=pl.BlockSpec((m, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, k_total), out_dtype),
        interpret=interpret,
    )(w_stack, payloads)
