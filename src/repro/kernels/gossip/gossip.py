"""Pallas TPU kernels: row-stochastic gossip aggregation.

``gossip_mix`` computes ``out = Q^T @ deltas`` for a small (N, N) mixing
matrix Q and a huge (N, K) stacked-update plane (K = flattened parameter
count: 3.3e8 per client for the trainer's Qwen2 stage, 1.3 GB in f32).
The drain and enqueue kernels below serve the simulators' delay rings.

Blocking of the mix:
  - The plane stays 2-D and unpadded: at K = 3.3e8 and N = 2 it lies in
    HBM as ``f32[2, K]{1,0:T(2,128)}`` (rows tiled by the next power of
    two up to 8), which a (N, block_d) block reads as it is. A 3-D view
    (N, K/512, 512) is a bitcast to another tiling, not the row-major
    layout a Pallas operand takes, and its compile did not finish.
    Each block spans all N rows, and the last K block may be ragged:
    output columns depend only on their own input columns, and its
    out-of-bounds lanes are never written.
  - ``block_d`` comes from a VMEM budget (`mix_block_d`): one input
    buffer holds about ``MIX_BLOCK_BYTES`` of the plane as VMEM tiles it
    (rows rounded up to the tiling), so each grid step streams that much
    in and out. A grid step costs a fixed fraction of a microsecond, so
    small blocks make the kernel grid-bound: the former (2, 512) tiles
    took 638,615 steps, 0.18 s, for a pass whose bytes take 6.4 ms at
    819 GB/s. Input and output, double-buffered, stay inside the 16 MiB
    of VMEM a v5e kernel may use by default.
  - The contraction body follows the static N. Up to ``MIX_VPU_MAX_N``
    clients, each output row is ``sum_j q[j, i] * d[j]`` on the VPU, Q's
    scalars read from SMEM, accumulated in f32 in sender order: exact
    f32 arithmetic. Above it the MXU multiplies at ``precision=HIGHEST``,
    where the VPU's N^2 scalar products per lane would set the time.
    Q stays the kernel's first operand, f32 (N, N), in both.

Every dot runs at ``precision=HIGHEST``: the protocol mixes in f32, and
the MXU's default single bf16 pass would round the f32 weights and
payloads to 8 mantissa bits. Accumulation is f32 whatever the payload
dtype (bf16 deltas are common).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128

# VMEM bytes of one (N, block_d) input buffer of the mix. On a v5e the
# kernel streams 600 GB/s from 1 MiB to 4 MiB; at 4 MiB the MXU body
# passes the 16 MiB scoped VMEM (scripts/mix_blocks.py, PERF.md).
MIX_BLOCK_BYTES = 2 << 20
# Largest N mixed on the VPU, the MXU above it. On a v5e at the budget
# block the VPU body takes 8.7 ms against the MXU's 22.9 ms at N = 2 and
# 11.5 ms at N = 4; at N = 8 they tie, at 16 the VPU's takes 11.7 ms
# against 8.7 ms (scripts/mix_blocks.py, PERF.md).
MIX_VPU_MAX_N = 4


def _tile_rows(n: int) -> int:
    """Rows an (n, block) block occupies in memory: n rounded up to its
    tiling (1, 2 or 4 rows up to four, then a multiple of 8)."""
    if n <= 4:
        return 1 << (n - 1).bit_length()
    return -(-n // 8) * 8


def mix_block_d(n: int, k: int, dtype) -> int:
    """Lanes per grid step of the mix: a multiple of 128 whose (n, lanes)
    block fills ``MIX_BLOCK_BYTES`` of VMEM as f32, or all K lanes if
    fewer. Narrower payloads get the f32 size too: the body's f32
    working values take VMEM in proportion to the block."""
    per_lane = _tile_rows(n) * max(jnp.dtype(dtype).itemsize, 4)
    block = max(MIX_BLOCK_BYTES // per_lane // _LANES, 1) * _LANES
    return min(block, k)


def _mix_vpu_kernel(q_ref, d_ref, o_ref):
    """out[i] = sum_j q[j, i] * d[j]: Q's scalars from SMEM, f32 sums in
    sender order."""
    n = d_ref.shape[0]
    for i in range(n):
        acc = q_ref[0, i] * d_ref[0:1, :].astype(jnp.float32)
        for j in range(1, n):
            acc = acc + q_ref[j, i] * d_ref[j:j + 1, :].astype(jnp.float32)
        o_ref[i:i + 1, :] = acc.astype(o_ref.dtype)


def _mix_mxu_kernel(q_ref, d_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)  # (N, N) resident
    d = d_ref[...].astype(jnp.float32)  # (N, block_d)
    o_ref[...] = jnp.dot(
        q.T, d, precision=_HIGHEST, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def _mix_call(kernel, q, deltas, block_d, interpret):
    n, d_total = deltas.shape
    assert q.shape == (n, n)
    if kernel is _mix_vpu_kernel:
        q_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    else:
        q_spec = pl.BlockSpec((n, n), lambda i: (0, 0))  # resident in VMEM
    return pl.pallas_call(
        kernel,
        name="gossip_mix",
        grid=(pl.cdiv(d_total, block_d),),
        in_specs=[q_spec, pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d_total), deltas.dtype),
        interpret=interpret,
    )(q, deltas)


def gossip_mix_pallas(q, deltas, *, interpret: bool = False):
    """q (N, N) f32; deltas (N, K), unpadded -> Q^T @ deltas (N, K), in
    `mix_block_d`'s blocks, on the VPU or the MXU by N (module
    docstring)."""
    n, k = deltas.shape
    kernel = _mix_vpu_kernel if n <= MIX_VPU_MAX_N else _mix_mxu_kernel
    return _mix_call(kernel, q, deltas, mix_block_d(n, k, deltas.dtype),
                     interpret)


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
