"""Pallas TPU kernels: row-stochastic gossip aggregation.

``gossip_mix`` computes ``out = Q^T @ deltas`` for a small (N, N) mixing
matrix Q and one client-stacked leaf ``deltas`` (N, ...), in the leaf's
own shape and dtype: the trainer calls it once per parameter leaf of its
update (bf16 (N, 151936, 1536), (N, 2, 1536, 8960), (N, 2, 256), ...;
3.3e8 values per client for the Qwen2 stage), with no flat plane built.
The drain and enqueue kernels below serve the simulators' delay rings.

Blocking of the mix (`mix_block`):
  - The leaf is read where it lies. On the TPU, HBM tiles an array's
    minor two dims (a bf16 (A, B) slab in (16, 128) tiles), so a reshape
    that merges a dim into them is a relayout copy (merging a padded
    second-minor dim such as the (2, 256) biases' 2), and a 3-D view of a
    flat plane was a bitcast to another tiling whose compile did not
    finish. So the block keeps the leaf's rank: each block spans all N
    clients, one index of every dim between the client axis and the
    minor two (squeezed from the block), and a window of the minor two,
    whole or in multiples of the (8 or 16, 128) tile. A rank-2 leaf
    (N, K) is the one case whose minor two dims hold the clients: its
    blocks are (N, lanes). The last block of a dim may be ragged: output
    elements depend only on their own input elements, and out-of-bounds
    ones are never written. No padded copy is made.
  - The block's size comes from a VMEM budget: one block holds about
    ``MIX_BLOCK_BYTES`` of the leaf as f32 working values, with its dims
    rounded up to their tiling, so each grid step streams that much in
    and out. A grid step costs a fixed fraction of a microsecond, so
    small blocks make the kernel grid-bound: the former (2, 512) tiles
    of the f32 plane took 638,615 steps, 0.18 s, for a pass whose bytes
    take 6.4 ms at 819 GB/s. Input and output, double-buffered, stay
    inside the 16 MiB of VMEM a v5e kernel may use by default.
  - The contraction body follows the static N. Up to ``MIX_VPU_MAX_N``
    clients, each output client is ``sum_j q[j, i] * d[j]`` on the VPU,
    Q's scalars read from SMEM, accumulated in f32 in sender order: exact
    f32 arithmetic, one rounding to the leaf's dtype. Above it the MXU
    multiplies at ``precision=HIGHEST``, where the VPU's N^2 scalar
    products per element would set the time: one (N, N) x (N, lanes)
    dot per row of the block. Q stays the kernel's first operand, f32
    (N, N), in both.

Every dot runs at ``precision=HIGHEST``: the protocol mixes in f32, and
the MXU's default single bf16 pass would round the f32 weights and
payloads to 8 mantissa bits. Accumulation is f32 whatever the payload
dtype; widening a bf16 payload to f32 in VMEM is exact, so a bf16 leaf
mixes to the bits that the f32 plane, rounded back to bf16, gave.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128

# VMEM bytes of one mix block as f32. On a v5e the trainer's 14 bf16
# leaves (N = 2, VPU, one program) mix in 5.6 to 6.0 ms from 1 MiB to
# 4 MiB; over the former f32 plane a 4 MiB block passed the 16 MiB of
# scoped VMEM (scripts/mix_blocks.py, PERF.md).
MIX_BLOCK_BYTES = 2 << 20
# Largest N mixed on the VPU, the MXU above it. Set on a v5e over an f32
# (N, K) plane of 6.5e8 values, which no caller builds any more: the VPU
# body took 8.7 ms against the MXU's 22.9 ms at N = 2 and 11.5 ms at
# N = 4, they tied at N = 8, and at 16 the MXU was the faster (8.7 ms
# against 11.7). Not re-derived for the bf16 leaves the kernel now mixes:
# over the trainer's 14 leaves, one call each, the VPU body is still the
# faster at N = 8 (27.8 ms against 46.3), and N = 16 is unmeasured
# (scripts/mix_blocks.py, PERF.md).
MIX_VPU_MAX_N = 4


def _tile_rows(n: int) -> int:
    """Rows an (n, block) block occupies in memory: n rounded up to its
    tiling (1, 2 or 4 rows up to four, then a multiple of 8)."""
    if n <= 4:
        return 1 << (n - 1).bit_length()
    return -(-n // 8) * 8


def _sublanes(dtype) -> int:
    """Rows of one TPU tile of a `dtype` array's minor two dims: 8 at 32
    bits, 16 at 16 bits."""
    return 8 * max(4 // jnp.dtype(dtype).itemsize, 1)


def mix_block_d(n: int, k: int, dtype, budget: int = MIX_BLOCK_BYTES) -> int:
    """Lanes per grid step of the mix of an (n, k) leaf: a multiple of 128
    whose (n, lanes) block fills `budget` bytes of VMEM as f32, or all K
    lanes if fewer. Narrower payloads get the f32 size too: the body's
    f32 working values take VMEM in proportion to the block."""
    per_lane = _tile_rows(n) * max(jnp.dtype(dtype).itemsize, 4)
    block = max(budget // per_lane // _LANES, 1) * _LANES
    return min(block, k)


def mix_block(shape, dtype, budget: int = MIX_BLOCK_BYTES) -> tuple:
    """Block of the mix over a client-stacked leaf of `shape` (N, ...):
    (N, lanes) for a rank-2 leaf (`mix_block_d`); else (N, None, ...,
    rows, lanes), one index of each middle dim (None, squeezed) and a
    (rows, lanes) window of the minor two dims that fills about `budget`
    bytes of VMEM as f32 over the N clients, dims rounded up to their
    tiling: all lanes and as many whole tiles of rows as fit (all rows if
    they fit), else one tile of rows and a multiple of 128 lanes."""
    n = shape[0]
    if len(shape) == 2:
        return (n, mix_block_d(n, shape[1], dtype, budget))
    rows, lanes = shape[-2:]
    sub = _sublanes(dtype)
    per_row = 4 * n * -(-lanes // _LANES) * _LANES
    fit = budget // per_row
    if fit >= -(-rows // sub) * sub:
        block = (rows, lanes)
    elif fit >= sub:
        block = (fit // sub * sub, lanes)
    else:
        block = (min(rows, sub),
                 max(budget // (4 * n * sub * _LANES), 1) * _LANES)
    return (n,) + (None,) * (len(shape) - 3) + block


def _mix_vpu_kernel(q_ref, d_ref, o_ref):
    """out[i] = sum_j q[j, i] * d[j]: Q's scalars from SMEM, f32 sums in
    sender order, one rounding to the output's dtype."""
    n = d_ref.shape[0]
    for i in range(n):
        acc = q_ref[0, i] * d_ref[0:1].astype(jnp.float32)
        for j in range(1, n):
            acc = acc + q_ref[j, i] * d_ref[j:j + 1].astype(jnp.float32)
        o_ref[i:i + 1] = acc.astype(o_ref.dtype)


def _mix_mxu_kernel(q_ref, d_ref, o_ref):
    """out = Q^T d on the MXU at HIGHEST: one (N, N) x (N, lanes) dot per
    row of the block."""
    qt = q_ref[...].astype(jnp.float32).T  # (N, N) resident
    d = d_ref[...].astype(jnp.float32)  # (N, lanes) or (N, rows, lanes)

    def dot(x):
        return jnp.dot(qt, x, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)

    if d.ndim == 2:
        out = dot(d)
    else:
        out = jnp.stack([dot(d[:, r]) for r in range(d.shape[1])], axis=1)
    o_ref[...] = out.astype(o_ref.dtype)


def _mix_call(kernel, q, deltas, block, interpret):
    n = deltas.shape[0]
    assert q.shape == (n, n) and block[0] == n, (q.shape, block)
    if kernel is _mix_vpu_kernel:
        q_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    else:
        q_spec = pl.BlockSpec((n, n), lambda *g: (0, 0))  # resident in VMEM
    grid = tuple(pl.cdiv(size, 1 if b is None else b)
                 for size, b in zip(deltas.shape[1:], block[1:]))
    spec = pl.BlockSpec(block, lambda *g: (0,) + g)
    return pl.pallas_call(
        kernel,
        name="gossip_mix",
        grid=grid,
        in_specs=[q_spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(deltas.shape, deltas.dtype),
        interpret=interpret,
    )(q, deltas)


def gossip_mix_pallas(q, deltas, *, interpret: bool = False):
    """q (N, N) f32; deltas (N, K), or any client-stacked leaf (N, ...) of
    rank 2 or more, unpadded, in its own dtype -> Q^T @ deltas in that
    shape and dtype, in `mix_block`'s blocks, on the VPU or the MXU by N
    (module docstring)."""
    n = deltas.shape[0]
    kernel = _mix_vpu_kernel if n <= MIX_VPU_MAX_N else _mix_mxu_kernel
    return _mix_call(kernel, q, deltas, mix_block(deltas.shape, deltas.dtype),
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
