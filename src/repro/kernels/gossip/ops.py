"""jit'd public wrappers for the gossip kernels (padding, backend select).

Backend auto-selection (one policy for every wrapper):

  - ``use_kernel=None``  -> Pallas only on TPU; pure-XLA lowering
    elsewhere (the kernel path in ``interpret`` mode is a correctness
    tool, far too slow for CPU CI hot loops). Mosaic kernels cannot be
    partitioned automatically, so a caller that jits over a multi-device
    mesh without a ``shard_map`` passes ``use_kernel=False``.
  - ``interpret=None``   -> interpret mode exactly when not on TPU, so
    explicitly requesting the kernel path off-TPU still works (tests),
    while on TPU the compiled kernel is actually exercised.

Both lowerings multiply at ``precision=HIGHEST`` (f32 mixing on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gossip.gossip import (
    gossip_drain_pallas,
    gossip_enqueue_pallas,
    gossip_mix_pallas,
)
from repro.kernels.gossip.ref import gossip_enqueue_ref, gossip_mix_ref


def default_interpret() -> bool:
    """Pallas interpret mode iff there is no TPU to compile for."""
    return jax.default_backend() != "tpu"


def default_use_kernel() -> bool:
    """Use the Pallas kernels only where they compile natively."""
    return jax.default_backend() == "tpu"


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gossip_mix(q, deltas, *, interpret=None):
    """out = Q^T @ deltas, summed in f32; q (N, N) and deltas (N, K), or
    any one client-stacked leaf (N, ...), such as a parameter leaf of the
    trainer's update -> the same shape and dtype. The leaf is read where it
    lies: no padding and no relayout (an (N,) leaf is mixed as (N, 1)),
    since at model width a copy would move as many bytes as the mix. The
    kernel's block comes from a VMEM budget and its body (VPU or MXU)
    from N (`gossip.mix_block`, `gossip.MIX_VPU_MAX_N`)."""
    if interpret is None:
        interpret = default_interpret()
    q = q.astype(jnp.float32)
    if deltas.ndim == 1:
        return gossip_mix_pallas(q, deltas[:, None],
                                 interpret=interpret)[:, 0]
    return gossip_mix_pallas(q, deltas, interpret=interpret)


def gossip_mix_reference(q, deltas):
    """Pure-jnp oracle: q (N, N), deltas (N, K) or any client-stacked
    leaf (N, ...) -> Q^T @ deltas."""
    return gossip_mix_ref(q, deltas)


def gossip_enqueue(w_stack, pending, *, block_d: int = 512, use_kernel=None,
                   interpret=None, out_dtype=None):
    """Batched delay-bucketed mixing: ``out[j] = w_stack[j]^T @ pending``.

    This is the *eager* lowering of bucketed gossip — mix one broadcast
    into all J delay buckets at send time.  The production DRACO engine
    instead stores raw payloads and defers mixing to `gossip_drain`;
    `gossip_enqueue` is kept as the eager building block (and as the
    oracle structure the drain parity tests lean on) for protocols that
    want mixed-delta rings.

    w_stack (J, N, N): per-delay-bucket masked weights (Q ⊙ M_d) for all
    buckets j at once; pending (N, K) flat updates.  Returns (J, N, K).
    On TPU this is one Pallas grid pass reading each pending tile from
    HBM exactly once (stacked weights resident in VMEM); elsewhere a
    batched einsum.  f32 accumulation regardless of input dtype;
    ``out_dtype`` defaults to ``pending.dtype``.
    """
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if not use_kernel:
        return gossip_enqueue_ref(w_stack, pending, out_dtype=out_dtype)
    if interpret is None:
        interpret = default_interpret()
    j, n, _ = w_stack.shape
    _, k = pending.shape
    wp = _pad_to(_pad_to(w_stack.astype(jnp.float32), 8, 1), 8, 2)
    pp = _pad_to(_pad_to(pending, 8, 0), block_d, 1)
    out = gossip_enqueue_pallas(
        wp, pp, block_d=block_d, interpret=interpret,
        out_dtype=pending.dtype if out_dtype is None else out_dtype)
    return out[:, :n, :k]


def gossip_drain(w_stack, ring, slots, *, block_d: int = 512, use_kernel=None,
                 interpret=None):
    """Fused delay-bucketed drain: ``sum_j w_stack[j]^T @ ring[slots[j]]``.

    w_stack (J, N, M): masked weights per stored broadcast, stacked
    oldest-first — square (M == N) on the single-device path,
    rectangular (a senders slice against all M receivers) under
    `gossip_drain_sharded`; ring (S, N, K): the payload ring buffer;
    slots (J,): ring rows aligned with ``w_stack`` (oldest first).
    Returns the f32 (M, K) aggregate of everything arriving this window.

    The f32 accumulation runs in chronological order, so the result is
    bit-for-bit what the seed ring buffer would have accumulated slot by
    slot.  The XLA fallback unrolls one small GEMM per stored broadcast
    and wraps each in ``lax.cond`` keyed on "does this bucket carry any
    edge at all" — empty delay buckets (the common case when the delay
    distribution does not fill the ring) cost neither FLOPs nor memory
    traffic, which is what makes deep ``D`` nearly free.  Skipping is
    exact: an all-zero weight bucket contributes an exact ±0 matrix.
    """
    if use_kernel is None:
        use_kernel = default_use_kernel()
    m = w_stack.shape[2]  # receivers (== senders except per-shard slices)
    k = ring.shape[2]
    j_total = w_stack.shape[0]
    if use_kernel:
        if interpret is None:
            interpret = default_interpret()
        payloads = ring[slots]  # (J, N, K) HBM gather, chronological order
        wp = _pad_to(_pad_to(w_stack.astype(jnp.float32), 8, 1), 8, 2)
        pp = _pad_to(_pad_to(payloads, 8, 1), block_d, 2)
        out = gossip_drain_pallas(wp, pp, block_d=block_d, interpret=interpret)
        return out[:m, :k]
    out = jnp.zeros((m, k), jnp.float32)
    for j in range(j_total):
        w_j = w_stack[j].astype(jnp.float32)

        def _acc(o, w_j=w_j, j=j):
            p = jax.lax.dynamic_index_in_dim(ring, slots[j], 0, keepdims=False)
            return o + jax.lax.dot(w_j.T, p.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST)

        out = jax.lax.cond(jnp.any(w_j != 0), _acc, lambda o: o, out)
    return out


def gossip_drain_sharded(w_stack, ring, slots, mesh, client_axes, *,
                         block_d: int = 512, use_kernel=None, interpret=None):
    """Client-sharded drain: per-device tiles + one `psum_scatter`.

    The explicit `shard_map` lowering of the sweep engine's sharded
    gossip contraction: the payload ring is sharded over the *sender*
    axis (each device holds its clients' stored broadcasts), every
    device runs `gossip_drain` on its `(J, N_loc, N)` weight slice —
    the Pallas grid on TPU, the unrolled-GEMM fallback elsewhere — and a
    single ``lax.psum_scatter`` over the *receiver* axis both sums the
    per-device partials and leaves each device holding exactly its own
    clients' aggregate (no gather; the TPU compiler may lower it as an
    all-reduce plus a local slice, as it does at paper scale on v5e).

    w_stack (J, N, N) and ring (S, N, K) are both sharded on their
    *sender* axis (axis 1) over `client_axes` (a mesh axis name or
    tuple, e.g. the `sharding/axes.py` "clients" rule) — each device
    holds a rectangular (J, N_loc, N) weight slice and its senders'
    payloads; slots (J,) is replicated. N must divide the client mesh
    size. Returns the (N, K) f32 aggregate, sharded on axis 0.

    The per-receiver sum is re-associated across devices (psum order),
    so the result matches `gossip_drain` up to f32 reduction order —
    exact when every sender bucket lives on one device.
    """
    from jax.sharding import PartitionSpec as P

    axes = client_axes if isinstance(client_axes, tuple) else (client_axes,)
    # one name for both roles: PartitionSpec entry and collective axis
    ax = axes if len(axes) > 1 else axes[0]
    ndev = 1
    for a in axes:
        ndev *= mesh.shape[a]
    n = ring.shape[1]
    if n % ndev:
        raise ValueError(f"client count {n} not divisible by mesh client "
                         f"size {ndev}")

    def body(w, r, s):
        # w (J, N_loc, N): this device's senders against all receivers
        partial_full = gossip_drain(w, r, s, block_d=block_d,
                                    use_kernel=use_kernel,
                                    interpret=interpret)  # (N, K)
        # sum partials across devices AND keep only our receiver rows
        return jax.lax.psum_scatter(partial_full, ax,
                                    scatter_dimension=0, tiled=True)

    # check_vma=False: pallas_call has no shard_map varying-axes rule;
    # the output spec is exact — psum_scatter leaves each device its
    # receiver rows
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, ax, None), P(None, ax, None), P()),
                       out_specs=P(ax, None), check_vma=False)
    return fn(w_stack, ring, slots)
