"""Pallas TPU kernel: Mamba2 SSD intra-chunk block.

The SSD "dual" form makes the intra-chunk computation an attention-like
pair of matmuls — exactly the MXU's sweet spot:

    scores = (C @ B^T) o exp(cums_i - cums_j) o dt_j   (Q x Q, masked)
    Y      = scores @ X                                 (Q x P)
    S      = (B * decay_dt)^T @ X                       (N x P)

Blocking: grid over (batch*heads, n_chunks); each step holds one chunk's
C/B (Q, N), X (Q, P) and the (Q, Q) score tile in VMEM. With the default
Q = 128, N = 128, P = 64 everything is lane/sublane aligned and the
working set is ~200 KB — far under the ~16 MB v5e VMEM, leaving room for
double buffering of the HBM streams.

The inter-chunk state recurrence (a tiny associative scan over n_chunks)
stays in JAX; it is O(T/Q) and bandwidth-trivial.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(c_ref, b_ref, x_ref, cums_ref, dt_ref, y_ref, s_ref):
    C = c_ref[0, 0].astype(jnp.float32)  # (Q, N)
    B = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    X = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    cums = cums_ref[0, 0].astype(jnp.float32)[:, 0]  # (Q,)
    dt = dt_ref[0, 0].astype(jnp.float32)[:, 0]  # (Q,)
    Q = C.shape[0]

    CB = jnp.dot(C, B.T, preferred_element_type=jnp.float32)  # (Qi, Qj)
    li = cums[:, None] - cums[None, :]
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.exp(jnp.where(iota_j <= iota_i, li, -1e30))  # mask pre-exp
    scores = CB * L * dt[None, :]
    y_ref[0, 0] = jnp.dot(scores, X, preferred_element_type=jnp.float32).astype(y_ref.dtype)

    # last row through the ref: `cums[-1]` lowers to a dynamic_slice,
    # which the TPU lowering does not implement
    last = cums_ref[0, 0, Q - 1].astype(jnp.float32)  # (1,)
    decay_dt = jnp.exp(last - cums) * dt  # (Q,)
    Bw = B * decay_dt[:, None]
    s_ref[0, 0] = jnp.dot(Bw.T, X, preferred_element_type=jnp.float32).astype(s_ref.dtype)


def ssd_chunk_pallas(C, B, x, cums, dt, *, interpret: bool = False):
    """C/B (BH, nc, Q, N); x (BH, nc, Q, P); cums/dt (BH, nc, Q).

    Returns Y (BH, nc, Q, P) f32 and S (BH, nc, N, P) f32.
    """
    BH, nc, Qn, N = C.shape
    P = x.shape[-1]
    cums2 = cums[..., None]  # (BH, nc, Q, 1) — TPU wants >=2D trailing dims
    dt2 = dt[..., None]
    grid = (BH, nc)
    spec4 = lambda d3, d4: pl.BlockSpec((1, 1, d3, d4), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            spec4(Qn, N),
            spec4(Qn, N),
            spec4(Qn, P),
            spec4(Qn, 1),
            spec4(Qn, 1),
        ],
        out_specs=[spec4(Qn, P), spec4(N, P)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Qn, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(C, B, x, cums2, dt2)
