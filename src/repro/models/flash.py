"""Pure-JAX FlashAttention-2 with a custom VJP.

The scan-based online-softmax forward alone does NOT save training
memory: scan linearization stores every per-step carry (the (B,H,bq,hd)
accumulator), which for wide-head models is as large as the score matrix
(measured on yi-34b: no temp reduction). The fix is the FlashAttention-2
factorization — save only (out, logsumexp) per q block and *recompute*
the block probabilities in the backward pass:

  fwd:  out_i, lse_i = online-softmax over kv blocks j <= i
  bwd:  D_i = rowsum(dout_i * out_i)
        p_ij = exp(q_i k_j^T / sqrt(d) - lse_i)
        dv_j += p_ij^T dout_i ;  dp = p o (dout_i v_j^T - D_i)
        dq_i += dp k_j ;         dk_j += dp^T q_i

Residual memory: q,k,v + out + (B,H,S) stats — O(S), never O(S^2).
This is exactly what a Pallas/TPU flash kernel does; expressed here in
lax.scan form so XLA's memory analysis of the compiled step sees it.

Layout: q (B,H,S,hd), k/v (B,H,T,hd) (kv heads already repeated or
grouped by the caller). Causal + optional sliding window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask(qi, kj, bq, bk, window):
    iq = qi * bq + jnp.arange(bq)[:, None]
    jk = kj * bk + jnp.arange(bk)[None, :]
    m = jk <= iq
    if window > 0:
        m = m & (jk > iq - window)
    return m


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, block_q: int = 512, block_kv: int = 512,
                    sliding_window: int = 0):
    out, _ = _flash_fwd_impl(q, k, v, block_q, block_kv, sliding_window)
    return out


def _flash_fwd_impl(q, k, v, bq, bk, window):
    B, H, S, hd = q.shape
    T = k.shape[2]
    nq, nk = S // bq, T // bk
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qb = q.reshape(B, H, nq, bq, hd)
    kb = k.reshape(B, H, nk, bk, hd)
    vb = v.reshape(B, H, nk, bk, hd)

    def q_block(qi, q_i):
        def kv_step(carry, inp):
            acc, m, l = carry
            kj, k_j, v_j = inp
            s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j).astype(jnp.float32) * scale
            s = jnp.where(_mask(qi, kj, bq, bk, window)[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_j.dtype), v_j).astype(jnp.float32)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((B, H, bq, hd), jnp.float32)
        m0 = jnp.full((B, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.arange(nk), jnp.swapaxes(kb, 0, 2).swapaxes(1, 2),
             jnp.swapaxes(vb, 0, 2).swapaxes(1, 2)))
        l_safe = jnp.maximum(l, 1e-30)
        out_i = (acc / l_safe[..., None]).astype(q.dtype)
        lse_i = m + jnp.log(l_safe)
        return out_i, lse_i

    outs, lses = jax.lax.map(
        lambda args: q_block(*args),
        (jnp.arange(nq), jnp.swapaxes(qb, 0, 2).swapaxes(1, 2)))
    out = jnp.swapaxes(jnp.swapaxes(outs, 1, 2), 0, 2).reshape(B, H, S, hd)
    lse = jnp.swapaxes(jnp.swapaxes(lses, 1, 2), 0, 2).reshape(B, H, S)
    return out, lse


def _flash_fwd(q, k, v, bq, bk, window):
    out, lse = _flash_fwd_impl(q, k, v, bq, bk, window)
    return out, (q, k, v, out, lse)


def _flash_bwd(bq, bk, window, res, dout):
    q, k, v, out, lse = res
    B, H, S, hd = q.shape
    T = k.shape[2]
    nq, nk = S // bq, T // bk
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # (B,H,S)

    qb = q.reshape(B, H, nq, bq, hd)
    doutb = dout.reshape(B, H, nq, bq, hd)
    lseb = lse.reshape(B, H, nq, bq)
    Db = D.reshape(B, H, nq, bq)
    kb = k.reshape(B, H, nk, bk, hd)
    vb = v.reshape(B, H, nk, bk, hd)

    def q_block(carry, inp):
        dk_acc, dv_acc = carry
        qi, q_i, dout_i, lse_i, D_i = inp

        def kv_step(dq_i, inp2):
            kj, k_j, v_j = inp2
            s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j).astype(jnp.float32) * scale
            s = jnp.where(_mask(qi, kj, bq, bk, window)[None, None], s, NEG_INF)
            p = jnp.exp(s - lse_i[..., None])  # recomputed, never saved
            dp = jnp.einsum("bhqd,bhkd->bhqk", dout_i.astype(jnp.float32),
                            v_j.astype(jnp.float32))
            ds = p * (dp - D_i[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bhqk,bhkd->bhqd", ds, k_j.astype(jnp.float32))
            dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, q_i.astype(jnp.float32))
            dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, dout_i.astype(jnp.float32))
            return dq_i, (dk_j, dv_j)

        dq0 = jnp.zeros((B, H, bq, hd), jnp.float32)
        dq_i, (dks, dvs) = jax.lax.scan(
            kv_step, dq0,
            (jnp.arange(nk), jnp.swapaxes(kb, 0, 2).swapaxes(1, 2),
             jnp.swapaxes(vb, 0, 2).swapaxes(1, 2)))
        # dks: (nk, B, H, bk, hd) contributions from this q block
        return (dk_acc + dks, dv_acc + dvs), dq_i

    dk0 = jnp.zeros((nk, B, H, bk, hd), jnp.float32)
    dv0 = jnp.zeros((nk, B, H, bk, hd), jnp.float32)
    (dk_acc, dv_acc), dqs = jax.lax.scan(
        q_block, (dk0, dv0),
        (jnp.arange(nq), jnp.swapaxes(qb, 0, 2).swapaxes(1, 2),
         jnp.swapaxes(doutb, 0, 2).swapaxes(1, 2),
         jnp.swapaxes(lseb, 0, 2).swapaxes(1, 2),
         jnp.swapaxes(Db, 0, 2).swapaxes(1, 2)))
    dq = jnp.swapaxes(jnp.swapaxes(dqs, 1, 2), 0, 2).reshape(B, H, S, hd)
    dk = jnp.swapaxes(jnp.swapaxes(dk_acc, 1, 2), 0, 2).reshape(B, H, T, hd)
    dv = jnp.swapaxes(jnp.swapaxes(dv_acc, 1, 2), 0, 2).reshape(B, H, T, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
