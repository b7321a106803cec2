"""Work counts: the operations and bytes that the weights and events of a
run make necessary, not the shapes a kernel is handed.

A gossip contraction ``out = sum_j W_j^T @ payload_j`` must read each
payload row that carries at least one nonzero weight, once, and write
each receiving row that gets at least one nonzero weight, once, in f32.
Padded rows, gathered copies and zero-weight rows are not work. So an
implementation that skips empty buckets or silent senders still reads
at most 100% of its roofline.

Model FLOPs follow the usual analytic count (as `launch/roofline.py`'s
`model_flops_analytic` has it): 6 FLOPs per parameter per trained
sample or token (forward 2, backward 4), 2 per evaluated sample.
Recomputation does not count.
"""
from __future__ import annotations

import numpy as np

F32 = 4


def contraction_rows(w) -> tuple:
    """(rows read, rows written) of one contraction or a stack of them.

    `w` is (..., senders, receivers): a drain's (J, N, N) weight stack,
    a mix's (N, N) matrix, or any leading batch of those. A payload row
    is one (stack entry, sender) pair with a nonzero weight; a receiving
    row is one receiver with a nonzero weight in any entry of its
    contraction."""
    nz = np.asarray(w) != 0
    if nz.ndim == 2:  # one mix: a stack of one
        nz = nz[None]
    nz = nz.reshape((-1,) + nz.shape[-3:])  # (contractions, J, N, N)
    read = int(nz.any(axis=-1).sum())
    written = int(nz.any(axis=(-3, -2)).sum())
    return read, written


def contraction_bytes(rows_read: int, rows_written: int, k: int,
                      itemsize: int = F32) -> float:
    return float(rows_read + rows_written) * k * itemsize


def contraction_flops(nonzero_weights: int, k: int) -> float:
    return 2.0 * nonzero_weights * k


def least_time_s(flops: float, nbytes: float, peak_flops: float,
                 peak_bytes_per_s: float) -> float:
    """Roofline: the larger of compute and memory time."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)


def train_flops(params: int, samples: float) -> float:
    return 6.0 * params * samples


def eval_flops(params: int, samples: float) -> float:
    return 2.0 * params * samples


def qwen2_params(cfg: dict) -> int:
    """Parameters of one Qwen2 client as the configuration file sizes it:
    tied embedding counted once, q/k/v biases, two RMSNorm scales per
    layer and the final norm."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = d // hq
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + (hq + 2 * hkv) * hd
    mlp = 3 * d * ff
    layer = attn + mlp + 2 * d
    head = 0 if cfg["tie_word_embeddings"] else v * d
    return int(v * d + head + cfg["num_hidden_layers"] * layer + d)
