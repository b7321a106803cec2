#!/usr/bin/env python3
"""Kernel-alone sweep of the Pallas `gossip_mix` on a TPU: body x N x block.

  python scripts/mix_blocks.py [--out chiprun_out/mix_blocks.jsonl]

The trainer's own update as it mixes it: the 14 bf16 leaves of
qwen2-1.5b at depth 2, stacked over N in {2, 4, 8} clients (1.3 GB at
N = 2), each mixed in its own shape by the VPU body and by the MXU body
at the VMEM-budget block (`mix_block`), half of it and twice it where
that fits the chip's scoped VMEM. A row per leaf (one call each, so the
small leaves' rows are mostly the call's own dispatch and wait), their
``sum``, and a ``set`` row: all 14 mixed in one program, as the train
step mixes them.

Each row gives the median of `ITERS` calls on the host clock (each call
ends on `block_until_ready`), the bandwidth of its bytes (the operand
read once, written once) and its share of the chip's HBM peak. At the
budget block a leaf row gives the count of elements whose bits differ
from the leaf raveled into an f32 (N, K) row, mixed and rounded back
(the former plane formulation; 0 is bit-equal), where that row fits in
2 GB. One JSON object per line on stdout and in `--out`. Needs a TPU.

This is the measurement behind `gossip.MIX_BLOCK_BYTES`, and what
`gossip.MIX_VPU_MAX_N`'s comment cites for bf16 leaves.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import peaks  # noqa: E402
from repro.kernels.gossip import gossip  # noqa: E402

ITERS = 5
BODIES = {"vpu": gossip._mix_vpu_kernel, "mxu": gossip._mix_mxu_kernel}
SCALES = (0.5, 1, 2)  # the block at these multiples of MIX_BLOCK_BYTES
# (kernel, q, deltas, block, interpret): one program per body and block
MIX = jax.jit(gossip._mix_call, static_argnums=(0, 3, 4))
# (kernel, q, leaves, blocks): every leaf of the update in one program
MIX_SET = jax.jit(
    lambda kernel, q, ds, blocks: tuple(
        gossip._mix_call(kernel, q, d, b, False) for d, b in zip(ds, blocks)),
    static_argnums=(0, 3))


def time_call(fn, *args) -> float:
    """Median seconds of one call, after a warm call."""
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def block_at(shape, dtype, scale):
    """`gossip.mix_block` with a budget of `scale` x MIX_BLOCK_BYTES."""
    return gossip.mix_block(shape, dtype, int(gossip.MIX_BLOCK_BYTES * scale))


def steps_of(shape, block) -> int:
    return math.prod(-(-s // (b or 1)) for s, b in zip(shape[1:], block[1:]))


def train_leaves():
    """(name, per-client shape) of the trainer's update: qwen2-1.5b at its
    published width, 2 layers, as `launch.train` builds it."""
    from repro.configs.base import get_config
    from repro.launch.steps import depth_config, param_specs_abstract

    params = param_specs_abstract(depth_config(get_config("qwen2-1.5b"), 2))
    return [(jax.tree_util.keystr(path), tuple(leaf.shape)) for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]]


def bits_off_the_row(kernel, q, leaf, out) -> int:
    """Elements of `out` whose bits differ from `leaf` raveled into an f32
    (N, K) row, mixed by `kernel` and rounded back to the leaf's dtype."""
    n = leaf.shape[0]
    row = leaf.reshape(n, -1).astype(jnp.float32)
    ref = MIX(kernel, q, row, gossip.mix_block(row.shape, row.dtype), False)
    ref = ref.astype(leaf.dtype).reshape(leaf.shape)
    return int(jnp.sum(jax.lax.bitcast_convert_type(out, jnp.uint16) !=
                       jax.lax.bitcast_convert_type(ref, jnp.uint16)))


def sweep_leaves(emit, hbm):
    leaves = train_leaves()
    for n in (2, 4, 8):
        key = jax.random.PRNGKey(100 + n)
        q = jax.nn.softmax(jax.random.normal(key, (n, n)), axis=1)
        ds = [jax.random.normal(jax.random.fold_in(key, i), (n,) + shape,
                                jnp.bfloat16)
              for i, (_, shape) in enumerate(leaves)]
        for body in BODIES:
            for x in SCALES:
                total_s, total_bytes, total_steps, failed = 0.0, 0, 0, False
                for (name, _), d in zip(leaves, ds):
                    blk = block_at(d.shape, d.dtype, x)
                    call = (BODIES[body], q, d, blk, False)
                    nbytes = 2 * d.size * d.dtype.itemsize
                    row = {"set": "leaves", "n": n, "leaf": name,
                           "shape": d.shape, "body": body, "scale": x,
                           "block": blk, "steps": steps_of(d.shape, blk)}
                    try:
                        s = time_call(MIX, *call)
                    except Exception as e:  # a block past the scoped VMEM
                        emit({**row, "error":
                              f"{type(e).__name__}: {str(e)[-200:]}"})
                        failed = True
                        continue
                    row.update(ms=1e3 * s, gb_per_s=nbytes / s / 1e9,
                               hbm_share=nbytes / s / hbm)
                    if x == 1 and 4 * d.size <= 2 << 30:
                        out = MIX(*call)
                        row["bits_off_row"] = bits_off_the_row(
                            BODIES[body], q, d, out)
                        del out
                    emit(row)
                    total_s += s
                    total_bytes += nbytes
                    total_steps += row["steps"]
                if failed:
                    continue
                row = {"set": "leaves", "n": n, "body": body, "scale": x,
                       "steps": total_steps}
                emit({**row, "leaf": "sum", "ms": 1e3 * total_s,
                      "gb_per_s": total_bytes / total_s / 1e9,
                      "hbm_share": total_bytes / total_s / hbm})
                blocks = tuple(block_at(d.shape, d.dtype, x) for d in ds)
                s = time_call(MIX_SET, BODIES[body], q, tuple(ds), blocks)
                emit({**row, "leaf": "set", "ms": 1e3 * s,
                      "gb_per_s": total_bytes / s / 1e9,
                      "hbm_share": total_bytes / s / hbm})
        del ds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    hbm = peaks.peaks_for(dev.device_kind).hbm_bytes_per_s
    rows = []

    def emit(row):
        row["device"] = dev.device_kind
        print(json.dumps(row), flush=True)
        rows.append(row)

    sweep_leaves(emit, hbm)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
