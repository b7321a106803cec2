#!/usr/bin/env python3
"""Kernel-alone sweep of the Pallas `gossip_mix` on a TPU: body x N x block.

  python scripts/mix_blocks.py [--out chiprun_out/mix_blocks.jsonl]

For each client count N in {2, 4, 8, 16}, a plane of the trainer's size
(2 x 326,970,880 f32 values, 2.6 GB, split over N rows; a ragged last
block at every N) is mixed by the VPU body and by the MXU body at the
VMEM-budget block (`mix_block_d`), half of it and twice it where that
fits the chip's scoped VMEM. At N = 2 the former (2, 512) MXU tiling is
timed too. Each row gives the median of `ITERS` calls on the host clock
(each call ends on `block_until_ready`), the bandwidth of its bytes (the
plane read once, written once) and its share of the chip's HBM peak, and
the worst gap to the f32 einsum reference at the budget block. One JSON
object per line on stdout and in `--out`. Needs a TPU.

This is the measurement behind `gossip.MIX_VPU_MAX_N` and
`gossip.MIX_BLOCK_BYTES`.
"""
from __future__ import annotations

import argparse
import json
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
from repro.kernels.gossip.ref import gossip_mix_ref  # noqa: E402

TOTAL = 2 * 326_970_880  # the trainer's two f32 client planes
ITERS = 5
BODIES = {"vpu": gossip._mix_vpu_kernel, "mxu": gossip._mix_mxu_kernel}
# (kernel, q, deltas, block_d, interpret): one program per body and block
MIX = jax.jit(gossip._mix_call, static_argnums=(0, 3, 4))
REF = jax.jit(gossip_mix_ref)


def time_call(fn, *args) -> float:
    """Median seconds of one call, after a warm call."""
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


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

    for n in (2, 4, 8, 16):
        k = TOTAL // n + (0 if n == 2 else 77)
        key = jax.random.PRNGKey(n)
        q = jax.nn.softmax(jax.random.normal(key, (n, n)), axis=1)
        d = jax.random.normal(jax.random.fold_in(key, 1), (n, k), jnp.float32)
        ref = REF(q, d)
        nbytes = 2 * n * k * 4
        budget = gossip.mix_block_d(n, k, jnp.float32)
        runs = [(b, blk) for b in BODIES for blk in (budget // 2, budget, 2 * budget)]
        if n == 2:
            runs.append(("mxu", 512))
        for body, blk in runs:
            call = (BODIES[body], q, d, blk, False)
            row = {"n": n, "k": k, "body": body, "block_d": blk,
                   "steps": -(-k // blk)}
            try:
                s = time_call(MIX, *call)
            except Exception as e:  # a block past the scoped VMEM
                emit({**row, "error": f"{type(e).__name__}: {str(e)[-200:]}"})
                continue
            row.update(ms=1e3 * s, gb_per_s=nbytes / s / 1e9,
                       hbm_share=nbytes / s / hbm)
            if blk == budget:
                out = MIX(*call)
                row["max_rel_gap"] = float(jnp.max(jnp.abs(out - ref)) /
                                           jnp.max(jnp.abs(ref)))
                del out
            emit(row)
        del d, ref
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
