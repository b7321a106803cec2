#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

  python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 2 \
      [--control] [--faults half_batch,no_exchange,altered]

For each seed, in one process: a short run of the cell through its entry
(set-up, window, release) and the numbers its check compares, program
against reference (the lower readings); with `--control`, the reference
one precision below the configuration's in the program's place against
the reference (the upper readings); with `--faults`, the program with
each named fault planted (bench/entries/<entry>.py, FAULTS) against the
reference. Prints one JSON line per reading. The benchmark's own runs
never run this; it needs the chip, as the cell does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the precision one step below the configuration's, per stated precision
LOWER = {"highest": "high", "bfloat16": "fp8"}
CONTROL_SEEDS = 3  # the first seeds also read the control


def readings(cell, traffic, seed, seconds, devices, fault=None,
             control=False):
    from bench import common

    # a fresh copy of the configuration's module per run: its functions
    # are static arguments of the program's jitted calls, so a new copy
    # makes a planted fault be traced instead of taken from the cache
    cfg, model = common.config_files(cell["config"])
    entry = common.entry_module(traffic["entry"])
    plant = entry.FAULTS[fault]() if fault else _null()
    with plant:
        runner = entry.Cell(cfg, model, traffic, seed, devices)
        runner.setup()
        runner.window(seconds, common.annotate(False))
    runner.release()
    out = {"seed": seed, "fault": fault, "program": runner.readings()}
    if control:
        stated = cfg.get("matmul_precision") or cfg.get("torch_dtype")
        lower = LOWER[stated]
        out["control"] = {"precision": lower, **runner.readings(
            outputs=runner.control_outputs(lower))}
    return out


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)

    from bench import common
    from bench.run import check_devices, enable_cache, find_cell

    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    traffic = common.traffic_file(cell["traffic"])
    devices = check_devices(cell["chips"], True)
    common.add_program_path()
    enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    for i, seed in enumerate(seeds):
        r = readings(cell, traffic, seed, args.seconds, devices,
                     control=args.control and i < CONTROL_SEEDS)
        print(json.dumps(r), flush=True)
    for fault in faults:
        for seed in seeds[:3]:
            r = readings(cell, traffic, seed, args.seconds, devices,
                         fault=fault)
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
