#!/usr/bin/env python3
"""The benchmark's one command.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds everything by name: the cell in BENCHMARK.json, its configuration
in bench/configs/<config>.json with the plain reference beside it
(<config>.ref.py), its traffic mix in bench/traffic/<traffic>.json, the
code that drives the traffic's public entry in bench/entries/<entry>.py,
the limits of its correctness check in bench/limits/<cell>.json, and
each per-layer metric's reader in bench/metrics/<metric>.py.

A run loads, warms every program the cell uses (set-up), measures for
`--seconds`, then checks what the window produced against the plain
reference and prints one JSON line as the last line of stdout. With
`--trace 1` the window is traced and the line carries the per-layer
metrics instead of the end-to-end ones. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class NoChip(RuntimeError):
    pass


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str):
    """(end-to-end, per-layer) metric entries that this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def check_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def enable_cache():
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Count of XLA compiles, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class GcClock:
    """Collections of Python's cyclic collector and the seconds they
    took, while the block runs: a host stall in the window that is not
    the collector shows here as none."""

    def __init__(self):
        self.n, self.s, self.longest, self._t = 0, 0.0, 0.0, None

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.n, self.s, self.longest = self.n + 1, self.s + d, max(self.longest, d)
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)
        return False

    def summary(self) -> dict:
        return {"collections": self.n, "seconds": self.s, "longest_s": self.longest}


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def measure(cell: dict, cfg: dict, model, traffic: dict, limits: dict,
            e2e_metrics, layer_metrics, *, seed: int, seconds: float,
            trace: bool, require_tpu: bool = True, t_start: float = None,
            cache: bool = True):
    """One run of one cell. Returns the result dict (the last line)."""
    import jax

    from bench import common
    from bench import trace as trace_lib
    from bench.peaks import peaks_for

    t_start = T_START if t_start is None else t_start
    devices = check_devices(cell["chips"], require_tpu)
    common.add_program_path()
    if cache:
        enable_cache()
    compiles = CompileCounter()
    entry = common.entry_module(traffic["entry"])
    runner = entry.Cell(cfg, model, traffic, seed, devices)
    runner.setup()
    setup_s = time.perf_counter() - t_start
    n_setup = compiles.n

    span = common.annotate(trace)
    captured = []
    with GcClock() as collector:
        if trace:
            with trace_lib.capture() as captured:
                with span(trace_lib.WINDOW_SPAN):
                    e2e, info = runner.window(seconds, span)
        else:
            e2e, info = runner.window(seconds, span)
    info["gc"] = collector.summary()
    in_window = compiles.n - n_setup
    memory = peak_bytes(devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}

    metrics = {}
    breakdown = None
    if trace:
        summary = trace_lib.load(captured[0])
        trace_lib.cleanup(captured)
        busy = trace_lib.busy_s(summary)
        device.update(busy_s=busy, window_s=summary.window_s)
        ctx = SimpleNamespace(
            summary=summary, counts=runner.counts(), info=info, e2e=e2e,
            busy_s=busy, window_s=summary.window_s, chips=len(devices),
            peaks=peaks_for(dev.device_kind) if require_tpu else None)
        for m in layer_metrics:
            value = common.metric_module(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = trace_lib.breakdown(
            summary, program=trace_lib.program_files(os.path.join(ROOT, "src")))
    else:
        for m in e2e_metrics:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    runner.release()
    readings = runner.readings()
    off = [k for k, v in readings.items()
           if not (math.isfinite(v) and v <= limits[k])]
    # a reading that is not finite is written as text ("inf", "nan"):
    # strict JSON has no such number
    compared = {k: {"value": v if math.isfinite(v) else repr(float(v)),
                    "limit": limits[k]} for k, v in readings.items()}
    result = {"correct": not off, "attempted": info.get("attempted", 1),
              "failed": len(off), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["setup"] = {"setup_s": setup_s, "compiles_in_setup": n_setup,
                       "compiles_in_window": in_window}
    result["window"] = info
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the program takes its compile cache from this variable; it has to
    # be the benchmark's fixed directory inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR

    from bench import common

    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    cfg, model = common.config_files(cell["config"])
    traffic = common.traffic_file(cell["traffic"])
    limits = common.load_json(os.path.join(BENCH_DIR, "limits",
                                           cell["name"] + ".json"))
    e2e, layer = metrics_of(bench, cell["name"])
    try:
        result = measure(cell, cfg, model, traffic, limits, e2e, layer,
                         seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; this benchmark measures the chip only",
              file=sys.stderr)
        return 1
    for k, c in result["compared"].items():
        print(f"compared {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
