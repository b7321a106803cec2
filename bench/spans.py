#!/usr/bin/env python3
"""Readings of the program's own spans and counters (`repro.obs`).

The per-layer readers `bench/metrics/*.py` of the host loop, the call
entry, the trace count and the simulator's host entry call the
functions here after the window, in the process that ran it. Each
finds the window's own call(s) among the records: the last
`repro.train.run` root, or the last `attempted` `repro.simulate` roots.
A program without `repro.obs`, or a record that does not hold the
window's steps or jobs, gives None.

Run as a script, it runs the cell as `bench/run.py --trace 0` does (the
same timed window and check) and then reads the same metrics, with the
profiler off, the spans of the window's slowest step or job (what held
a stall), and what a span and a count cost with the profiler off and
on:

  python bench/spans.py --workload <cell> --seed <n> --seconds <s>

It prints `bench/run.py`'s result line with `untraced` (the readings),
`slowest`, `records` and `cost` added. The profiler's Python tracer
slows the host in a `--trace 1` run of `bench/run.py`, so its readings
of host time are higher than these.
"""
from __future__ import annotations

import statistics

TRAIN_ROOT = "repro.train.run"
TRAIN_STEP = "repro.train.step"
# the host's own work in a step: everything but waiting for the device
# (`repro.train.sync`) and the periodic unification and checkpoint
TRAIN_HOST = ("repro.train.events", "repro.train.batch", "repro.train.dispatch")
SIM_ROOT = "repro.simulate"
TRACE_COUNT = "repro.trace."
COST_SPAN = "repro.obs.cost"
COST_N = 20000  # spans (and counts) in a row per cost reading


def _obs():
    try:
        from repro import obs
    except ImportError:  # a program that records no spans
        return None
    return obs


def last_root(recs, name: str):
    """The latest-opened root span called `name`, or None."""
    found = [r for r in recs if r.name == name and r.parent == -1 and r.n is None]
    return max(found, key=lambda r: r.id, default=None)


def within(recs, root):
    """Every record under `root` (at any depth), in the order opened.
    A record opens after its parent, so one pass in id order finds them."""
    inside, out = {root.id}, []
    for r in sorted(recs, key=lambda r: r.id):
        if r.parent in inside:
            inside.add(r.id)
            out.append(r)
    return out


def train_call(attempted: int):
    """(obs, root, records under it, its step spans in order) of the last
    `repro.train.run`, or None unless it ran `attempted` steps."""
    obs = _obs()
    if obs is None:
        return None
    recs = obs.records()
    root = last_root(recs, TRAIN_ROOT)
    if root is None:
        return None
    inside = within(recs, root)
    steps = [r for r in inside if r.name == TRAIN_STEP]
    if len(steps) != attempted:
        return None
    return obs, root, inside, steps


def host_ms_per_step(attempted: int):
    """Median over steps 1.. of the host's own work in a step: the self
    time of its events, batch and dispatch spans, in ms."""
    call = train_call(attempted)
    if call is None or attempted < 2:
        return None
    obs, _, inside, steps = call
    per = [sum(obs.self_time(c, inside) for c in obs.children(inside, s)
               if c.name in TRAIN_HOST) for s in steps[1:]]
    return 1e3 * statistics.median(per)


def step_p90_ms(attempted: int):
    """90th percentile of the step spans' durations over steps 1.., ms
    (step 0 holds the step's trace and compile)."""
    call = train_call(attempted)
    if call is None or attempted < 3:
        return None
    durs = [s.seconds for s in call[3][1:]]
    return 1e3 * statistics.quantiles(durs, n=10, method="inclusive")[-1]


def entry_s(attempted: int):
    """Seconds from the call's start to its first step."""
    call = train_call(attempted)
    if call is None:
        return None
    obs, root, inside, _ = call
    entry = [r for r in obs.children(inside, root) if r.name == "repro.train.entry"]
    return entry[0].seconds if entry else None


def traces(attempted: int):
    """Jitted functions traced during the call (`repro.trace.*` counts)."""
    call = train_call(attempted)
    if call is None:
        return None
    return sum(r.n for r in call[2] if r.n is not None and r.name.startswith(TRACE_COUNT))


def sim_host_entry_ms(attempted: int):
    """Median over the window's jobs of `simulate`'s host entry (the
    `repro.simulate.prepare` span: workload, context checks, state
    init), in ms."""
    obs = _obs()
    if obs is None or attempted < 1:
        return None
    recs = obs.records()
    roots = sorted((r for r in recs if r.name == SIM_ROOT and r.parent == -1
                    and r.n is None), key=lambda r: r.id)[-attempted:]
    if len(roots) != attempted:
        return None
    per = [c.seconds for r in roots for c in obs.children(recs, r)
           if c.name == "repro.simulate.prepare"]
    if len(per) != attempted:
        return None
    return 1e3 * statistics.median(per)


def slowest(attempted: int):
    """Where the window's slowest step (steps 1..) or job spent its
    time: {span: seconds} of it and of its children, to name what held
    a stall."""
    obs = _obs()
    call = train_call(attempted)
    if call is not None and attempted >= 2:
        recs, top = call[2], max(call[3][1:], key=lambda r: r.seconds)
    else:
        recs = obs.records() if obs is not None else []
        roots = [r for r in recs if r.name == SIM_ROOT and r.parent == -1]
        if not roots:
            return None
        top = max(sorted(roots, key=lambda r: r.id)[-attempted:], key=lambda r: r.seconds)
    out = {top.name: top.seconds}
    for c in obs.children(recs, top):
        if c.n is None:
            out[c.name] = out.get(c.name, 0.0) + c.seconds
    return out


def cost_us(obs, n: int = COST_N) -> dict:
    """Microseconds per span and per count, and per empty `with` block
    (the loop's own cost), each over `n` in a row."""
    import contextlib
    import time

    def per(block) -> float:
        t = time.perf_counter()
        block()
        return 1e6 * (time.perf_counter() - t) / n

    def spans():
        for _ in range(n):
            with obs.span(COST_SPAN):
                pass

    def counts():
        for _ in range(n):
            obs.count(COST_SPAN)

    def empty():
        for _ in range(n):
            with contextlib.nullcontext():
                pass

    return {"span_us": per(spans), "count_us": per(counts), "empty_with_us": per(empty)}


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys
    import tempfile
    from types import SimpleNamespace

    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import common
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = bench_run.CACHE_DIR

    bench = common.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = bench_run.find_cell(bench, args.workload)
    cfg, model = common.config_files(cell["config"])
    traffic = common.traffic_file(cell["traffic"])
    limits = common.load_json(os.path.join(here, "limits", cell["name"] + ".json"))
    e2e, layer = bench_run.metrics_of(bench, cell["name"])
    result = bench_run.measure(cell, cfg, model, traffic, limits, e2e, layer,
                               seed=args.seed, seconds=args.seconds, trace=False)

    import jax
    from repro import obs

    # the cell's readers of the program's own record, which still holds
    # the window's call(s): the check after it runs the plain reference
    info = result["window"]
    ctx = SimpleNamespace(info=info)
    result["untraced"] = {m["name"]: common.metric_module(m["name"]).read(ctx)
                          for m in layer
                          if m["source"] in ("program_span", "program_counter")}
    result["records"] = len(obs.records())
    result["slowest"] = slowest(info.get("attempted", 1))
    obs.reset()
    cost = {"profiler_off": cost_us(obs)}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            cost["profiler_on"] = cost_us(obs)
        finally:
            jax.profiler.stop_trace()
    obs.reset()
    result["cost"] = cost
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
