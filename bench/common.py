"""Small helpers shared by the harness, its entries and its tests."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def add_program_path():
    """The system under test lives in `<checkout>/src`."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_module(path: str, name: str):
    """Import a file of the benchmark by path: its names hold dots and
    dashes, which a plain import cannot take."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def config_files(name: str):
    """(sizes, plain reference module) of configuration `name`."""
    base = os.path.join(BENCH_DIR, "configs", name)
    return load_json(base + ".json"), load_module(base + ".ref.py",
                                                  f"bench_ref_{name}")


def traffic_file(name: str):
    return load_json(os.path.join(BENCH_DIR, "traffic", name + ".json"))


def entry_module(entry: str):
    return load_module(os.path.join(BENCH_DIR, "entries", entry + ".py"),
                       f"bench_entry_{entry}")


def metric_module(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       f"bench_metric_{name.replace('.', '_')}")


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it, the
    rest is folded in, so seeds above 2**32 stay distinct."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    if seed >> 32:
        key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return key


def worst_of(values) -> float:
    """The largest of `values` (0 for none); a value that is not finite
    makes it inf. (`max` alone would drop a nan that comes second.)"""
    out = 0.0
    for v in values:
        v = float(v)
        if not math.isfinite(v):
            return math.inf
        out = max(out, v)
    return out


def annotate(on: bool):
    """`jax.profiler.TraceAnnotation` when tracing, else a no-op."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation
