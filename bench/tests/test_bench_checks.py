"""The correctness check of each entry, run on the CPU at a size a test
run holds: the program passes against the plain reference; the control
(the reference one precision below the configuration's, in the
program's place) and every planted fault of the timed path fail it.

Each run goes through `bench.run.measure` with the look for a chip
skipped, so the whole path of a run is exercised: set-up, window,
release, reference, limits.
"""
import copy
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common  # noqa: E402
from bench.run import measure  # noqa: E402

common.add_program_path()
CELL = {"name": "tiny", "chips": 1}


def _sim():
    cfg, model = common.config_files("emnist-n25")
    cfg = copy.deepcopy(cfg)
    cfg["num_clients"] = 6
    cfg["model"]["dims"] = [784, 16, 47]
    cfg["data"].update(samples_per_client=64, test_samples=100, pool_samples=1000)
    traffic = dict(common.traffic_file("windowed-d8"), windows_per_job=12,
                   eval_every=6, psi=2, lambda_grad=0.5, lambda_tx=0.5)
    limits = common.load_json(os.path.join(
        ROOT, "bench", "limits", "emnist-n25.windowed-d8.json"))
    return cfg, model, traffic, limits


def _train():
    cfg, model = common.config_files("qwen2-1.5b-d2")
    cfg = dict(cfg, hidden_size=192, intermediate_size=384, num_attention_heads=6,
               num_key_value_heads=2, vocab_size=512, torch_dtype="float32",
               program_reduced=True)
    traffic = dict(common.traffic_file("train-2c"), seq=16, batch_per_client=2)
    limits = common.load_json(os.path.join(
        ROOT, "bench", "limits", "qwen2-1.5b-d2.train-2c.json"))
    return cfg, model, traffic, limits


SETUPS = {"simulate": _sim, "train": _train}


def _run(kind, seed=2**31 + 17):
    cfg, model, traffic, limits = SETUPS[kind]()
    e2e = [{"name": "setup_s", "unit": "s"}]
    return measure(CELL, cfg, model, traffic, limits, e2e, [], seed=seed,
                   seconds=0.5, trace=False, require_tpu=False,
                   t_start=time.perf_counter(), cache=False)


@pytest.mark.parametrize("kind", sorted(SETUPS))
def test_bench_program_passes_its_check(kind):
    r = _run(kind)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "compared"
    assert set(r["window"]["gc"]) == {"collections", "seconds", "longest_s"}


def _control(kind, lower):
    cfg, model, traffic, limits = SETUPS[kind]()
    entry = common.entry_module(traffic["entry"])
    import jax

    runner = entry.Cell(cfg, model, traffic, 5, jax.devices()[:1])
    runner.setup()
    runner.window(0.2, common.annotate(False))
    runner.release()
    return (runner.readings(), runner.readings(
        outputs=runner.control_outputs(lower)), limits)


def test_bench_control_fails_the_check_train():
    _, control, limits = _control("train", "fp8")
    assert any(v > limits[k] for k, v in control.items()), control


def test_bench_control_is_seen_by_the_check_simulate():
    """At this size the three-pass bf16 control moves the final params by
    about 5e-5 of their change, where the program reads 0 (f32 on the
    CPU is exact to the reference); at the cell's own size on the chip
    it reads 1.0e-3 to 5.5e-3 against the limit 2e-4 (PERF.md)."""
    program, control, _ = _control("simulate", "high")
    assert program["params_err"] == 0.0, program
    assert control["params_err"] > 1e-5, control


FAULTS = [(kind, f) for kind in sorted(SETUPS)
          for f in ("unchanged", "half_batch", "no_exchange", "altered", "nan")]
FAULTS += [("train", "no_unify"), ("train", "wrong_hub")]


@pytest.mark.parametrize("kind,fault", FAULTS)
def test_bench_fault_fails_the_check(kind, fault):
    entry = common.entry_module("simulate" if kind == "simulate" else "train")
    with entry.FAULTS[fault]():
        r = _run(kind)
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("values,want", [
    ([0.1, float("nan")], float("inf")), ([float("nan"), 0.1], float("inf")),
    ([0.1, float("inf")], float("inf")), ([0.2, 0.1], 0.2), ([], 0.0)])
def test_bench_worst_of_keeps_what_is_not_finite(values, want):
    assert common.worst_of(values) == want
