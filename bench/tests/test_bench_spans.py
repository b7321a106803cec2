"""The readers of the program's span record (`bench/spans.py` and the
per-layer metrics that call it): on hand-made records they pick the
window's own call or jobs, give None when the step or job count does
not match and when the program records no spans; on the CPU, a traced
run of each entry at a test size reports every one of them."""
import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common, spans  # noqa: E402

common.add_program_path()
from repro import obs  # noqa: E402
from repro.obs import Record  # noqa: E402

TRAIN = ("host_ms_per_step.train", "step_p90_ms.train", "entry_s.train",
         "traces.train")
SIM = ("host_entry_ms.sim",)


class _Book:
    """Hand-made records: spans get their ids in the order opened."""

    def __init__(self):
        self.recs, self.ids = [], 0

    def add(self, name, parent, t0, t1, n=None):
        r = Record(self.ids, name, parent, t0, t1, n)
        self.ids += 1
        self.recs.append(r)
        return r

    def close(self, r, t1):
        self.recs[self.recs.index(r)] = r._replace(t1=t1)


def _train_call(book, steps, t, unify_at=5):
    """One `train.run`: a 2-s entry; per step events 4 ms, batch 2 ms,
    dispatch 1 ms (1.5 s at step 0, which traces), a sync of
    0.25 + 0.01 k s, 0.5 ms of the step's own; unification after step
    `unify_at`."""
    root = book.add("repro.train.run", -1, t, None)
    entry = book.add("repro.train.entry", root.id, t, t + 2.0)
    book.add("repro.trace.stack_clients", entry.id, t + 1.0, t + 1.0, 1)
    t += 2.0
    for k in range(steps):
        step = book.add("repro.train.step", root.id, t, None)
        s0 = t
        for name, d in (("events", 0.004), ("batch", 0.002),
                        ("dispatch", 1.5 if k == 0 else 0.001),
                        ("sync", 0.25 + 0.01 * k)):
            r = book.add(f"repro.train.{name}", step.id, t, t + d)
            if name == "dispatch" and k == 0:
                book.add("repro.trace.train_step", r.id, t, t, 1)
            t += d
        if k == unify_at:
            u = book.add("repro.train.unify", step.id, t, t + 0.003)
            book.add("repro.trace.unify_step", u.id, t, t, 1)
            t += 0.003
        t += 0.0005
        book.close(step, t)
        assert t - s0 > 0
    book.close(root, t)
    return t


@pytest.fixture
def train_record(monkeypatch):
    book = _Book()
    t = _train_call(book, 3, 0.0)  # a set-up call
    _train_call(book, 11, t + 1.0)  # the window's
    book.add("repro.train.run", 0, 0.0, 1.0)  # not a root: never the window's
    monkeypatch.setattr(obs, "records", lambda: list(book.recs))


def _read(name, attempted):
    return common.metric_module(name).read(SimpleNamespace(info={"attempted": attempted}))


def test_bench_train_readers_pick_the_window_call(train_record):
    assert _read("host_ms_per_step.train", 11) == pytest.approx(7.0)
    # steps 1..10 last 0.2575 + 0.01 k s; the 90th percentile lies a
    # tenth of the way from the 9th to the 10th
    assert _read("step_p90_ms.train", 11) == pytest.approx(1e3 * (0.2575 + 0.091))
    assert _read("entry_s.train", 11) == pytest.approx(2.0)
    assert _read("traces.train", 11) == 3


def test_bench_slowest_step_names_the_span_that_held_it(train_record):
    out = spans.slowest(11)  # step 10: the longest sync
    assert out["repro.train.step"] == pytest.approx(0.2575 + 0.1)
    assert out["repro.train.sync"] == pytest.approx(0.25 + 0.1)
    assert out["repro.train.events"] == pytest.approx(0.004)


@pytest.mark.parametrize("attempted", [3, 10, 12])
def test_bench_train_readers_need_the_window_step_count(train_record, attempted):
    for name in TRAIN:
        assert _read(name, attempted) is None, name


def test_bench_sim_reader_takes_the_last_jobs(monkeypatch):
    book = _Book()
    t = 0.0
    for prep in (0.05, 0.010, 0.030, 0.020):  # the warm-up job, then 3
        root = book.add("repro.simulate", -1, t, None)
        book.add("repro.simulate.prepare", root.id, t, t + prep)
        book.add("repro.simulate.run", root.id, t + prep, t + prep + 0.1)
        t += prep + 0.5
        book.close(root, t)
    monkeypatch.setattr(obs, "records", lambda: list(book.recs))
    assert _read("host_entry_ms.sim", 3) == pytest.approx(20.0)
    assert _read("host_entry_ms.sim", 2) == pytest.approx(25.0)
    assert _read("host_entry_ms.sim", 5) is None


def test_bench_readers_are_silent_without_the_program_spans(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    for name in TRAIN + SIM:
        assert _read(name, 3) is None, name


@pytest.mark.parametrize("kind", ["simulate", "train"])
def test_bench_traced_run_reports_the_span_metrics(kind):
    from bench.run import measure
    from bench.tests.test_bench_checks import CELL, SETUPS

    cfg, model, traffic, limits = SETUPS[kind]()
    names = SIM if kind == "simulate" else TRAIN
    layer = [{"name": n, "unit": "-"} for n in names]
    r = measure(CELL, cfg, model, traffic, limits, [], layer, seed=2**31 + 5,
                seconds=0.5, trace=True, require_tpu=False,
                t_start=time.perf_counter(), cache=False)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == set(names), r["metrics"]
    if kind == "train":
        assert r["metrics"]["traces.train"]["value"] >= 2  # step and stacking
        assert 0 < r["metrics"]["host_ms_per_step.train"]["value"]
    assert r["metrics"][names[0]]["value"] > 0


def test_bench_last_root_and_within_take_one_call_apart():
    obs.reset()
    try:
        for _ in range(2):
            with obs.span("a"):
                with obs.span("a.b"):
                    obs.count("c", 2)
                with obs.span("a.d"):
                    pass
        obs.count("c")  # outside any root
        recs = obs.records()
    finally:
        obs.reset()
    a = spans.last_root(recs, "a")
    assert a == max((r for r in recs if r.name == "a"), key=lambda r: r.id)
    assert spans.last_root(recs, "a.b") is None  # not a root
    assert [r.name for r in spans.within(recs, a)] == ["a.b", "c", "a.d"]
    assert sum(r.n for r in spans.within(recs, a) if r.n) == 2
