"""`repro.obs`: the program's span and counter record, the trace
counters at the top of jitted bodies, the profiler's view of the spans,
and the device scopes in the lowered simulate and train programs."""
import collections
import glob
import importlib
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def _named(recs, name):
    return [r for r in recs if r.name == name]


def _under(recs, root):
    """The records whose chain of parents reaches `root`."""
    parent = {r.id: r.parent for r in recs}

    def reaches(p):
        while p != -1 and p != root.id:
            p = parent.get(p, -1)
        return p == root.id

    return [r for r in recs if reaches(r.parent)]


def test_spans_nest_and_name_their_parents():
    with obs.span("a"):
        with obs.span("a.b"):
            obs.count("c", 2)
        with obs.span("a.d"):
            pass
    with obs.span("e"):
        pass
    recs = obs.records()
    (a,), (b,), (c,), (d,), (e,) = (_named(recs, k) for k in "a a.b c a.d e".split())
    assert a.parent == e.parent == -1
    assert b.parent == d.parent == a.id and c.parent == b.id
    assert a.id < b.id < c.id < d.id < e.id
    assert (a.n, c.n) == (None, 2) and c.t0 == c.t1
    assert a.t0 <= b.t0 <= b.t1 <= d.t0 <= d.t1 <= a.t1 <= e.t0
    assert [r.name for r in obs.children(recs, a)] == ["a.b", "a.d"]
    assert [r.name for r in obs.children(recs, b)] == ["c"]


def test_span_records_on_exception_and_pops():
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError
    with obs.span("after"):
        pass
    assert [r.parent for r in obs.records()] == [-1, -1]


def test_self_time_subtracts_child_spans_only():
    with obs.span("outer"):
        time.sleep(0.01)
        with obs.span("inner"):
            time.sleep(0.02)
        obs.count("n")
    recs = obs.records()
    (outer,), (inner,) = _named(recs, "outer"), _named(recs, "inner")
    assert obs.self_time(outer, recs) == pytest.approx(outer.seconds - inner.seconds)
    assert obs.self_time(inner, recs) == inner.seconds >= 0.02
    assert 0.01 <= obs.self_time(outer, recs) < outer.seconds


def test_record_is_bounded(monkeypatch):
    monkeypatch.setattr(obs, "_records", collections.deque(maxlen=5))
    for i in range(8):
        obs.count(f"k{i}")
    assert [r.name for r in obs.records()] == [f"k{i}" for i in range(3, 8)]
    assert obs.MAXLEN >= 1 << 16


def test_counts_are_scoped_by_their_root():
    for call in range(2):
        with obs.span("root"):
            for _ in range(call + 2):
                with obs.span("root.step"):
                    obs.count("repro.trace.f")
    obs.count("repro.trace.f")  # outside any root
    recs = obs.records()
    last = max(_named(recs, "root"), key=lambda r: r.id)
    assert sum(c.n for s in obs.children(recs, last)
               for c in obs.children(recs, s) if c.n) == 3
    assert sum(r.n for r in recs if r.n) == 2 + 3 + 1


def test_trace_counter_counts_traces_not_calls():
    from repro.launch.steps import make_unify_step

    unify = jax.jit(make_unify_step(None, None))
    params = {"w": jnp.arange(6.0).reshape(3, 2)}

    def traces():
        return sum(r.n for r in _named(obs.records(), "repro.trace.unify_step"))

    unify(params, jnp.int32(1))
    unify(params, jnp.int32(2))
    assert traces() == 1
    out = unify({"w": jnp.arange(8.0).reshape(4, 2)}, jnp.int32(1))  # new shape
    assert traces() == 2
    np.testing.assert_array_equal(out["w"], np.tile([2.0, 3.0], (4, 1)))


def test_profiler_shows_each_span_on_the_host_timeline(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("repro.test.outer"):
            time.sleep(0.02)
            with obs.span("repro.test.inner"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro.test."):
                        events[ev.name] = (ev.end_ns - ev.start_ns) * 1e-9
    for r in obs.records():
        assert events[r.name] == pytest.approx(r.seconds, abs=1e-3), r.name


def _has_scope(text: str, scope: str) -> bool:
    """Some op of the lowered module (StableHLO with locations) sits
    under the named scope `scope`."""
    return re.search(r'loc\("(?:[^"]*/)?' + re.escape(scope) + "/", text) is not None


class _Lowered(Exception):
    pass


def _lowered_run(monkeypatch, call) -> str:
    """The StableHLO (with locations) of the `_run` program that `call`
    would run, lowered in its place."""
    sim_mod = importlib.import_module("repro.api.simulate")
    real = sim_mod._run
    texts = []

    def lower_only(*args):
        texts.append(real.lower(*args).as_text(debug_info=True))
        raise _Lowered

    monkeypatch.setattr(sim_mod, "_run", lower_only)
    with pytest.raises(_Lowered):
        call()
    return texts[0]


@pytest.fixture(scope="module")
def small_task():
    from repro.data.synthetic import federated_classification, make_mlp

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    train, test = federated_classification(k1, 4, input_dim=6, num_classes=3,
                                           per_client=16)
    params0, _, loss, acc = make_mlp(k2, 6, (8,), 3)
    return train, test, params0, loss, acc


def _cfg():
    from repro.core.protocol import DracoConfig

    return DracoConfig(num_clients=4, lr=0.1, local_batches=1, batch_size=4,
                       lambda_grad=0.8, lambda_tx=0.8, unify_period=3, psi=2,
                       topology="complete", max_delay_windows=3, channel=None)


def test_simulate_program_carries_the_window_scopes(monkeypatch, small_task):
    from repro.api import simulate

    train, test, params0, loss, acc = small_task
    text = _lowered_run(monkeypatch, lambda: simulate(
        "draco", _cfg(), params0, loss, train, num_steps=4,
        key=jax.random.PRNGKey(1), eval_every=2, eval_fn=acc, eval_data=test))
    for scope in ("draco.drain", "draco.local_step", "draco.tx",
                  "draco.enqueue", "draco.unify", "draco.eval"):
        assert _has_scope(text, scope), scope
    # the lowering stopped the call inside its run span, which still closed
    recs = obs.records()
    (root,) = _named(recs, "repro.simulate")
    assert root.parent == -1
    assert [r.name for r in obs.children(recs, root)] == [
        "repro.simulate.prepare", "repro.simulate.run"]


def test_event_program_carries_the_branch_scopes(monkeypatch, small_task):
    from repro.events import simulate_events

    train, _, params0, loss, _ = small_task
    text = _lowered_run(monkeypatch, lambda: simulate_events(
        "draco-event", _cfg(), params0, loss, train, horizon=4.0,
        key=jax.random.PRNGKey(1)))
    for scope in ("event.drain", "event.grad", "event.tx", "event.unify"):
        assert _has_scope(text, scope), scope


def test_train_step_carries_its_scopes():
    from repro.configs.base import ShapeConfig, get_reduced
    from repro.launch import steps
    from repro.launch.train import client_mesh

    cfg = get_reduced("qwen2-1.5b")
    n = 2
    mesh = client_mesh(n)
    params = steps.stack_clients_abstract(steps.param_specs_abstract(cfg), n)
    batch = steps.train_batch_specs(cfg, ShapeConfig("train", 8, n, "train"), n)
    q = jax.ShapeDtypeStruct((n, n), jnp.float32)
    text = jax.jit(steps.make_train_step(cfg, mesh)).lower(
        params, batch, q).as_text(debug_info=True)
    for scope in ("train.grad", "train.mix", "train.apply"):
        assert _has_scope(text, scope), scope
    text = jax.jit(steps.make_unify_step(cfg, mesh)).lower(
        params, jax.ShapeDtypeStruct((), jnp.int32)).as_text(debug_info=True)
    assert _has_scope(text, "train.unify")


def test_train_run_records_its_phases():
    from repro.launch import train

    args = train.parse_args([
        "--arch", "qwen2-1.5b", "--reduced", "--steps", "4", "--clients", "2",
        "--batch-per-client", "1", "--seq", "8", "--log-every", "2",
        "--unify-every", "3"])
    train.run(args)
    recs = obs.records()
    (root,) = _named(recs, "repro.train.run")
    assert root.parent == -1
    top = [r.name for r in obs.children(recs, root)]
    assert top == ["repro.train.entry"] + ["repro.train.step"] * 4
    steps = [r for r in obs.children(recs, root) if r.name == "repro.train.step"]
    phases = [[c.name.rsplit(".", 1)[1] for c in obs.children(recs, s)] for s in steps]
    want = ["events", "batch", "dispatch", "sync"]
    assert phases == [want, want, want + ["unify"], want]
    counts = sorted(r.name for r in _under(recs, root) if r.n)
    assert counts == ["repro.trace.stack_clients", "repro.trace.train_step",
                      "repro.trace.unify_step"]
