"""The reduction from a profiler trace to metrics, on a small trace that
was recorded on a TPU v5e (one 10-window simulate job of the emnist-n25
deployment, gzip of the `.xplane.pb`), and on hand-made intervals."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "sim_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


def test_bench_trace_recorded_device_and_spans(recorded):
    assert list(recorded.ops) == [0]
    assert len(recorded.ops[0]) > 100
    names = [s.name for s in recorded.spans]
    assert trace.WINDOW_SPAN in names and "bench.job" in names
    assert recorded.window_s == pytest.approx(0.052288408, rel=1e-6)


def test_bench_trace_recorded_busy_and_kernels(recorded):
    busy = trace.busy_s(recorded)
    assert busy == pytest.approx(0.020876066, rel=1e-6)
    assert 0 < busy < recorded.window_s
    drain = trace.kernel_s(recorded, trace.pallas_call(3))
    assert drain == pytest.approx(0.003365809, rel=1e-6)
    assert trace.kernel_s(recorded, trace.pallas_call(2)) == 0.0
    idle = trace.idle_percent(busy, recorded.window_s)
    assert idle == pytest.approx(100 * (1 - 0.020876066 / 0.052288408), rel=1e-6)


def test_bench_trace_recorded_breakdown(recorded):
    b = trace.breakdown(recorded)
    ops = dict(b["device_ops"])
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert "pallas_kernel" in ops and "while" not in ops
    assert ops["pallas_kernel"] == pytest.approx(0.003365809, rel=1e-6)
    gaps = dict(b["idle_gaps"])
    busy = trace.busy_s(recorded)
    assert sum(gaps.values()) == pytest.approx(recorded.window_s - busy, rel=1e-6)
    assert "bench.job" in gaps


def _op(name, a, b):
    return trace.Op(name, a, b)


def test_bench_trace_intervals_by_hand():
    ops = {0: [_op("%fusion.1 = f32[2] fusion()", 0.0, 1.0),
               _op("%fusion.2 = f32[2] fusion()", 0.5, 1.5),
               _op("%all-reduce.3 = f32[2] all-reduce()", 1.2, 2.0),
               _op("%fusion.4 = f32[2] fusion()", 3.0, 4.0)],
           1: [_op("%fusion.1 = f32[2] fusion()", 0.0, 2.0)]}
    spans = [trace.Span(trace.WINDOW_SPAN, 0.0, 5.0),
             trace.Span("bench.job", 0.0, 2.4), trace.Span("bench.host", 2.4, 5.0)]
    s = trace.summarize(ops, spans)
    # device 0 busy 0..2 and 3..4 = 3 s, device 1 busy 2 s: mean 2.5 s
    assert trace.busy_s(s) == pytest.approx(2.5)
    gaps = dict(trace.breakdown(s)["idle_gaps"])
    # device 0 is idle over 2..3 and 4..5, both inside bench.host
    assert gaps == {"bench.host": pytest.approx(2.0)}


def test_bench_trace_recorded_gaps_name_program_frames(recorded):
    assert recorded.frames and all(not f.name.startswith("$")
                                   for f in recorded.frames)
    b = trace.breakdown(recorded, program=frozenset({"simulate.py"}))
    gaps = dict(b["idle_gaps"])
    assert any(k.startswith("bench.job > simulate.py:") for k in gaps), gaps
    busy = trace.busy_s(recorded)
    assert sum(gaps.values()) == pytest.approx(recorded.window_s - busy, rel=1e-6)


def test_bench_trace_gap_takes_the_innermost_program_frame():
    ops = {0: [_op("%fusion.1 = f32[2] fusion()", 0.0, 1.0)]}
    spans = [trace.Span(trace.WINDOW_SPAN, 0.0, 3.0),
             trace.Span("bench.host", 0.0, 3.0)]
    frames = [trace.Span("train.py:10 run", 0.5, 3.0),
              trace.Span("model.py:20 init_params", 1.5, 2.5),
              trace.Span("core.py:30 bind", 1.9, 2.1)]
    s = trace.summarize(ops, spans, frames)
    gaps = dict(trace.breakdown(s, program=frozenset({"train.py", "model.py"}))
                ["idle_gaps"])
    assert gaps == {"bench.host > model.py:20 init_params": pytest.approx(2.0)}
    assert dict(trace.breakdown(s)["idle_gaps"]) == {"bench.host": pytest.approx(2.0)}
