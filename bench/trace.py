"""Reduction from a JAX profiler trace to device busy time, kernel time
and the breakdown of the result line.

The profiler writes an `.xplane.pb` under `<dir>/plugins/profile/<t>/`.
`jax.profiler.ProfileData` reads it: device planes are named
`/device:TPU:<i>`, and their "XLA Ops" line holds one event per HLO
operation that ran, with its start and duration in nanoseconds on the
host's clock. Host spans written with `jax.profiler.TraceAnnotation`
sit on the `/host:CPU` plane, and so do the Python frames that the
profiler's Python tracer records, named `$<file>.py:<line> <function>`.

Everything here is plain arithmetic on intervals, so a small recorded
trace checks it on the CPU (bench/tests).
"""
from __future__ import annotations

import contextlib
import glob
import heapq
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_FRAME = re.compile(r"^([\w.\-]+\.py):\d+ ")
_OP_LINES = ("XLA Ops",)
WINDOW_SPAN = "bench.window"


class Op(NamedTuple):
    name: str  # the HLO instruction as the trace names it, "%fusion.12 = ..."
    start: float  # seconds
    end: float


class Span(NamedTuple):
    name: str
    start: float
    end: float


class TraceSummary(NamedTuple):
    ops: Dict[int, List[Op]]  # device index -> ops clipped to the window
    spans: List[Span]  # host annotations
    window: Tuple[float, float]
    frames: List[Span] = []  # Python frames that overlap the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


@contextlib.contextmanager
def capture():
    """Trace the block; yields a list that holds the `.xplane.pb` path
    once the block has ended. The trace directory lives under TMPDIR
    and is removed by `cleanup`."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    out: List[str] = []
    jax.profiler.start_trace(d)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        out.extend(found[:1])
        out.append(d)


def cleanup(captured: Sequence[str]):
    if captured:
        shutil.rmtree(captured[-1], ignore_errors=True)


def load(path: str) -> TraceSummary:
    """Device ops and host spans of one `.xplane.pb` file (or its gzip)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    frames: List[Span] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            evs = ops.setdefault(dev, [])
            for line in plane.lines:
                if line.name not in _OP_LINES:
                    continue
                for ev in line.events:
                    evs.append(Op(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append(Span(ev.name, ev.start_ns * 1e-9,
                                          ev.end_ns * 1e-9))
                    elif ev.name.startswith("$"):
                        frames.append(Span(ev.name[1:], ev.start_ns * 1e-9,
                                           ev.end_ns * 1e-9))
    return summarize(ops, spans, frames)


def program_files(src: str) -> frozenset:
    """Names of the program's Python files under `src` that no file of
    JAX shares, so that a frame of the trace names the program alone."""
    import jax

    def names(top):
        return {f for _, _, fs in os.walk(top) for f in fs if f.endswith(".py")}

    return frozenset(names(src) - names(os.path.dirname(jax.__file__)))


def summarize(ops: Dict[int, List[Op]], spans: List[Span],
              frames: Sequence[Span] = ()) -> TraceSummary:
    """Clip ops to the `bench.window` span (or to the ops' own extent
    when the trace has none) and sort them."""
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        lo, hi = min(s.start for s in win), max(s.end for s in win)
    else:
        every = [o for evs in ops.values() for o in evs]
        lo = min((o.start for o in every), default=0.0)
        hi = max((o.end for o in every), default=0.0)
    clipped = {}
    for dev, evs in ops.items():
        keep = [Op(o.name, max(o.start, lo), min(o.end, hi))
                for o in evs if o.end > lo and o.start < hi]
        clipped[dev] = sorted(keep, key=lambda o: o.start)
    frames = [f for f in frames if f.end > lo and f.start < hi]
    return TraceSummary(clipped, sorted(spans, key=lambda s: s.start), (lo, hi),
                        frames)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(summary: TraceSummary) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not summary.ops:
        return 0.0
    per = [sum(b - a for a, b in union((o.start, o.end) for o in evs))
           for evs in summary.ops.values()]
    return sum(per) / len(per)


def idle_percent(busy: float, window: float) -> Optional[float]:
    """100 x (1 - busy / window); None for an empty window."""
    if window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)


def pallas_call(first_operand_rank: int) -> re.Pattern:
    """A Pallas kernel in the trace: a `tpu_custom_call` whose first
    operand is an f32 array of the given rank. The kernels carry no name
    of their own there (`kernel_metadata={}`), so the drain (first
    operand the (J, N, M) weight stack) and the mix (first operand the
    (N, N) matrix) are told apart by that operand."""
    dims = r"\d+" + r",\d+" * (first_operand_rank - 1)
    return re.compile(r"custom-call\(f32\[" + dims + r"\]\{.*tpu_custom_call")


def kernel_s(summary: TraceSummary, pattern: re.Pattern) -> float:
    """Summed device time of the ops whose instruction matches `pattern`,
    over all devices (a union per device, so nested events count once)."""
    total = 0.0
    for evs in summary.ops.values():
        total += sum(b - a for a, b in union(
            (o.start, o.end) for o in evs if pattern.search(o.name)))
    return total


_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:[.\d]+)?(?:\s|=|$)")
_CONTROL = ("while", "conditional", "call")  # hold other ops' time


def _base(name: str) -> str:
    """`%fusion.356 = ...` -> `fusion`; a Pallas kernel -> `pallas_kernel`."""
    if "tpu_custom_call" in name:
        return "pallas_kernel"
    m = _INSTR.match(name)
    return m.group(1) if m else name[:40]


def _file(frame: Span) -> str:
    m = _FRAME.match(frame.name)
    return m.group(1) if m else ""


def _innermost(spans: Iterable[Span], times: Sequence[float]) -> list:
    """For each of the ascending `times`, the shortest of `spans` that
    holds it (None where none does): one sweep, a heap of the spans
    begun so far, those that have ended dropped as they come up."""
    spans = sorted(spans, key=lambda s: s.start)
    out, begun, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i].start <= t:
            heapq.heappush(begun, (spans[i].end - spans[i].start, i))
            i += 1
        while begun and spans[begun[0][1]].end < t:
            heapq.heappop(begun)
        out.append(spans[begun[0][1]] if begun else None)
    return out


def breakdown(summary: TraceSummary, top: int = 10,
              program: frozenset = frozenset()) -> dict:
    """The device ops that took most time (device 0, by base name) and
    the longest idle gaps on device 0 summed by what the host was doing:
    the innermost `bench.*` span around the gap's middle, followed by
    the innermost frame of a file named in `program` there, if any."""
    if not summary.ops:
        return {"device_ops": [], "idle_gaps": []}
    dev0 = summary.ops[min(summary.ops)]
    by_op: Dict[str, float] = {}
    for o in dev0:
        base = _base(o.name)
        if base not in _CONTROL:
            by_op[base] = by_op.get(base, 0.0) + (o.end - o.start)
    busy = union((o.start, o.end) for o in dev0)
    lo, hi = summary.window
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    mids = [(a + b) / 2 for a, b in idle]
    spans = _innermost((s for s in summary.spans if s.name != WINDOW_SPAN), mids)
    frames = _innermost((f for f in summary.frames if _file(f) in program), mids)
    gaps: Dict[str, float] = {}
    for (a, b), span, frame in zip(idle, spans, frames):
        label = span.name if span else "none"
        if frame:
            label += " > " + frame.name
        gaps[label] = gaps.get(label, 0.0) + (b - a)
    ops_sorted = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_sorted = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops_sorted],
            "idle_gaps": [[k, v] for k, v in gaps_sorted]}
