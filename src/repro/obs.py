"""The program's spans and counters: one in-memory record per process.

- `span(name)` times a block on `time.perf_counter()` and also opens
  `jax.profiler.TraceAnnotation(name)`, so that a profiler trace shows
  the block on its host timeline, on the device ops' clock.
- `count(name, n)` records an instant count, scoped by the spans open
  around it.

Every record names the span that was open around it on its thread (its
parent; -1 at a root), so a reader can take one call's records apart
from another's (`children`, `self_time`). The record is always on and
bounded: past `MAXLEN` records the oldest fall out. Profiler off, a
span costs the deque append and an inactive annotation, a few
microseconds.

Names live under `repro.`: `repro.<entry>[.<phase>]` for spans, and
`repro.trace.<function>` for the counter at the top of a jitted
function's Python body. That body runs once per trace, never per call,
so the counter counts (re-)traces where they happen.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Iterable, List, NamedTuple, Optional

import jax

MAXLEN = 1 << 16


class Record(NamedTuple):
    id: int  # order of opening, process-wide
    name: str
    parent: int  # id of the span open around it on its thread; -1 at a root
    t0: float  # time.perf_counter() seconds
    t1: float  # the span's end; t0 for a count
    n: Optional[int]  # a count's increment; None for a span

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_records: collections.deque = collections.deque(maxlen=MAXLEN)
_ids = itertools.count()
_local = threading.local()


def _open() -> list:
    """Ids of the spans open on this thread, innermost last."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager: one span record of the block, and the same name
    on the profiler's host timeline."""

    __slots__ = ("name", "_id", "_parent", "_t0", "_note")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _open()
        self._parent = stack[-1] if stack else -1
        self._id = next(_ids)
        stack.append(self._id)
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._note.__exit__(*exc)
        _open().pop()
        _records.append(Record(self._id, self.name, self._parent, self._t0,
                               t1, None))
        return False


def count(name: str, n: int = 1) -> None:
    """An instant record of `n` under the innermost open span."""
    stack = _open()
    t = time.perf_counter()
    _records.append(Record(next(_ids), name, stack[-1] if stack else -1,
                           t, t, n))


def records() -> List[Record]:
    """Every record kept, in the order they closed."""
    return list(_records)


def reset() -> None:
    _records.clear()


def children(recs: Iterable[Record], parent: Record) -> List[Record]:
    """The records directly under `parent`, in the order opened."""
    return sorted((r for r in recs if r.parent == parent.id), key=lambda r: r.id)


def self_time(s: Record, recs: Iterable[Record]) -> float:
    """`s`'s seconds less those of its child spans (children of one
    thread nest, so they do not overlap)."""
    return s.seconds - sum(c.seconds for c in children(recs, s) if c.n is None)
