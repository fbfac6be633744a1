"""Host spans of the program, recorded in memory on demand.

    with trace.recording() as rec:
        solver.solve_fast_batch(problems, "energy")
    rec.seconds()["pack.slots"]     # seconds of every temporal_pack call

`span(name)` marks a block of host work.  While a `recording()` is
open, each span appends a `Span` record (name, start and end on the
`perf_counter_ns` clock, the index of the enclosing span on the same
thread, and the id of the `batched` call it ran in) and is also a
`jax.profiler.TraceAnnotation` of the same name, so a profiler trace
taken at the same time shows the span on the device trace's clock.
With no recording open a span is one test of a module variable: it
builds no annotation and stores nothing.

`spanned(name)` makes every call of a function one span.  `batched`
groups the spans of one call into the program, such as one
`solve_fast_batch`: every span opened inside it on the same thread
shares one batch id, and a batched call inside another keeps the outer
id.

Span names are dotted by layer: `problem.mask` (a ScheduleProblem's
flow-edge mask) with `problem.hops` inside it (hop-count rows found on
a cache miss); `lp.build`; `pdhg.stack`, `pdhg.run`, `pdhg.unstack`;
`pack.decompose`, `pack.slots`, `pack.evaluate`; `verify.check`;
`failure.degrade` (`failures.degrade_problem`) and `warm.project`
(`solver.project_warm_start`).  Counters stay with the code they count
(`solver.dispatch_stats()`, `solver.ensemble_stats()`,
`timeslot.accounting_stats()`).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import threading
import time


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int | None          # None while the span is open
    parent: int | None          # index of the enclosing span, same thread
    batch: int | None           # id of the batched call it ran in

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class Recorder:
    """The spans of one `recording()`, in the order they were opened."""

    def __init__(self):
        self.records: list[Span] = []
        self._lock = threading.Lock()
        self._batch_ids = itertools.count()

    def _open(self, name: str, parent: int | None, batch: int | None) -> int:
        with self._lock:
            self.records.append(Span(name, time.perf_counter_ns(), None,
                                     parent, batch))
            return len(self.records) - 1

    def seconds(self) -> dict[str, list[float]]:
        """Seconds of every closed span, by name."""
        out = collections.defaultdict(list)
        for r in self.records:
            if r.end_ns is not None:
                out[r.name].append(r.seconds)
        return dict(out)

    def total(self, prefix: str, start: int = 0) -> float:
        """Seconds in closed spans whose name starts with `prefix`, of
        the records from index `start` on; a span nested inside another
        such span is not counted again."""
        recs = self.records
        total_ns = 0
        for r in recs[start:]:
            if r.end_ns is None or not r.name.startswith(prefix):
                continue
            up = r.parent
            while up is not None and not recs[up].name.startswith(prefix):
                up = recs[up].parent
            if up is None:
                total_ns += r.end_ns - r.start_ns
        return total_ns * 1e-9


_recorder: Recorder | None = None
_local = threading.local()    # .stack: open (recorder, index); .batch: id
_OFF = contextlib.nullcontext()


class _SpanCtx:
    __slots__ = ("rec", "name", "index", "note")

    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        import jax

        stack = _stack()
        parent = (stack[-1][1] if stack and stack[-1][0] is self.rec
                  else None)
        self.note = jax.profiler.TraceAnnotation(self.name)
        self.note.__enter__()
        self.index = self.rec._open(self.name, parent,
                                    getattr(_local, "batch", None))
        stack.append((self.rec, self.index))
        return self

    def __exit__(self, *exc):
        self.rec.records[self.index].end_ns = time.perf_counter_ns()
        _stack().pop()
        self.note.__exit__(*exc)
        return False


def _stack() -> list[tuple[Recorder, int]]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def span(name: str):
    """A context manager marking one block of host work as `name`."""
    if _recorder is None:
        return _OFF
    return _SpanCtx(_recorder, name)


def spanned(name: str):
    """Decorator: every call of the function is one span `name`."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if _recorder is None:
                return fn(*args, **kwargs)
            with _SpanCtx(_recorder, name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def batched(fn):
    """Decorator: the spans of one call of `fn` share one batch id."""
    @functools.wraps(fn)
    def inner(*args, **kwargs):
        rec = _recorder
        if rec is None or getattr(_local, "batch", None) is not None:
            return fn(*args, **kwargs)
        _local.batch = next(rec._batch_ids)
        try:
            return fn(*args, **kwargs)
        finally:
            _local.batch = None
    return inner


def active() -> Recorder | None:
    """The open recording's recorder, or None."""
    return _recorder


@contextlib.contextmanager
def recording():
    """Record spans until the block ends; yields the Recorder."""
    global _recorder
    outer, rec = _recorder, Recorder()
    _recorder = rec
    try:
        yield rec
    finally:
        _recorder = outer
