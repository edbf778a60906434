"""Spans over the program's host code, on the profiler's clock.

``span(name, **counts)`` marks a stretch of host code. It always enters
``jax.profiler.TraceAnnotation(name, **counts)``, so under
``jax.profiler.trace`` the span lands in the trace beside the device's
operations, on the profiler's one clock, with its counts as attributes.
Inside ``recording()`` it is also kept in memory, timed with
``time.perf_counter``, with its parent and its counts, for readers that
need a longer stretch than a profile holds::

    with obs.recording() as rec:
        simulate(params, state, reqs, window_requests=4096)
    [s.name for s in rec.spans]  # repro.simulate, repro.simulate.window, ...

Spans are for host code only: inside ``jax.jit`` a span would time the
tracing, not the run. Phases of a jitted program carry
``jax.named_scope`` instead, which reaches the device trace as the ops'
``op_name``.

The profiler counts its timestamps from the start of the profile and the
recorder from ``perf_counter``'s origin, so the two never compare: inside
a profile read the profiler's copy of a span, over a longer stretch the
recorder's.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
import time
from typing import Iterator, Optional

import jax


@dataclasses.dataclass
class Span:
    name: str
    start_s: float           # time.perf_counter at entry
    end_s: float             # at exit; NaN while the span is open
    parent: Optional[int]    # index of the enclosing span in ``spans``
    counts: dict

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


class Recorder:
    """The spans closed or opened inside one ``recording()`` block, in
    the order they were entered."""

    def __init__(self):
        self.spans: list[Span] = []
        self._open: list[int] = []


_recorder: contextvars.ContextVar[Optional[Recorder]] = \
    contextvars.ContextVar("repro_obs_recorder", default=None)


@contextlib.contextmanager
def span(name: str, **counts) -> Iterator[None]:
    """A host span named ``name`` with integer ``counts`` as attributes."""
    rec = _recorder.get()
    with jax.profiler.TraceAnnotation(name, **counts):
        if rec is None:
            yield
            return
        i = len(rec.spans)
        parent = rec._open[-1] if rec._open else None
        rec.spans.append(Span(name, time.perf_counter(), math.nan, parent,
                              counts))
        rec._open.append(i)
        try:
            yield
        finally:
            rec._open.pop()
            rec.spans[i].end_s = time.perf_counter()


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Keep every span of this block (in this thread or task) in memory;
    yields the ``Recorder``, to be read once the block ends."""
    rec = Recorder()
    token = _recorder.set(rec)
    try:
        yield rec
    finally:
        _recorder.reset(token)
