"""Profiler spans and the in-memory timeline of a wall-clock ``parallel_for``.

:func:`span` opens an ``eneac.*`` profiler span: a
``jax.profiler.TraceAnnotation`` once ``jax`` has been imported (so the
spans land on the profiler's host clock, next to the device trace), a
no-op before that, so the scheduling core never imports ``jax`` itself.
Spans are always emitted and cost about a microsecond; the profiler
records them only while a trace runs.

The same call sites fill :class:`Timeline`, which the ``"interrupt"``
engine attaches to ``RunReport.timeline``: the dispatcher's seconds per
phase (:class:`Phases`), one :class:`ChunkTimes` per completed chunk, and
the completion bus's wake-ups.

:func:`add_work` is the per-chunk work counter: an op adds the work it did
(in its own unit: stored entries for a sparse product) to the chunk that
the calling thread is running, and the backends sum it per unit into
``RunReport.per_worker_work``.  Outside a chunk it does nothing.
"""

from __future__ import annotations

import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, NamedTuple

__all__ = ["span", "add_work", "Phases", "ChunkTimes", "Timeline"]


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **ids) -> None:
        return None


_NO_SPAN = _NoSpan()
_annotation = None


def span(name: str, **ids):
    """A profiler span ``name`` carrying ``ids`` (``unit``, ``start``) as
    its arguments; ``set_metadata(**ids)`` adds ids once they are known."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return _NO_SPAN
        _annotation = profiler.TraceAnnotation
    return _annotation(name, **ids)


class _ChunkWork(threading.local):
    """The work counted for the chunk the calling thread runs: ``open``
    while a backend runs a chunk on this thread, ``work`` None until an op
    counts."""

    open = False
    work = None


_chunk_work = _ChunkWork()


def add_work(n: int) -> None:
    """Add ``n`` to the work of the chunk the calling thread is running;
    a no-op outside a chunk."""
    cw = _chunk_work
    if cw.open:
        cw.work = n if cw.work is None else cw.work + n


def open_work() -> None:
    """A backend starts a chunk on this thread: count from nothing."""
    cw = _chunk_work
    cw.open, cw.work = True, None


def close_work():
    """The chunk's counted work (None if no op counted); stops counting."""
    cw = _chunk_work
    work, cw.open, cw.work = cw.work, False, None
    return work


class Phases:
    """Seconds the dispatcher spends in each phase of a ``parallel_for``.

    ``with phases("submit", unit=u):`` times the block into
    ``seconds["submit"]`` and opens the span ``eneac.submit`` over it.
    """

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}

    def __call__(self, name: str, **ids) -> "_Phase":
        t0 = time.perf_counter_ns()
        return _Phase(self, name, span("eneac." + name, **ids), t0)


class _Phase:
    __slots__ = ("_phases", "_name", "_span", "_t0")

    def __init__(self, phases: Phases, name: str, sp, t0: int) -> None:
        self._phases, self._name, self._span, self._t0 = phases, name, sp, t0

    def __enter__(self):
        self._span.__enter__()
        return self._span

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        t1 = time.perf_counter_ns()
        seconds = self._phases.seconds
        seconds[self._name] = seconds.get(self._name, 0.0) + (t1 - self._t0) / 1e9


class ChunkTimes(NamedTuple):
    """One completed chunk; times are ``time.perf_counter_ns()``."""

    unit: str
    start: int
    stop: int
    submitted: int   # the unit's submit() entered
    enqueued: int    # submit() returned (jax units) / execution began (host units)
    ready: int       # the result was ready, on the unit's thread
    posted: int      # the completion was posted to the bus
    drained: int     # the dispatcher took it off the bus


@dataclass
class Timeline:
    """What one wall-clock ``"interrupt"`` run's dispatcher did, and when."""

    # dispatcher seconds per phase: units_start, submit, bus_wait,
    # complete, units_close, report (the eneac.<phase> spans)
    phase_s: Dict[str, float]
    # one per completed chunk on a backend that stamps its chunks (every
    # local backend; transport units do not), in completion order
    chunks: List[ChunkTimes]
    wakeups: int     # returns of CompletionBus.wait
    drained: int     # completions taken off the bus
