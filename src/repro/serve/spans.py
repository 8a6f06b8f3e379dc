"""Spans at the serve stack's own layer boundaries, on the profiler's
clock.

A span is a context manager around one phase of an engine step or one
backend call.  It does two things:

* while a profiler trace is on, it opens a
  ``jax.profiler.TraceAnnotation``, so the phase lands in the trace on
  the same clock as the device ops;
* it adds its SELF time — its duration less the time its child spans
  cover — to ``self_s:<name>`` and 1 to ``calls:<name>`` in the owning
  engine's flat ``stats`` dict.

Self times of nested spans therefore sum to the duration of the
outermost span, and nothing is counted twice.  The counters are flat
keys beside the engine's other counters, so a shallow copy of ``stats``
snapshots them and ``router.aggregate_stats`` sums them across
replicas.  Durations are read with ``time.perf_counter_ns`` (the clock
of ``time.perf_counter``, in integer nanoseconds, so a parent's self
time is never negative).  Spans are always on; with no trace running
one costs the check for a trace and two clock reads.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

from jax.profiler import TraceAnnotation

_tracing = TraceAnnotation.is_enabled    # a profiler trace is on

#: Every span the serve stack opens.  ``engine.*`` are phases of
#: ``ContinuousBatchingEngine.step``; ``backend.*`` are device calls of
#: ``SingleDeviceBackend`` (and so of its tensor-parallel subclass).
NAMES = (
    "engine.step",              # the whole step, less the phases below
    "engine.grow",              # lazy page growth, eviction, swap/preempt
    "engine.admit",             # one request's admission or prefill chunk
    "engine.decode",            # window drafting to the end of the commit
    "engine.finish",            # completions and slot release
    "backend.prefill",          # a jitted prefill through its first token
    "backend.decode_dispatch",  # decode inputs moved, program enqueued
    "backend.decode_wait",      # device compute and the results' copy back
    "backend.page_table",       # eager block-table and page ops
    "backend.swap",             # host-tier page moves
)


class SpanRecorder:
    """Opens spans and keeps their counters in ``stats`` (see module
    docstring).  ``clock`` returns integer nanoseconds."""

    def __init__(self, stats: Dict[str, float]):
        self.stats = stats
        self.clock = time.perf_counter_ns
        # nanoseconds the open spans' children covered, innermost last
        self._open: List[int] = []
        self._keys = {name: ("self_s:" + name, "calls:" + name)
                      for name in NAMES}
        for self_key, calls_key in self._keys.values():
            stats.setdefault(self_key, 0.0)
            stats.setdefault(calls_key, 0)

    @property
    def depth(self) -> int:
        """Spans open now; 0 between steps, whatever a step raised."""
        return len(self._open)

    def span(self, name: str, **args) -> "_Span":
        """A span called ``name`` (one of ``NAMES``); ``args`` (e.g. a
        request's ``uid``) become arguments of its trace event."""
        return _Span(self, name, args)


class _Span:
    __slots__ = ("rec", "name", "args", "note", "t0")

    def __init__(self, rec: SpanRecorder, name: str, args: dict):
        self.rec, self.name, self.args = rec, name, args

    def __enter__(self) -> "_Span":
        # an annotation records nothing unless a profiler trace is on,
        # so it is made only then (it would cost more than the rest)
        self.note = None
        if _tracing():
            self.note = TraceAnnotation(self.name, **self.args)
            self.note.__enter__()
        self.rec._open.append(0)
        self.t0 = self.rec.clock()
        return self

    def __exit__(self, *exc) -> bool:
        rec = self.rec
        took = rec.clock() - self.t0
        children = rec._open.pop()
        self_key, calls_key = rec._keys[self.name]
        rec.stats[self_key] += (took - children) * 1e-9
        rec.stats[calls_key] += 1
        if rec._open:
            rec._open[-1] += took
        if self.note is not None:
            self.note.__exit__(*exc)
        return False


class _NoSpans:
    """The recorder of a backend no engine has bound: records nothing."""

    _none = contextlib.nullcontext()

    def span(self, name: str, **args):
        return self._none


NO_SPANS = _NoSpans()


def host_split_ms(stats: Dict[str, float]) -> Dict[str, float]:
    """The host time of a step, split as the benchmark reads it:
    milliseconds per engine step in the engine's own code and in the
    eager page-table ops, and per decode call in the decode dispatch."""
    steps = max(1, stats["calls:engine.step"])
    decodes = max(1, stats["calls:backend.decode_dispatch"])
    engine = sum(v for k, v in stats.items()
                 if k.startswith("self_s:engine."))
    return {"engine_self_ms": engine / steps * 1e3,
            "page_table_ms": stats["self_s:backend.page_table"] / steps * 1e3,
            "decode_dispatch_ms":
                stats["self_s:backend.decode_dispatch"] / decodes * 1e3}
