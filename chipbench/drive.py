"""Drive the program's engine for a measured window and record what
happened: per-token host stamps, the host spans of every backend call,
per-step host time and the engine's counters.

Spans are recorded from here only: ``TimedBackend`` forwards every call
to the program's backend and times the ones that reach the device.
Each of them returns host values (token ids, flags), so the call ends on
the device's result.  In a traced run each span is also a
``jax.profiler.TraceAnnotation``, on the trace's clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench.traffic import Request, Traffic


class CompileLog:
    """Counts XLA backend compiles and their seconds (a listener on
    JAX's monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class GcLog:
    """Times the interpreter's garbage collections (a ``gc.callbacks``
    hook): runs per generation, their seconds and the longest."""

    def __init__(self):
        self.runs, self.seconds, self.longest = [0, 0, 0], 0.0, 0.0
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            self.runs[info["generation"]] += 1
            self.seconds += d
            self.longest = max(self.longest, d)
            self._t0 = None


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    tokens: int = 0             # prefill: uncached prompt tokens written
    prefix: int = 0             # prefill: cached tokens attended over
    contexts: Optional[np.ndarray] = None   # decode: context per live slot


class TimedBackend:
    """Forwards every attribute to the program's backend; times
    ``admit_full``, ``admit_prefix``, ``prefill_chunk`` and ``decode``,
    and mirrors each slot's context length (what the device's ``pos``
    holds) so a decode span knows the context of every live slot."""

    TIMED = ("admit_full", "admit_prefix", "prefill_chunk", "decode")

    def __init__(self, inner, max_slots: int):
        self._inner = inner
        self.spans: List[Span] = []
        self.admits: List[tuple] = []      # (slot, cached prefix tokens)
        self.trace = False
        self._pos = np.zeros(max_slots, np.int64)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def annotate(self, name):
        """A profiler annotation while a trace is on, else nothing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def _span(self, name, **kw):
        with self.annotate(name):
            t0 = time.perf_counter()
            yield
            self.spans.append(Span(name, t0, time.perf_counter(), **kw))

    def admit_full(self, padded, slot, true_len, bt_row):
        with self._span("admit_full", tokens=true_len):
            out = self._inner.admit_full(padded, slot, true_len, bt_row)
        self._pos[slot] = true_len
        self.admits.append((slot, 0))
        return out

    def admit_prefix(self, padded, slot, prefix_len, true_len, bt_row, *,
                     n_prefix_pages):
        with self._span("admit_prefix", tokens=true_len, prefix=prefix_len):
            out = self._inner.admit_prefix(padded, slot, prefix_len,
                                           true_len, bt_row,
                                           n_prefix_pages=n_prefix_pages)
        self._pos[slot] = prefix_len + true_len
        self.admits.append((slot, prefix_len))
        return out

    def prefill_chunk(self, padded, slot, prefix_len, true_len, bt_row, *,
                      n_prefix_pages):
        with self._span("prefill_chunk", tokens=true_len, prefix=prefix_len):
            out = self._inner.prefill_chunk(padded, slot, prefix_len,
                                            true_len, bt_row,
                                            n_prefix_pages=n_prefix_pages)
        self._pos[slot] = prefix_len + true_len
        return out

    def decode(self, tokens, active, lens=None):
        live = np.asarray(active).astype(bool)
        ctx = self._pos[live] + 1
        with self._span("decode", tokens=int(live.sum()), contexts=ctx):
            out = self._inner.decode(tokens, active, lens)
        self._pos = np.where(live, self._pos + 1, 0)
        return out

    def release_slot(self, slot):
        self._pos[slot] = 0
        return self._inner.release_slot(slot)


@dataclasses.dataclass
class Window:
    """What one measured window recorded.  Times are seconds from the
    window's start on the host clock."""
    seconds: float
    closed: bool
    requests: Dict[int, Request]
    submitted: Dict[int, float]           # uid -> submit time
    stamps: Dict[int, List[float]]        # uid -> one stamp per token
    completions: Dict[int, object]        # uid -> program Completion
    matched: Dict[int, int]               # uid -> cached prefix tokens
    steps: List[tuple]                    # (t0, t1) of each engine step
    spans: List[Span]
    stats0: Dict[str, float]
    stats1: Dict[str, float]              # counters at the window's close
    drained_s: float                      # open loop: drain after close
    compiles: int                         # compiles inside the window
    late_s: float                         # open loop: largest submit lag

    def in_window(self) -> List[int]:
        """Requests the window is answerable for: scheduled (open loop)
        or submitted (closed loop) before its close."""
        return [u for u, t in self.submitted.items()
                if 0 <= t < self.seconds]


def _program_request(req: Request):
    from repro.serve.scheduler import Request as ProgramRequest
    return ProgramRequest(req.uid, req.prompt, req.max_new_tokens)


def run_window(engine, backend: TimedBackend, traffic: Traffic,
               seconds: float, compiles: CompileLog, *,
               drain_cap_s: float = 30.0, on_time=None) -> Window:
    """Serve ``traffic`` through ``engine``; measure for ``seconds``.

    The mix's ``preroll_s`` (default 0) of traffic runs first, so the
    window opens on the engine's steady state; its requests are not the
    window's.  The mix's arrival process (``traffic.arrivals()``) gives
    the scheduled requests, each submitted when its arrival comes due,
    and the requests that a completion sends, submitted at once.  Open
    loop: after the close the engine keeps stepping, with no new
    arrivals, until every request of the window has its first token or
    ``drain_cap_s`` has passed.  Closed loop: the run stops at the
    close.

    ``on_time(t)`` is called before every step (the traced run starts
    and stops the profiler from it).  Times are seconds from the
    window's opening, negative during the pre-roll."""
    reqs: Dict[int, Request] = {}
    submitted: Dict[int, float] = {}
    stamps: Dict[int, List[float]] = {}
    counts: Dict[int, int] = {}
    done: Dict[int, object] = {}
    matched: Dict[int, int] = {}
    steps: List[tuple] = []
    late = 0.0
    source = traffic.arrivals()
    closed = source.closed
    preroll = float(traffic.mix.get("preroll_s", 0.0))
    pending: List[Request] = source.schedule()
    n_spans0 = len(backend.spans)
    origin = time.perf_counter() + preroll

    def submit(req: Request, when: float):
        reqs[req.uid] = req
        submitted[req.uid] = when
        stamps[req.uid] = []
        counts[req.uid] = 0
        engine.submit(_program_request(req))

    i = 0
    stats0 = stats1 = c0 = c1 = None
    while True:
        now = time.perf_counter() - origin
        if now >= 0 and stats0 is None:
            stats0, c0 = dict(engine.stats), compiles.count
        if now >= seconds and stats1 is None:
            stats1, c1 = dict(engine.stats), compiles.count
            if closed:
                break
        if stats1 is not None:
            waiting = [u for u in reqs if 0 <= submitted[u] < seconds
                       and counts[u] == 0 and u not in done]
            if not waiting or now >= seconds + drain_cap_s:
                break
        while i < len(pending) and pending[i].arrival_s <= now \
                and now < seconds:
            late = max(late, now - pending[i].arrival_s)
            submit(pending[i], pending[i].arrival_s)
            i += 1
        if engine.num_active == 0 and not engine.queue:
            # idle: sleep to the next arrival, the opening or the close
            events = [seconds] + ([0.0] if stats0 is None else [])
            if i < len(pending):
                events.append(pending[i].arrival_s)
            time.sleep(max(0.0, min(events) - now))
            continue
        if on_time is not None:
            on_time(now)
        n_adm = len(backend.admits)
        with backend.annotate("step"):
            t0 = time.perf_counter()
            out = engine.step()
            t1 = time.perf_counter()
        steps.append((t0 - origin, t1 - origin))
        for slot, pre in backend.admits[n_adm:]:
            s = engine.slots[slot]
            if s is not None:
                matched[s.uid] = pre
        stamp = t1 - origin
        prog = engine.progress()
        for c in out:
            prog[c.uid] = len(c.tokens)
            done[c.uid] = c
        for uid, k in prog.items():
            if k > counts[uid]:
                stamps[uid].extend([stamp] * (k - counts[uid]))
                counts[uid] = k
        if stamp < seconds:
            for c in out:
                for req in source.after(c):
                    submit(req, stamp)
    end = time.perf_counter() - origin
    for s in backend.spans[n_spans0:]:
        s.t0 -= origin
        s.t1 -= origin
    return Window(seconds=seconds, closed=closed, requests=reqs,
                  submitted=submitted,
                  stamps=stamps, completions=done, matched=matched,
                  steps=steps, spans=backend.spans[n_spans0:],
                  stats0=stats0, stats1=stats1,
                  drained_s=max(0.0, end - seconds),
                  compiles=c1 - c0, late_s=late)
