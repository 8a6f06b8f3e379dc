"""What the program's own spans give the benchmark (the program's
``repro/serve/spans.py``).

Each span keeps its self time and calls as flat counters of the
engine's ``stats`` (``self_s:<span>``, ``calls:<span>``), which the
window copies at its open and close; ``self_ms_per`` reads their change
across the window, and None where the program keeps no such counter.

Each span is also a ``TraceAnnotation`` on the profiler's clock.
``split_gaps`` puts idle device time down to them: each idle gap is
split over the innermost spans that cover its parts, so one gap that
runs from the tail of a decode call through the engine's bookkeeping to
the next dispatch is shared among those phases, and the labels still
sum to the idle time.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, Optional, Sequence, Tuple

#: Host spans that label idle time, innermost first: the program's
#: backend calls, the harness's timed calls (``drive.TimedBackend``),
#: the engine's phases, the engine's step and the harness's step.
GAP_SPANS = ("backend.prefill", "backend.decode_dispatch",
             "backend.decode_wait", "backend.page_table", "backend.swap",
             "admit_full", "admit_prefix", "prefill_chunk", "decode",
             "engine.admit", "engine.grow", "engine.decode", "engine.finish",
             "engine.step", "step")


def self_ms_per(window, spans: Iterable[str], per: str) -> Optional[float]:
    """Milliseconds of self time of ``spans`` over the window, per call
    of span ``per``; None without calls."""
    s0, s1 = window.stats0, window.stats1
    calls = s1.get("calls:" + per, 0) - s0.get("calls:" + per, 0)
    if calls <= 0:
        return None
    own = sum(s1.get("self_s:" + s, 0.0) - s0.get("self_s:" + s, 0.0)
              for s in spans)
    return own / calls * 1e3


def _label(name: Optional[str]) -> str:
    if name is None:
        return "between steps"
    return "scheduler" if name == "step" else name


def split_gaps(gaps: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[float, float, str]],
               names: Sequence[str] = GAP_SPANS) -> Dict[str, float]:
    """Seconds of idle time per label.  ``gaps`` are sorted, disjoint
    (start_ns, end_ns) intervals; ``spans`` are (start_ns, end_ns, name)
    host spans, of which those named in ``names`` (innermost first)
    count.  Each part of a gap takes the innermost span that covers it
    (``step`` reads ``scheduler``; no span, ``between steps``)."""
    rank = {n: i for i, n in enumerate(names)}
    edges = []                        # (time, +1/-1, rank)
    for s, e, n in spans:
        if n in rank and e > s:
            edges += [(s, 1, rank[n]), (e, -1, rank[n])]
    edges.sort()
    # elementary segments between consecutive span edges, each with the
    # innermost span open over it
    seg = []                          # (start, end, label)
    open_ = [0] * len(names)
    for k, (t, d, r) in enumerate(edges):
        open_[r] += d
        if k + 1 < len(edges) and edges[k + 1][0] > t:
            inner = next((i for i, c in enumerate(open_) if c), None)
            seg.append((t, edges[k + 1][0],
                        _label(None if inner is None else names[inner])))
    out = collections.Counter()
    j = 0
    for g0, g1 in gaps:
        while j < len(seg) and seg[j][1] <= g0:
            j += 1
        t, k = g0, j
        while t < g1:
            if k < len(seg) and seg[k][0] <= t:
                end = min(g1, seg[k][1])
                out[seg[k][2]] += (end - t) * 1e-9
                k += 1
            else:
                end = min(g1, seg[k][0]) if k < len(seg) else g1
                out[_label(None)] += (end - t) * 1e-9
            t = end
    return dict(out)


def idle_by_span(path: str) -> Dict[str, float]:
    """``split_gaps`` over a profiler trace (``.xplane.pb``): the idle
    gaps between the first chip's device ops, and the host spans of
    ``GAP_SPANS`` (``trace_reduce`` reads the same lines)."""
    from jax.profiler import ProfileData
    from chipbench.trace_reduce import OPS_LINE, _union
    ops, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name) and ops is None:
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
        elif plane.name.startswith("/host:"):
            spans += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for line in plane.lines for ev in line.events
                      if ev.name in GAP_SPANS]
    if ops is None:
        raise ValueError(f"{path}: no TPU device plane")
    busy = _union(ops)
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]
    return split_gaps(gaps, spans)
