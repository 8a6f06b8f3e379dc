"""Reduce a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to device busy time, device time per
operation and idle gaps labelled by the host span they fall in.

Device operations are the events of each ``/device:TPU:<n>`` plane's
``XLA Ops`` line.  Host spans are the ``TraceAnnotation`` events the
benchmark writes (``drive.TimedBackend``, plus ``step`` around each
engine step); they sit on the same clock.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

#: Host annotations that label idle gaps, innermost first.
SPAN_NAMES = ("admit_full", "admit_prefix", "prefill_chunk", "decode",
              "step")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    chips: int
    busy_s: float                      # union of op intervals, per chip
    first_ns: float                    # earliest device op
    last_ns: float                     # latest device op end
    op_s: Dict[str, float]             # device seconds per op name
    gaps: List[Tuple[str, float]]      # (host label, seconds), longest first

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_s.items() if rx.search(n))


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {trace_dir}, "
                                f"found {len(files)}")
    return files[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_name(event_name: str) -> str:
    """The op's name without XLA's numeric uniquifier: TPU traces name
    an op by its whole HLO instruction, ``%fusion.12 = bf16[...] ...``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def reduce(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    busy, op_s = 0.0, collections.Counter()
    chips, first, last = 0, float("inf"), 0.0
    merged0 = []
    host = []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            chips += 1
            iv = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    op_s[_op_name(ev.name)] += ev.duration_ns * 1e-9
            merged = _union(iv)
            busy += sum(e - s for s, e in merged) * 1e-9
            if merged:
                first = min(first, merged[0][0])
                last = max(last, merged[-1][1])
            if chips == 1:
                merged0 = merged
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        host.append((ev.start_ns, ev.start_ns
                                     + ev.duration_ns, ev.name))
    if chips == 0:
        raise ValueError(f"{path}: no TPU device plane")
    by_name = {n: sorted((s, e) for s, e, m in host if m == n)
               for n in SPAN_NAMES}
    starts = {n: [s for s, _ in iv] for n, iv in by_name.items()}

    def label(t):
        for n in SPAN_NAMES:                       # innermost first
            i = bisect.bisect_right(starts[n], t) - 1
            if i >= 0 and by_name[n][i][1] >= t:
                return "scheduler" if n == "step" else n
        return "between steps"

    labels = collections.Counter()
    for (_, e0), (s1, _) in zip(merged0, merged0[1:]):
        labels[label((e0 + s1) / 2)] += (s1 - e0) * 1e-9
    return TraceSummary(chips=chips, busy_s=busy / chips, first_ns=first,
                        last_ns=last, op_s=dict(op_s),
                        gaps=labels.most_common())
