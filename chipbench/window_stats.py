"""Arithmetic over one window's record, shared by the metric readers.

Rates and tails are taken over the whole window: every token stamped
before the close, every inter-token gap that ends before it, every
request scheduled in it.  A request of the window that failed, was shed
or had no first token when the drain stopped counts as beyond every
limit: its time to first token is at least the time it waited, to the
end of the drain, and no finite reading is taken for it.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile with linear interpolation between closest
    ranks; infinity where it reaches an infinite value (a failed
    request)."""
    v = np.sort(np.asarray(values, np.float64))
    if len(v) == 0:
        return math.nan
    x = q / 100.0 * (len(v) - 1)
    lo, hi = int(math.floor(x)), int(math.ceil(x))
    if math.isinf(v[hi]):
        return math.inf
    return float(v[lo] + (x - lo) * (v[hi] - v[lo]))


def tokens_in_window(w) -> int:
    return sum(sum(1 for t in s if 0 <= t <= w.seconds)
               for s in w.stamps.values())


def itl_gaps(w) -> List[float]:
    out = []
    for s in w.stamps.values():
        out.extend(b - a for a, b in zip(s, s[1:]) if 0 <= b <= w.seconds)
    return out


def failed(w, uid: int) -> bool:
    """A request of the window that will not count as served."""
    c = w.completions.get(uid)
    if c is not None:
        return c.status != "ok"
    # open loop: still without a first token when the drain stopped
    return not w.stamps[uid] and not w.closed


def ttfts(w) -> List[float]:
    """Time to first token of every request scheduled in the window,
    from its scheduled arrival; a failed request reads infinity."""
    out = []
    for uid in w.in_window():
        s = w.stamps[uid]
        if failed(w, uid) or not s:
            out.append(math.inf)
        else:
            out.append(s[0] - w.submitted[uid])
    return out


def window_spans(w, names):
    return [s for s in w.spans
            if s.name in names and 0 <= s.t0 and s.t1 <= w.seconds]
