"""Host milliseconds per engine step outside the backend calls the
benchmark times: the scheduler's own work, mean over the window's
steps."""
from chipbench.window_stats import window_spans

TIMED = ("admit_full", "admit_prefix", "prefill_chunk", "decode")


def read(rec):
    w = rec.window
    steps = [(a, b) for a, b in w.steps if 0 <= a and b <= w.seconds]
    if not steps:
        return None
    spans = sorted(window_spans(w, TIMED), key=lambda s: s.t0)
    inside, j = 0.0, 0
    for a, b in steps:
        while j < len(spans) and spans[j].t0 < a:
            j += 1
        while j < len(spans) and spans[j].t1 <= b:
            inside += spans[j].t1 - spans[j].t0
            j += 1
    return (sum(b - a for a, b in steps) - inside) / len(steps) * 1e3
