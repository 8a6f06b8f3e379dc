"""Mean host milliseconds of one batched decode call."""
from chipbench.window_stats import window_spans


def read(rec):
    spans = window_spans(rec.window, ("decode",))
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(spans) * 1e3
