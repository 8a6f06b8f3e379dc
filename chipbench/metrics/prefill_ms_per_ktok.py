"""Host milliseconds in prefill calls (``admit_full``, ``admit_prefix``,
``prefill_chunk``) per 1,000 uncached prompt tokens they wrote."""
from chipbench.window_stats import window_spans


def read(rec):
    spans = window_spans(rec.window,
                         ("admit_full", "admit_prefix", "prefill_chunk"))
    tokens = sum(s.tokens for s in spans)
    if not tokens:
        return None
    return sum(s.t1 - s.t0 for s in spans) / tokens * 1e6
