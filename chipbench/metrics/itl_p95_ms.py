"""95th percentile of every inter-token gap that ends in the window."""
from chipbench.window_stats import itl_gaps, percentile


def read(rec):
    gaps = itl_gaps(rec.window)
    return percentile(gaps, 95) * 1e3 if gaps else None
