"""Generated tokens stamped in the window, per second of the window."""
from chipbench.window_stats import tokens_in_window


def read(rec):
    return tokens_in_window(rec.window) / rec.window.seconds
