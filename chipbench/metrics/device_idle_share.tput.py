"""Share of the traced window in which no operation ran on the device:
1 minus the union of device op intervals over the window's length."""


def read(rec):
    t = rec.trace
    if t is None or rec.trace_t1 <= rec.trace_t0:
        return None
    return 100.0 * (1.0 - t.busy_s / (rec.trace_t1 - rec.trace_t0))
