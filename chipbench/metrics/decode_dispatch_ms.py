"""Host milliseconds per decode call before the decode program runs:
its inputs moved and the program enqueued (``backend.decode_dispatch``).
None where the program keeps no span counters."""
from chipbench.program_spans import self_ms_per


def read(rec):
    return self_ms_per(rec.window, ("backend.decode_dispatch",),
                       per="backend.decode_dispatch")
