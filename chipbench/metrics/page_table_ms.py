"""Host milliseconds per engine step in the eager block-table and page
ops (``backend.page_table``: block-table writes, slot releases, page
copies).  None where the program keeps no span counters."""
from chipbench.program_spans import self_ms_per


def read(rec):
    return self_ms_per(rec.window, ("backend.page_table",),
                       per="engine.step")
