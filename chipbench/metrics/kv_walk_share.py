"""Share of the block table the paged-attention kernel walks: the
engine's ``kv_pages_streamed`` over ``kv_pages_table`` (slots times
entries, per decode call), both taken as their change across the
window.  None where the program keeps no such counters."""


def read(rec):
    w = rec.window
    if "kv_pages_table" not in w.stats1:
        return None
    table = w.stats1["kv_pages_table"] - w.stats0["kv_pages_table"]
    if table <= 0:
        return None
    streamed = (w.stats1["kv_pages_streamed"]
                - w.stats0["kv_pages_streamed"])
    return 100.0 * streamed / table
