"""Model FLOPs this chip computed in the window — every prefill and
decode call's matmuls outside the routed experts, attention over the
context and the head (``chipbench.lfm2_flops``), plus the expert
matmuls of each row the held experts computed (the program's
``moe_rows`` counter, its change across the window) — over the
window's seconds times the chip's bf16 peak, in percent.  None where
the program keeps no such counter."""
from chipbench.lfm2_flops import expert_row_flops, span_flops
from chipbench.window_stats import window_spans


def read(rec):
    w = rec.window
    if "moe_rows" not in w.stats1:
        return None
    spans = window_spans(w, ("admit_full", "admit_prefix", "prefill_chunk",
                             "decode"))
    if not spans:
        return None
    rows = w.stats1["moe_rows"] - w.stats0["moe_rows"]
    flops = (sum(span_flops(rec.dims, s) for s in spans)
             + rows * expert_row_flops(rec.dims))
    return 100.0 * flops / (w.seconds * rec.peaks["bf16_flops"])
