"""Model FLOPs of every prefill and decode call in the window (matmuls,
the head for each sampled token, attention over the context;
``chipbench.flops``) over the window's seconds times the chip's bf16
peak, in percent."""
from chipbench.flops import span_flops
from chipbench.window_stats import window_spans


def read(rec):
    spans = window_spans(rec.window, ("admit_full", "admit_prefix",
                                      "prefill_chunk", "decode"))
    if not spans:
        return None
    flops = sum(span_flops(rec.dims, s) for s in spans)
    return 100.0 * flops / (rec.window.seconds * rec.peaks["bf16_flops"])
