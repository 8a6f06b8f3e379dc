"""Share of its memory roofline the paged-attention kernel reaches in
the traced window: the bytes it needs (live K/V pages and scales, q and
out rows, ``chipbench.flops.paged_attention_bytes``) over the chip's
HBM bandwidth, divided by the device time of its
``paged_attention_decode_*`` ops.  The kernel is memory bound: its
arithmetic intensity is about one FLOP per byte."""
from chipbench.flops import paged_attention_bytes


def read(rec):
    t = rec.trace
    if t is None:
        return None
    kernel_s = t.op_seconds(r"paged_attention_decode")
    spans = [s for s in rec.window.spans if s.name == "decode"
             and s.t0 >= rec.trace_t0 and s.t1 <= rec.trace_t1]
    if kernel_s <= 0 or not spans:
        return None
    prog = rec.cell.config["program"]
    need = sum(paged_attention_bytes(rec.dims, s.contexts,
                                     prog["page_size"], prog["cache_dtype"])
               for s in spans)
    return 100.0 * need / rec.peaks["hbm_bytes_per_s"] / kernel_s
