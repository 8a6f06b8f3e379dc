"""Share of its memory roofline the routed layers' grouped matmuls
reach in the traced window: the bytes they need
(``chipbench.lfm2_flops.expert_matmul_bytes``: the weights of the held
experts each call hit, and the routed rows in and out) over the chip's
HBM bandwidth, divided by the device time of the ``ragged-dot`` ops.

The program counts experts hit and rows per routed-layer call over the
whole window (``moe_experts_hit``, ``moe_rows``, ``moe_layer_calls``);
the traced part is charged the window's mean per call times its own
routed-layer calls (each prefill and decode call in it runs every MoE
layer once).  At decode the layer is memory bound: a few rows meet
each expert's weights.  None where the program keeps no such counter."""
from chipbench.lfm2_flops import expert_matmul_bytes


def read(rec):
    t, w = rec.trace, rec.window
    if t is None or "moe_layer_calls" not in w.stats1:
        return None
    kernel_s = t.op_seconds(r"ragged-dot")
    calls = w.stats1["moe_layer_calls"] - w.stats0["moe_layer_calls"]
    spans = [s for s in w.spans
             if s.name in ("admit_full", "admit_prefix", "prefill_chunk",
                           "decode")
             and s.t0 >= rec.trace_t0 and s.t1 <= rec.trace_t1]
    if kernel_s <= 0 or calls <= 0 or not spans:
        return None
    traced = len(spans) * (rec.dims.L - rec.dims.dense)
    hit = (w.stats1["moe_experts_hit"] - w.stats0["moe_experts_hit"]) / calls
    rows = (w.stats1["moe_rows"] - w.stats0["moe_rows"]) / calls
    need = traced * expert_matmul_bytes(rec.dims, hit, rows)
    return 100.0 * need / rec.peaks["hbm_bytes_per_s"] / kernel_s
