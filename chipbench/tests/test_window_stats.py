"""The window arithmetic and the metric readers, on a hand-made window:
a tail is taken over every request and every gap of the window (no
medians of chunks), a failed request counts as beyond every limit, and
the pre-roll and the drain stay outside."""
import math
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import flops
from chipbench.cell import reader
from chipbench.drive import Span, Window
from chipbench.reference.dense_gqa import dims
from chipbench.window_stats import (itl_gaps, percentile, tokens_in_window,
                                    ttfts)

CFG = {"name": "t", "hidden_size": 64, "intermediate_size": 128,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_hidden_layers": 2, "vocab_size": 100, "rms_norm_eps": 1e-6,
       "rope_theta": 1e4, "program": {"page_size": 16, "cache_dtype": "fp32"}}


def _window(closed=False):
    ok = SimpleNamespace(status="ok")
    return Window(
        seconds=10.0, closed=closed, requests={},
        # uid 0: pre-roll; 1, 2: served; 3: failed; 4: no first token
        submitted={0: -5.0, 1: 1.0, 2: 2.0, 3: 3.0, 4: 9.0},
        stamps={0: [-4.0, -1.0, 0.5, 1.5], 1: [1.25, 2.0, 9.0, 10.5],
                2: [4.0, 5.0], 3: [3.5], 4: []},
        completions={0: ok, 1: ok, 2: ok,
                     3: SimpleNamespace(status="failed")},
        matched={}, steps=[(-1.0, -0.5), (0.0, 1.0), (1.0, 3.0)],
        spans=[Span("admit_full", 0.1, 0.3, tokens=100),
               Span("admit_prefix", 1.1, 1.2, tokens=50, prefix=512),
               Span("decode", 0.4, 0.9, tokens=2,
                    contexts=np.array([10, 20])),
               Span("decode", 1.5, 2.5, tokens=1, contexts=np.array([30])),
               Span("decode", -0.9, -0.6, tokens=1, contexts=np.array([5]))],
        stats0={"prompt_tokens": 100, "prefix_hit_tokens": 10},
        stats1={"prompt_tokens": 300, "prefix_hit_tokens": 110},
        drained_s=4.0, compiles=0, late_s=0.0)


def test_tokens_and_gaps_cover_the_window_only():
    w = _window()
    assert tokens_in_window(w) == 8
    # the pre-roll's gap (-4, -1) and the drain's (9, 10.5) stay out
    assert sorted(itl_gaps(w)) == pytest.approx([0.75, 1.0, 1.0, 1.5, 7.0])


def test_ttft_counts_failures_beyond_every_limit():
    t = ttfts(_window())
    assert t[:2] == pytest.approx([0.25, 2.0])
    assert math.isinf(t[2]) and math.isinf(t[3])
    assert percentile(t, 25) == pytest.approx(0.25 + 0.75 * 1.75)
    assert math.isinf(percentile(t, 90))
    w = _window()
    w.submitted[4] = -1.0                     # now outside the window
    w.completions[3].status = "ok"
    assert ttfts(w) == pytest.approx([0.25, 2.0, 0.5])


def test_percentile_interpolates_over_all_values():
    v = list(range(101))
    assert percentile(v, 95) == pytest.approx(95.0)
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert math.isnan(percentile([], 50))


def test_readers_on_the_window():
    w = _window()
    rec = SimpleNamespace(window=w, setup_s=12.5, dims=dims(CFG),
                          cell=SimpleNamespace(config=CFG),
                          peaks={"bf16_flops": 1e12,
                                 "hbm_bytes_per_s": 1e11},
                          trace=None, trace_t0=0.0, trace_t1=0.0)
    assert reader("output_tokens_per_s")(rec) == pytest.approx(0.8)
    assert reader("itl_p95_ms")(rec) == pytest.approx(
        np.percentile([0.75, 1.0, 1.0, 1.5, 7.0], 95) * 1e3)
    assert reader("setup_s")(rec) == 12.5
    assert reader("prefix_hit_share")(rec) == pytest.approx(50.0)
    assert reader("prefill_ms_per_ktok")(rec) == pytest.approx(
        0.3 / 150 * 1e6)
    assert reader("decode_step_ms.tput")(rec) == pytest.approx(750.0)
    # steps (0, 1) and (1, 3) hold 0.2 + 0.5 and 0.1 + 1.0 s of calls
    assert reader("sched_host_ms")(rec) == pytest.approx(
        (3.0 - 1.8) / 2 * 1e3)
    want = (flops.prefill_flops(rec.dims, 100, 0)
            + flops.prefill_flops(rec.dims, 50, 512)
            + flops.decode_flops(rec.dims, [10, 20])
            + flops.decode_flops(rec.dims, [30]))
    assert reader("mfu.tput")(rec) == pytest.approx(100 * want / 1e13)
    # the trace readers find nothing without a trace
    assert reader("paged_attn_roofline.tput")(rec) is None
    assert reader("device_idle_share.tput")(rec) is None
    trace = SimpleNamespace(busy_s=1.5, op_seconds=lambda p: 0.25)
    rec.trace, rec.trace_t0, rec.trace_t1 = trace, 1.0, 3.0
    assert reader("device_idle_share.tput")(rec) == pytest.approx(25.0)
    need = flops.paged_attention_bytes(rec.dims, [30], 16, "fp32")
    assert reader("paged_attn_roofline.tput")(rec) == pytest.approx(
        100 * need / 1e11 / 0.25)


def test_flops_and_bytes_from_shapes():
    dm = dims(CFG)
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert flops.layer_matmul_params(dm) == per_layer
    # one token, no prefix: 1 key, head once
    assert flops.prefill_flops(dm, 1, 0) == pytest.approx(
        2 * 2 * per_layer + 4 * 1 * 64 * 2 + 2 * 64 * 100)
    assert flops.decode_flops(dm, [3]) == pytest.approx(
        2 * (2 * per_layer + 6400) + 4 * 3 * 64 * 2)
    # 17 tokens of context fill two 16-token pages, K and V, 2 heads of 16
    assert flops.paged_attention_bytes(dm, [17], 16, "fp32") == \
        2 * (2 * 32 * 2 * 16 * 4 + 2 * 4 * 16 * 2)
    assert flops.paged_attention_bytes(dm, [17], 16, "int4") == \
        2 * (2 * 32 * 2 * 16 * 0.5 + 2 * 32 * 2 * 4 + 2 * 4 * 16 * 2)
