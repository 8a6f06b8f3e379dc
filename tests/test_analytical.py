"""Core analytical model: paper equations, generalized analysis, exactness
against the actual JAX model parameters."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, ASSIGNED, SHAPES
from repro.configs.edge_models import EDGE_MODELS
from repro.core import analytical, blocks
from repro.core.model_config import ModelSpec, ShapeSpec
from repro.core.precision import get as get_precision
from repro.models import lm


def test_eq7_param_count():
    # P = L·4H² + L·2HI + 2VH
    assert analytical.paper_param_count(22, 2048, 5632, 32000) == \
        22 * 4 * 2048 ** 2 + 22 * 2 * 2048 * 5632 + 2 * 32000 * 2048


def test_eq8_flops_per_token():
    L, H, I, S = 16, 1024, 4096, 2048
    expected = L * (6 * H * H + 4 * H * S + 4 * H * I + 4 * I * H + 9 * H)
    assert analytical.paper_flops_per_token(L, H, I, S) == expected


def test_eq9_memory():
    P, B, S, H, L = 1.1e9, 2.0, 2048, 2048, 22
    assert analytical.paper_memory(P, B, S, H, L) == pytest.approx(
        P * B + S * H * B + 2 * L * S * H * B)


def test_eq8_vs_generalized_accounting():
    """Paper eq. 8 uses idiosyncratic accounting (6H² for QKVO where the
    standard 2-FLOPs/MAC count gives 8H²; 4HI+4IH=8HI for the FF block
    where standard gives 4HI).  The generalized model uses the standard
    count; this test pins BOTH: the attention-context term (4HS) agrees
    exactly, and the known over/under-counts bound the total ratio.
    (Documented in DESIGN.md §1.)"""
    spec = ModelSpec(name="vanilla", family="dense", num_layers=8,
                     d_model=1024, num_heads=16, num_kv_heads=16,
                     d_ff=4096, vocab_size=32000, vocab_pad_multiple=1,
                     act="gelu")
    S = 2048
    ours = sum(blocks.layer_flops_per_token(spec, "attn", S)
               for _ in range(spec.num_layers))
    paper = analytical.paper_flops_per_token(
        spec.num_layers, spec.d_model, spec.d_ff, S)
    # attention-context term identical in both accountings (minus our
    # explicit softmax flops, which the paper folds into the 9H term)
    H = spec.d_model
    assert blocks.attention_flops_per_token(spec, S) - (
        2 * H * spec.q_dim + 4 * H * spec.kv_dim + 2 * spec.q_dim * H) \
        - 7 * spec.num_heads * S == pytest.approx(4 * H * S, rel=0.01)
    # paper over-counts FF 2x, under-counts QKVO -> ratio in a known band
    assert 0.6 < ours / paper < 0.9


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_param_count_matches_model_init(name):
    """Analytical parameter count is exact vs the materialized model."""
    spec = ASSIGNED[name].scaled_down(layers=4, width=64, vocab=128)
    params = lm.init(jax.random.PRNGKey(0), spec)
    assert lm.param_count_actual(params) == blocks.param_count(spec, padded=True)


def test_moe_active_params():
    spec = ASSIGNED["qwen2-moe-a2.7b"]
    active = blocks.active_param_count(spec)
    assert 2.4e9 < active < 3.0e9          # "A2.7B"
    assert blocks.param_count(spec, padded=False) > 14e9


def test_llama4_scout_totals():
    spec = ASSIGNED["llama4-scout-17b-a16e"]
    assert 16e9 < blocks.active_param_count(spec) < 18e9       # "17B"
    assert 100e9 < blocks.param_count(spec, padded=False) < 115e9


def test_decode_vs_train_model_flops():
    spec = ASSIGNED["glm4-9b"]
    prec = get_precision("bf16")
    tr = analytical.analyze(spec, SHAPES["train_4k"], prec)
    de = analytical.analyze(spec, SHAPES["decode_32k"], prec)
    # train: 6·N·tokens ; decode: 2·N·batch
    assert tr.model_flops == pytest.approx(6 * tr.params * SHAPES["train_4k"].tokens)
    assert de.model_flops == pytest.approx(2 * de.params * 128)


def test_kv_cache_scaling():
    spec = ASSIGNED["glm4-9b"]
    c1 = blocks.cache_bytes(spec, batch=1, max_seq=1024)
    c2 = blocks.cache_bytes(spec, batch=1, max_seq=2048)
    assert c2 == pytest.approx(2 * c1)


def test_sliding_window_caps_cache():
    g = ASSIGNED["gemma3-4b"]
    long_cache = blocks.cache_bytes(g, batch=1, max_seq=524_288)
    # local layers hold only the window: way below full-attention cost
    full = (g.num_layers * 2 * 524_288 * g.kv_dim * 2)
    assert long_cache < 0.25 * full


def test_ssm_cache_constant_in_seq():
    x = ASSIGNED["xlstm-350m"]
    assert blocks.cache_bytes(x, 1, 1024) == blocks.cache_bytes(x, 1, 524_288)


def test_collective_terms_scale_with_dp():
    spec = ASSIGNED["granite-3-8b"]
    prec = get_precision("bf16")
    a1 = analytical.analyze(spec, SHAPES["train_4k"], prec,
                            mesh=analytical.MeshShape(dp=16, tp=16))
    a2 = analytical.analyze(spec, SHAPES["train_4k"], prec,
                            mesh=analytical.MeshShape(dp=16, tp=16, pods=2))
    # more DP -> (n-1)/n grows slightly, per-device grad bytes unchanged
    assert a2.collectives.dp_grad > a1.collectives.dp_grad


def test_memory_fits_v5e_train():
    """Per-device training memory of the largest model must fit 16 GiB HBM
    under the production sharding. 109B params need FSDP (2-D weight
    sharding over model x data) on top of TP/EP — plain TP16 leaves
    13.7 GB of bf16 weights per chip."""
    spec = ASSIGNED["llama4-scout-17b-a16e"]
    prec = get_precision("bf16")
    dense = analytical.analyze(spec, SHAPES["train_4k"], prec,
                               mesh=analytical.MeshShape(dp=16, tp=16),
                               microbatch=1)
    assert dense.memory.total > 16 * 1024 ** 3        # TP-only does NOT fit
    fs = analytical.analyze(spec, SHAPES["train_4k"], prec,
                            mesh=analytical.MeshShape(dp=16, tp=16),
                            microbatch=1, fsdp=True)
    assert fs.memory.total < 16 * 1024 ** 3           # FSDP fits


def test_prefix_cache_savings_model():
    """Prefix-hit accounting: hits remove prefill FLOPs, templated-
    workload hit rates match the page-granular expectation."""
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    # 48 requests over 4 templates of 128 tokens: first of each is cold
    hit = analytical.expected_prefix_hit_tokens(48, 4, 128, 16)
    assert abs(hit - 128 * 44 / 48) < 1e-9
    # sharing is page-granular: an unaligned template floors to pages
    hit = analytical.expected_prefix_hit_tokens(48, 4, 120, 16)
    assert abs(hit - 112 * 44 / 48) < 1e-9
    hr = analytical.prefix_hit_rate(48, 4, 128, avg_prompt=160.0,
                                    page_size=16)
    assert 0.0 < hr < 1.0
    base = analytical.mixed_iteration_flops(spec, 128, 4, 200.0)
    cached = analytical.mixed_iteration_flops(spec, 64, 4, 200.0,
                                              cached_prefix_tokens=64)
    assert cached < base                    # hits skip projection FLOPs
    # cached tokens still shift the suffix attention span
    assert cached > analytical.mixed_iteration_flops(spec, 64, 4, 200.0)


def test_admission_occupancy_model():
    """Lazy allocation holds fewer pages per request than conservative
    admission, so the same pool sustains more concurrent requests."""
    lazy = analytical.mean_pages_held(64, 64, 16, "lazy")
    cons = analytical.mean_pages_held(64, 64, 16, "conservative")
    assert lazy < cons
    plan = analytical.PagedCachePlan(page_size=16, num_pages=33,
                                     page_bytes=1.0, bytes_per_token=1.0)
    el = analytical.effective_slots(plan, 16, 64, 64, "lazy")
    ec = analytical.effective_slots(plan, 16, 64, 64, "conservative")
    assert el > ec                          # 32 usable pages, 8 vs 6 held
    assert el <= 16
    with pytest.raises(ValueError):
        analytical.mean_pages_held(64, 64, 16, "eager")


def test_predict_serve_throughput_prefix_and_admission():
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import predict_serve_throughput
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    plan = analytical.PagedCachePlan(page_size=16, num_pages=129,
                                     page_bytes=4096.0,
                                     bytes_per_token=256.0)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    kw = dict(slots=8, avg_prompt=128.0, avg_new=32.0)
    base = predict_serve_throughput(spec, hw, prec, plan, **kw)
    warm = predict_serve_throughput(spec, hw, prec, plan,
                                    prefix_hit_rate=0.75, **kw)
    cons = predict_serve_throughput(spec, hw, prec, plan,
                                    admission="conservative", **kw)
    assert warm["continuous_tokens_per_s"] >= base["continuous_tokens_per_s"]
    assert warm["prefix_hit_rate"] == 0.75
    # conservative admission sustains fewer live slots on a tight pool
    assert cons["effective_slots"] <= base["effective_slots"]


def test_int4_paged_cache_bytes_in_paper_band():
    """int4 KV pages (0.5 B/value + per-token-per-head f32 scales) land
    inside the paper's "4-bit cuts memory 60-70%" band vs fp16-
    equivalent accounting on the test spec, and stay >= 60% on every
    assigned attention spec (the scale overhead is what keeps the
    reduction below a naive 8x-vs-fp32 story)."""
    spec = ASSIGNED["granite-3-8b"].scaled_down()      # head_dim 16
    b4, s4 = analytical.kv_cache_dtype_bytes("int4")
    fp16 = analytical.page_bytes(spec, 16, bytes_per=2.0)
    int4 = analytical.page_bytes(spec, 16, bytes_per=b4, quantized_scales=s4)
    red = 1.0 - int4 / fp16
    assert 0.60 <= red <= 0.70
    for name, s in ASSIGNED.items():
        if not s.num_attention_layers():
            continue
        fp16 = analytical.page_bytes(s, 16, bytes_per=2.0)
        int4 = analytical.page_bytes(s, 16, bytes_per=b4, quantized_scales=s4)
        assert 0.60 <= 1.0 - int4 / fp16 <= 0.75, name
    with pytest.raises(ValueError):
        analytical.kv_cache_dtype_bytes("fp64")


def test_predict_serve_throughput_consumes_cache_dtype_bytes():
    """plan_for_layout(cache_dtype=) orders the per-token byte terms
    fp32 > int8 > int4 and the predicted memory-bound continuous
    throughput improves monotonically as the KV stream narrows."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import predict_serve_throughput
    from repro.serve.paged_cache import plan_for_layout
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    layout = lm.PagedLayout(num_pages=257, page_size=16, pages_per_slot=32)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    plans = {d: plan_for_layout(spec, layout, d)
             for d in ("fp32", "int8", "int4")}
    assert plans["fp32"].bytes_per_token > plans["int8"].bytes_per_token \
        > plans["int4"].bytes_per_token
    tps = {d: predict_serve_throughput(
        spec, hw, prec, p, slots=8, avg_prompt=256.0, avg_new=64.0)
        ["continuous_tokens_per_s"] for d, p in plans.items()}
    assert tps["int4"] >= tps["int8"] >= tps["fp32"]


def test_expected_accepted_tokens():
    """Truncated-geometric emission count of one speculative verify
    window: 1 committed token plus the accepted draft prefix."""
    ea = analytical.expected_accepted_tokens
    assert ea(0.0, 1) == 1.0
    assert ea(0.0, 4) == 1.0               # every draft rejected
    assert ea(1.0, 4) == 4.0               # every draft accepted
    a = 0.5
    assert ea(a, 4) == pytest.approx(1 + a + a ** 2 + a ** 3)
    assert ea(0.9, 8) > ea(0.9, 4) > ea(0.9, 2) > 1.0
    assert ea(-0.3, 4) == 1.0 and ea(1.7, 4) == 4.0      # clamped
    with pytest.raises(ValueError):
        ea(0.5, 0)


def test_spec_decode_throughput_model():
    """spec_k amortizes the per-iteration weight+KV stream over every
    accepted token: predicted continuous tokens/s grows monotonically
    with the acceptance rate, never exceeds the spec_k x bound, and an
    all-rejected run pays the extra verify FLOPs for nothing."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import mixed_iteration_cost, predict_serve_throughput
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    plan = analytical.PagedCachePlan(page_size=16, num_pages=257,
                                     page_bytes=4096.0, bytes_per_token=256.0)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    kw = dict(slots=8, avg_prompt=128.0, avg_new=64.0)
    base = predict_serve_throughput(spec, hw, prec, plan, **kw)
    tps = {a: predict_serve_throughput(
        spec, hw, prec, plan, spec_k=4, acceptance_rate=a, **kw)
        for a in (0.0, 0.5, 0.9)}
    assert tps[0.0]["continuous_tokens_per_s"] <= \
        base["continuous_tokens_per_s"]
    assert tps[0.5]["continuous_tokens_per_s"] > \
        tps[0.0]["continuous_tokens_per_s"]
    assert tps[0.9]["continuous_tokens_per_s"] > \
        tps[0.5]["continuous_tokens_per_s"]
    assert tps[0.9]["continuous_tokens_per_s"] < \
        4 * base["continuous_tokens_per_s"]
    assert tps[0.9]["spec_k"] == 4.0
    assert tps[0.9]["expected_tokens_per_step"] == pytest.approx(
        analytical.expected_accepted_tokens(0.9, 4))
    assert "spec_k" not in base
    # iteration-level: the window multiplies FLOPs, not page reads
    c1 = mixed_iteration_cost(spec, hw, prec, plan, prefill_tokens=0,
                              decode_slots=8, avg_context=160.0)
    c4 = mixed_iteration_cost(spec, hw, prec, plan, prefill_tokens=0,
                              decode_slots=8, avg_context=160.0,
                              spec_k=4, acceptance_rate=0.8)
    assert c4.flops == pytest.approx(4 * c1.flops)
    assert c4.bytes_moved < 1.02 * c1.bytes_moved
    assert c4.decode_tokens == pytest.approx(
        8 * analytical.expected_accepted_tokens(0.8, 4))
    with pytest.raises(ValueError):
        mixed_iteration_cost(spec, hw, prec, plan, prefill_tokens=0,
                             decode_slots=8, avg_context=160.0, spec_k=0)


def test_serve_energy_per_token_int4_band():
    """Abstract: 'Power modeling estimates a 35-50% reduction in energy
    consumption for INT4 configurations' (vs the FP16 baseline).  The
    serve-level energy model — eq. (15) dynamic terms + the static
    board-power floor over the iteration + llama.cpp-style dequant
    compute overhead for weight-only INT4 — lands INSIDE the measured
    band on both Raspberry Pi targets at the continuous-batching
    operating points (the dynamic-only profiler path asserts the looser
    0.35-0.75 band in test_paper_validation.py)."""
    from repro.configs.edge_models import TINYLLAMA
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import predict_serve_throughput
    from repro.serve.paged_cache import plan_for_layout
    layout = lm.PagedLayout(num_pages=513, page_size=16, pages_per_slot=64)
    for hw_name in ("rpi4", "rpi5"):
        hw = hardware.get(hw_name)
        assert hw.p_static > 0.0
        for slots in (4, 8):
            kw = dict(slots=slots, avg_prompt=128.0, avg_new=64.0)
            e = {}
            for prec_name, cache_dtype in (("fp16", "fp32"),
                                           ("fp32", "fp32"),
                                           ("int4", "int4")):
                plan = plan_for_layout(TINYLLAMA, layout, cache_dtype)
                r = predict_serve_throughput(
                    TINYLLAMA, hw, prec_mod.get(prec_name), plan, **kw)
                assert r["energy_j_per_token"] > 0.0
                e[prec_name] = r["energy_j_per_token"]
            red = 1.0 - e["int4"] / e["fp16"]
            assert 0.35 <= red <= 0.50, (hw_name, slots, red)
            # vs fp32 the saving is bigger but still bounded by the
            # static floor + dequant overhead, not the naive 8x bytes
            assert e["fp32"] > e["fp16"] > e["int4"]
            assert 1.0 - e["int4"] / e["fp32"] < 0.75


def test_scale_page_tile_bytes_lane_major_wins():
    """Lane-major (KV, page) scale blocks occupy one (8, 128) f32 tile
    per page; the old row-major (page, KV, 1) layout padded a tile PER
    TOKEN — 16x more physical bytes at KV=2, page=16."""
    lane = analytical.scale_page_tile_bytes(2, 16)
    row = analytical.scale_page_tile_bytes(2, 16, layout="row_major")
    assert lane == 8 * 128 * 4.0
    assert row == 16 * 8 * 128 * 4.0
    assert row / lane == 16.0
    # logical bytes are a lower bound on both layouts
    assert lane >= 2 * 16 * 4.0
    with pytest.raises(ValueError):
        analytical.scale_page_tile_bytes(2, 16, layout="bogus")


def test_tensor_parallel_page_budget_and_throughput():
    """tp threading: page_bytes(tp=) is the per-device KV-head share,
    plan_paged_cache(tp=) turns the same per-device budget into ~tp x
    more pages, and predict_serve_throughput(tp=) divides weight AND
    KV traffic (and FLOPs) by tp while charging the megatron psum
    against the network link — so scaling is monotone but capped below
    linear wherever the collective term binds."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import mixed_iteration_cost, predict_serve_throughput
    spec = ASSIGNED["granite-3-8b"].scaled_down()   # KV=4 after scaling
    pb1 = analytical.page_bytes(spec, 16, bytes_per=1.0,
                                quantized_scales=True)
    pb4 = analytical.page_bytes(spec, 16, bytes_per=1.0,
                                quantized_scales=True, tp=4)
    assert pb4 == pytest.approx(pb1 / 4)
    plan1 = analytical.plan_paged_cache(spec, 1e6, bytes_per=1.0,
                                        quantized_scales=True)
    plan4 = analytical.plan_paged_cache(spec, 1e6, bytes_per=1.0,
                                        quantized_scales=True, tp=4)
    assert 4 * plan1.num_pages <= plan4.num_pages < 4 * (plan1.num_pages + 1)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    kw = dict(slots=8, avg_prompt=256.0, avg_new=64.0)
    base = predict_serve_throughput(spec, hw, prec, plan1, **kw)
    tp4 = predict_serve_throughput(spec, hw, prec, plan1, tp=4, **kw)
    # never better than linear; and for a SCALED-DOWN model over 1 GbE
    # the model must predict that TP LOSES — the psum payload does not
    # shrink with the weights, so a tiny model's collective swamps its
    # 1/tp traffic saving (don't TP toy models over slow links)
    assert tp4["continuous_tokens_per_s"] <= \
        4 * base["continuous_tokens_per_s"] + 1e-9
    assert tp4["continuous_tokens_per_s"] < base["continuous_tokens_per_s"]
    assert tp4["per_device_pool_bytes"] == pytest.approx(
        plan1.total_bytes / 4)
    assert 0.0 <= tp4["per_device_pool_occupancy"] <= 1.0
    assert "per_device_pool_bytes" not in base
    assert "tokens_per_s_per_device" in tp4 and \
        "cost_per_million_tokens" in tp4

    # the megatron all-reduce caps scaling below linear when the link
    # is the bottleneck: full-size granite on the jetson's fast memory
    # but 10 GbE-class link is exactly that regime
    big = ASSIGNED["granite-3-8b"]
    jet, fp16 = hardware.get("jetson_orin_nano"), prec_mod.get("fp16")
    bplan = analytical.plan_paged_cache(big, 2e9, bytes_per=2.0)
    c1 = mixed_iteration_cost(big, jet, fp16, bplan, prefill_tokens=64,
                              decode_slots=8, avg_context=288.0)
    c4 = mixed_iteration_cost(big, jet, fp16, bplan, prefill_tokens=64,
                              decode_slots=8, avg_context=288.0, tp=4)
    assert c1.collective_s == 0.0
    assert c4.collective_s > 0.0
    assert c4.iteration_s == pytest.approx(c4.collective_s)  # link-bound
    assert c1.tokens_per_s < c4.tokens_per_s < 4 * c1.tokens_per_s
    # cluster totals, not per-shard: the energy model bills all devices
    assert c4.flops == pytest.approx(c1.flops)
    assert c4.bytes_moved == pytest.approx(c1.bytes_moved)
    # a per-device plan (built with tp=) plus a tp= knob would divide
    # the pool bytes twice — rejected, not silently overstated
    assert plan4.tp == 4
    with pytest.raises(ValueError):
        predict_serve_throughput(spec, hw, prec, plan4, tp=4, **kw)

    # a tp that does NOT divide the head counts replicates the pools
    # (the sharding-layer fallback), so the per-device share must stay
    # the FULL page — pricing a shard would let budget-driven layouts
    # overshoot the device by up to tp x
    odd = spec.with_(num_heads=6, num_kv_heads=3)
    assert not analytical.tp_shards_kv(odd, 4)
    assert analytical.tp_shards_kv(spec, 4)
    pb_odd = analytical.page_bytes(odd, 16, bytes_per=1.0,
                                   quantized_scales=True)
    assert analytical.page_bytes(odd, 16, bytes_per=1.0,
                                 quantized_scales=True, tp=4) == pb_odd
    plan_odd = analytical.plan_paged_cache(odd, 1e6, bytes_per=1.0,
                                           quantized_scales=True)
    tp4_odd = predict_serve_throughput(odd, hw, prec, plan_odd, tp=4, **kw)
    assert tp4_odd["per_device_pool_bytes"] == pytest.approx(
        plan_odd.total_bytes)
    # ... and replicated weights too: no tp win at all for the odd spec
    assert not analytical.tp_shards_weights(odd, 4)
    base_odd = predict_serve_throughput(odd, hw, prec, plan_odd, **kw)
    assert tp4_odd["continuous_tokens_per_s"] == pytest.approx(
        base_odd["continuous_tokens_per_s"])


def test_dp_replicas_and_cluster_grid():
    """dp threading: replicas are independent engines, so dp multiplies
    the aggregate rate and slots without touching the per-replica cell;
    the tp x dp grid carries per-device rate + cost-per-million-tokens
    everywhere and its tp=1, dp=1 cell matches the bare prediction."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import (cost_per_million_tokens,
                                    predict_serve_throughput,
                                    serve_cluster_grid)
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    hw, prec = hardware.get("rpi5"), prec_mod.get("int4")
    plan = analytical.plan_paged_cache(spec, 1e6, bytes_per=0.5,
                                       quantized_scales=True)
    kw = dict(slots=8, avg_prompt=256.0, avg_new=64.0)
    base = predict_serve_throughput(spec, hw, prec, plan, **kw)
    # the pre-cluster cell is untouched: no tp/dp keys leak in
    for k in ("tp", "dp", "aggregate_tokens_per_s", "cluster_slots",
              "tokens_per_s_per_device", "cost_per_million_tokens"):
        assert k not in base, k
    dp2 = predict_serve_throughput(spec, hw, prec, plan, dp=2, **kw)
    assert dp2["continuous_tokens_per_s"] == pytest.approx(
        base["continuous_tokens_per_s"])
    assert dp2["aggregate_tokens_per_s"] == pytest.approx(
        2 * base["continuous_tokens_per_s"])
    assert dp2["cluster_slots"] == pytest.approx(2 * base["effective_slots"])
    assert dp2["tokens_per_s_per_device"] == pytest.approx(
        base["continuous_tokens_per_s"])

    grid = serve_cluster_grid(spec, hw, prec, plan, tps=(1, 2), dps=(1, 2),
                              **kw)
    assert len(grid) == 4
    cell11 = next(r for r in grid if r["tp"] == 1 and r["dp"] == 1)
    assert cell11["continuous_tokens_per_s"] == pytest.approx(
        base["continuous_tokens_per_s"])
    assert cell11["energy_j_per_token"] == pytest.approx(
        base["energy_j_per_token"])
    for r in grid:
        assert r["tokens_per_s_per_device"] == pytest.approx(
            r["aggregate_tokens_per_s"] / r["devices"])
        assert r["cost_per_million_tokens"] > 0
    # devices cost money: at equal aggregate rate, more devices can
    # never be cheaper
    assert cost_per_million_tokens(10.0, 4, 0.0, hw) > \
        cost_per_million_tokens(10.0, 2, 0.0, hw)


def test_chunked_prefill_latency_decomposition():
    """``chunk_tokens`` mirrors the scheduler's chunked-prefill budget:
    the worst (admission-burst) iteration's ITL drops, TTFT pays for it
    in ceil(suffix/chunk) chunk iterations, and the steady-state ITL is
    untouched — the exact trade the open-loop benchmark measures."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import mixed_iteration_cost, predict_serve_throughput
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    plan = analytical.PagedCachePlan(page_size=16, num_pages=129,
                                     page_bytes=4096.0,
                                     bytes_per_token=256.0)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    kw = dict(slots=8, avg_prompt=256.0, avg_new=32.0)
    base = predict_serve_throughput(spec, hw, prec, plan, **kw)
    chunked = predict_serve_throughput(spec, hw, prec, plan,
                                       chunk_tokens=64, **kw)
    # every call carries the decomposition
    for out in (base, chunked):
        assert out["predicted_itl_s"] > 0
        assert out["predicted_itl_worst_s"] >= out["predicted_itl_s"]
        assert out["predicted_ttft_s"] > 0
    # unchunked: one burst iteration carrying the whole 256-token prompt
    assert base["predicted_ttft_s"] == base["predicted_itl_worst_s"]
    assert "chunk_tokens" not in base
    # chunked: flatter worst iteration, ceil(256/64)=4 chunk iterations
    assert chunked["predicted_itl_worst_s"] < base["predicted_itl_worst_s"]
    assert chunked["prefill_chunks_per_request"] == 4.0
    assert chunked["chunk_tokens"] == 64.0
    assert chunked["predicted_ttft_s"] == pytest.approx(
        4 * chunked["predicted_itl_worst_s"]
        * analytical.expected_accepted_tokens(0.0, 1))
    # TTFT stays in the burst's ballpark: the model has no per-
    # iteration dispatch cost (the measured open-loop TTFT pays one
    # per chunk), and the burst's superlinear attention term can even
    # edge the n-chunk sum slightly below it — chunking buys its worst-
    # ITL cut without a large analytical TTFT regression, not for free
    assert chunked["predicted_ttft_s"] >= 0.9 * base["predicted_ttft_s"]
    assert chunked["predicted_itl_s"] == pytest.approx(
        base["predicted_itl_s"])
    # prefix hits shrink the burst both ways
    warm = predict_serve_throughput(spec, hw, prec, plan,
                                    prefix_hit_rate=0.75, chunk_tokens=64,
                                    **kw)
    assert warm["prefill_chunks_per_request"] == 1.0


def test_mixed_iteration_cost_chunk_cap():
    """``mixed_iteration_cost(chunk_tokens=)`` clamps the prefill term:
    capped cost <= uncapped, equal when the burst already fits, and a
    non-positive cap is rejected."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import mixed_iteration_cost
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    plan = analytical.PagedCachePlan(page_size=16, num_pages=129,
                                     page_bytes=4096.0,
                                     bytes_per_token=256.0)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    kw = dict(decode_slots=8, avg_context=128.0)
    full = mixed_iteration_cost(spec, hw, prec, plan,
                                prefill_tokens=512, **kw)
    capped = mixed_iteration_cost(spec, hw, prec, plan,
                                  prefill_tokens=512, chunk_tokens=64, **kw)
    same = mixed_iteration_cost(spec, hw, prec, plan,
                                prefill_tokens=32, chunk_tokens=64, **kw)
    uncapped_small = mixed_iteration_cost(spec, hw, prec, plan,
                                          prefill_tokens=32, **kw)
    assert capped.iteration_s < full.iteration_s
    assert same.iteration_s == uncapped_small.iteration_s
    with pytest.raises(ValueError):
        mixed_iteration_cost(spec, hw, prec, plan, prefill_tokens=64,
                             chunk_tokens=0, **kw)


def test_failover_recovery_cost_regimes():
    """EdgeProfiler's traffic methodology (bytes over a link vs FLOPs
    over a roofline) applied to failover: on a 1 GbE edge board a real
    8B model's context migrates orders of magnitude cheaper than it
    re-prefills, while a tiny model on an ICI-linked accelerator flips
    to the re-prefill regime — and narrowing the cache dtype shrinks
    the migrate term monotonically (quantization changes WHICH regime
    is cheap, not just how cheap)."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import failover_recovery_cost
    from repro.serve.paged_cache import plan_for_layout
    layout = lm.PagedLayout(num_pages=257, page_size=16, pages_per_slot=32)
    full, toy = ASSIGNED["granite-3-8b"], ASSIGNED["granite-3-8b"].scaled_down()
    kw = dict(context_tokens=512.0)

    edge = failover_recovery_cost(full, hardware.get("rpi5"),
                                  prec_mod.get("int4"),
                                  plan_for_layout(full, layout, "int4"), **kw)
    assert edge["cheaper"] == "migrate"
    assert edge["migrate_s"] * 10 < edge["reprefill_s"]
    assert edge["recovery_s"] == edge["migrate_s"]

    ici = failover_recovery_cost(toy, hardware.get("tpu_v5e"),
                                 prec_mod.get("fp32"),
                                 plan_for_layout(toy, layout, "fp32"), **kw)
    assert ici["cheaper"] == "reprefill"
    assert ici["recovery_s"] == ici["reprefill_s"]

    # dtype monotonicity on one board: int4 pages are ~1/8 the bytes
    hw = hardware.get("rpi5")
    m = {d: failover_recovery_cost(full, hw, prec_mod.get(d),
                                   plan_for_layout(full, layout, d),
                                   **kw)["migrate_s"]
         for d in ("fp32", "int8", "int4")}
    assert m["int4"] < m["int8"] < m["fp32"]
    # bytes scale linearly in context; zero context migrates for free
    zero = failover_recovery_cost(full, hw, prec_mod.get("fp32"),
                                  plan_for_layout(full, layout, "fp32"),
                                  context_tokens=0.0)
    assert zero["migrate_bytes"] == 0.0 and zero["migrate_s"] == 0.0
    with pytest.raises(ValueError):
        failover_recovery_cost(full, hw, prec_mod.get("fp32"),
                               plan_for_layout(full, layout, "fp32"),
                               context_tokens=-1.0)


def test_swap_vs_recompute_crossover():
    """The host-tier trade behind the scheduler's evict→swap→preempt
    escalation: int4 pages round-tripping the boards' own h2d links
    beat re-prefill on every paper edge board (quantization is what
    makes the swap tier pay), while fp32 pages over a throttled link
    on the Jetson — fast compute, slow copy path — flip back to the
    recompute regime."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import swap_vs_recompute
    from repro.serve.paged_cache import plan_for_layout
    layout = lm.PagedLayout(num_pages=257, page_size=16, pages_per_slot=32)
    full = ASSIGNED["granite-3-8b"]
    kw = dict(context_tokens=512.0)

    for board in ("rpi4", "rpi5", "jetson_orin_nano"):
        r = swap_vs_recompute(full, hardware.get(board),
                              prec_mod.get("int4"),
                              plan_for_layout(full, layout, "int4"), **kw)
        assert r["cheaper"] == "swap", board
        assert r["swap_s"] * 10 < r["reprefill_s"], board

    slow = hardware.get("jetson_orin_nano").with_(h2d_bw=50e6)
    r = swap_vs_recompute(full, slow, prec_mod.get("fp32"),
                          plan_for_layout(full, layout, "fp32"), **kw)
    assert r["cheaper"] == "reprefill"

    # dtype monotonicity on one board: int4 pages are ~1/8 the bytes
    hw = hardware.get("rpi5")
    s = {d: swap_vs_recompute(full, hw, prec_mod.get(d),
                              plan_for_layout(full, layout, d), **kw)["swap_s"]
         for d in ("fp32", "int8", "int4")}
    assert s["int4"] < s["int8"] < s["fp32"]

    # transfers move WHOLE pages (the backend's gather/scatter
    # granularity): one token still pays one page each way, and the
    # host tier holds host_mem_capacity / swap_bytes such contexts
    plan = plan_for_layout(full, layout, "fp32")
    one = swap_vs_recompute(full, hw, prec_mod.get("fp32"), plan,
                            context_tokens=1.0)
    assert one["swap_bytes"] == plan.page_bytes
    assert one["swap_s"] == one["swap_out_s"] + one["swap_in_s"]
    assert one["host_capacity_contexts"] == (hw.host_mem_capacity
                                             / plan.page_bytes)
    zero = swap_vs_recompute(full, hw, prec_mod.get("fp32"), plan,
                             context_tokens=0.0)
    assert zero["swap_bytes"] == 0.0
    assert zero["host_capacity_contexts"] == float("inf")
    with pytest.raises(ValueError):
        swap_vs_recompute(full, hw, prec_mod.get("fp32"), plan,
                          context_tokens=-1.0)


def test_predict_serve_throughput_parked_context():
    """``parked_context_tokens`` threads the swap crossover into the
    serve prediction: the result gains the resume-vs-recompute TTFT
    pair the ``--swap`` gate prints against, absent without the
    kwarg, and on an edge board with int4 pages the parked resume is
    predicted cheaper."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import predict_serve_throughput
    from repro.serve.paged_cache import plan_for_layout
    layout = lm.PagedLayout(num_pages=257, page_size=16, pages_per_slot=32)
    full = ASSIGNED["granite-3-8b"]
    hw = hardware.get("rpi5")
    plan = plan_for_layout(full, layout, "int4")
    kw = dict(slots=4, avg_prompt=128.0, avg_new=64.0)
    base = predict_serve_throughput(full, hw, prec_mod.get("int4"), plan,
                                    **kw)
    assert "swap_in_s" not in base and "swap_cheaper" not in base
    out = predict_serve_throughput(full, hw, prec_mod.get("int4"), plan,
                                   parked_context_tokens=256.0, **kw)
    assert out["parked_context_tokens"] == 256.0
    assert out["swap_cheaper"] == 1.0
    assert out["predicted_resume_ttft_s"] < out["predicted_recompute_ttft_s"]
    # both TTFTs share the admission iteration; the gap is the leg cost
    assert (out["predicted_recompute_ttft_s"]
            - out["predicted_resume_ttft_s"]) == pytest.approx(
        out["reprefill_s"] - out["swap_in_s"])
    # the throughput cells themselves are untouched by the kwarg
    assert out["continuous_tokens_per_s"] == base["continuous_tokens_per_s"]


def test_serve_availability_capacity_and_recovery():
    """Replicas are independent engines, so ``failed`` of ``dp`` dead
    leaves exactly the survivors' share of capacity, the survivors see
    ``dp/(dp-failed)`` of their load, and recovery charges one
    ``failover_recovery_cost`` per live slot the dead replicas held."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import serve_availability
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    plan = analytical.PagedCachePlan(page_size=16, num_pages=129,
                                     page_bytes=4096.0,
                                     bytes_per_token=256.0)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    kw = dict(slots=8, avg_prompt=128.0, avg_new=32.0)
    av = serve_availability(spec, hw, prec, plan, dp=4, failed=1, **kw)
    assert av["survivors"] == 3.0
    assert av["capacity_fraction"] == pytest.approx(0.75)
    assert av["load_multiplier"] == pytest.approx(4 / 3)
    assert av["degraded_tokens_per_s"] == pytest.approx(
        0.75 * av["aggregate_tokens_per_s"])
    # mean failover context: full prompt + half the output
    assert av["failover_context_tokens"] == pytest.approx(128 + 16)
    assert av["recovery_s_total"] == pytest.approx(
        av["failover_requests"] * av["recovery_s_per_request"])
    assert av["recovery_s_per_request"] == av["recovery_recovery_s"] > 0
    assert av["recovery_cheaper"] in ("migrate", "reprefill")

    healthy = serve_availability(spec, hw, prec, plan, dp=4, failed=0, **kw)
    assert healthy["capacity_fraction"] == pytest.approx(1.0)
    assert healthy["load_multiplier"] == 1.0
    assert healthy["failover_requests"] == 0.0
    assert healthy["recovery_s_total"] == 0.0

    with pytest.raises(ValueError):
        serve_availability(spec, hw, prec, plan, dp=4, failed=4, **kw)
    with pytest.raises(ValueError):
        serve_availability(spec, hw, prec, plan, dp=4, failed=-1, **kw)
    with pytest.raises(ValueError):
        serve_availability(spec, hw, prec, plan, dp=0, failed=0, **kw)


def test_serve_availability_goodput_clips_to_degraded_capacity():
    """Offered load below degraded capacity is fully served; above it,
    goodput clips to what the survivors can actually push — matching
    how the open-loop chaos benchmark counts goodput."""
    from repro.core import hardware, precision as prec_mod
    from repro.core.latency import serve_availability
    spec = ASSIGNED["granite-3-8b"].scaled_down()
    plan = analytical.PagedCachePlan(page_size=16, num_pages=129,
                                     page_bytes=4096.0,
                                     bytes_per_token=256.0)
    hw, prec = hardware.get("rpi5"), prec_mod.get("fp32")
    kw = dict(slots=8, avg_prompt=128.0, avg_new=32.0, dp=2, failed=1)
    cap = serve_availability(spec, hw, prec, plan, **kw)
    light = serve_availability(spec, hw, prec, plan,
                               offered_tokens_per_s=cap[
                                   "degraded_tokens_per_s"] / 2, **kw)
    assert light["goodput_fraction"] == pytest.approx(1.0)
    assert light["goodput_tokens_per_s"] == light["offered_tokens_per_s"]
    heavy = serve_availability(spec, hw, prec, plan,
                               offered_tokens_per_s=cap[
                                   "degraded_tokens_per_s"] * 2, **kw)
    assert heavy["goodput_tokens_per_s"] == pytest.approx(
        cap["degraded_tokens_per_s"])
    assert heavy["goodput_fraction"] == pytest.approx(0.5)


def test_lfm2_moe_counts_and_serve_terms():
    """LFM2-8B-A1B: published totals, the held share's parameters and
    active FLOPs, leading dense layers, the weights a decode step
    streams (expected distinct held experts hit at a batch) and the
    per-slot conv state."""
    import dataclasses
    from repro.core.hardware import TPU_V5E
    from repro.core.latency import mixed_iteration_cost
    full = ARCHS["lfm2-8b-a1b"]
    assert 8.3e9 < blocks.param_count(full, padded=False) < 8.4e9     # 8.3B
    assert 1.5e9 < blocks.active_param_count(full) < 1.6e9          # A1.5B
    spec = full.with_(moe=dataclasses.replace(full.moe, held=(0, 16)))
    assert blocks.param_count(spec, padded=False) == pytest.approx(4.464e9,
                                                                  rel=1e-3)
    assert [spec.moe_layer(i) for i in range(4)] == [False, False, True,
                                                     True]
    per_expert = 3 * 2048 * 1792
    # each token computes top_k * held / num_experts = 2 experts here
    moe = blocks.moe_flops_per_token(spec)
    assert moe == pytest.approx(2 * 2048 * 32 + 2 * (2 * per_expert
                                                     + 4 * 1792))
    # a held expert is missed by all 32 rows with probability (7/8)**32
    hit = analytical.expected_experts_hit(spec, 32)
    assert hit == pytest.approx(16 * (1 - (7 / 8) ** 32))
    P = blocks.param_count(spec, padded=False)
    assert analytical.streamed_params(spec, 32) == pytest.approx(
        P - 22 * (16 - hit) * per_expert)
    assert analytical.streamed_params(spec, 1) < 0.3 * P
    assert analytical.conv_state_bytes(spec, 32) == 32 * 18 * 2 * 2048 * 4
    plan = analytical.PagedCachePlan(page_size=16, num_pages=8193,
                                     page_bytes=393216.0,
                                     bytes_per_token=393216.0 / 16)
    c = mixed_iteration_cost(spec, TPU_V5E, get_precision("bf16"), plan,
                             prefill_tokens=0, decode_slots=32,
                             avg_context=1000)
    want = (2 * analytical.streamed_params(spec, 32)
            + plan.bytes_per_token * 32 * 1000
            + 2 * analytical.conv_state_bytes(spec, 32))
    assert c.memory_s == pytest.approx(want / (TPU_V5E.mem_bw
                                               * TPU_V5E.u_memory))
    # the dense stack is unchanged: every weight streams every step
    dense = ASSIGNED["granite-3-8b"]
    assert analytical.streamed_params(dense, 1) == blocks.param_count(
        dense, padded=False)
