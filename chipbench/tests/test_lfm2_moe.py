"""The LFM2-MoE configuration's parts of the benchmark, on the CPU: its
configuration file holds the program's published widths, the program's
parameters are the seeded bits the reference regenerates, ``correct``
passes the sound program and fails the float8 control at a small size,
and the two readers of its routed-expert counters compute what they
say."""
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, drive, run
from chipbench import traffic as traffic_mod
from chipbench.adapters import lfm2_moe as adapter
from chipbench.cell import BENCH, ROOT, Cell, load_json, reader
from chipbench.lfm2_flops import (decode_flops, expert_matmul_bytes,
                                  expert_row_flops, token_matmul_flops)
from chipbench.reference import lfm2_moe as ref
from chipbench.weights import base_key, canonical

CONFIG = ROOT / "chipbench" / "configs" / "lfm2-8b-a1b-ep2.json"
DATA = BENCH / "tests" / "data"
SEED = 7
#: Limits of the small configuration.  On three seeds the sound program
#: read gaps of 0.05-0.22 and misses of 1.5-2.6%, the control 0.34 and
#: 21-27% (CPU): the control fails by its misses.
LIMITS = {"greedy_gap": 0.5, "argmax_miss_pct": 8.0}


def test_configuration_is_the_programs_published_widths():
    from repro.configs import ARCHS
    cfg = json.loads(CONFIG.read_text())
    spec = adapter.program_spec(cfg)
    arch = ARCHS[cfg["program"]["arch"]]
    assert (spec.num_layers, spec.d_model, spec.num_heads, spec.num_kv_heads,
            spec.head_dim, spec.d_ff, spec.vocab_size, spec.layer_kinds(),
            spec.dense_layers, spec.conv_width, spec.norm_eps) == (
        arch.num_layers, arch.d_model, arch.num_heads, arch.num_kv_heads,
        arch.head_dim, arch.d_ff, arch.vocab_size, arch.layer_kinds(),
        arch.dense_layers, arch.conv_width, arch.norm_eps)
    m, a = spec.moe, arch.moe
    assert (m.num_experts, m.top_k, m.expert_ff) == (
        a.num_experts, a.top_k, a.expert_ff)
    # the one cut: 16 of the published 32 experts, the first ones
    assert cfg["reduced"] == ["num_experts"]
    assert cfg["published"]["num_experts"] == m.num_experts == 32
    assert m.held == (0, cfg["num_experts"]) == (0, 16)


def test_adapter_refuses_another_epsilon():
    cfg = json.loads(CONFIG.read_text())
    with pytest.raises(ValueError, match="epsilon"):
        adapter.program_spec(dict(cfg, norm_eps=1e-6))


def _tiny():
    cfg = load_json(DATA / "tiny-lfm2.json")
    spec = adapter.program_spec(cfg)
    key = base_key(SEED)
    return cfg, spec, key, adapter.program_params(cfg, spec, key)


def test_program_weights_are_the_seeded_bits():
    cfg, spec, key, p = _tiny()
    dm = ref.dims(cfg)
    conv_dense, attn_moe = p["groups"][0], p["groups"][2]
    assert jnp.array_equal(conv_dense["conv_w"][0],
                           canonical(key, "conv", 0, (dm.K, dm.d),
                                     shift=dm.shift("conv")))
    assert jnp.array_equal(attn_moe["k_norm"][0],
                           canonical(key, "k_norm", 2, (dm.D,)))
    wi = attn_moe["experts_wi"][0]
    assert jnp.array_equal(wi[..., dm.Fe:], canonical(
        key, "up", 2, (dm.n_held, dm.d, dm.Fe)))
    assert jnp.array_equal(attn_moe["expert_bias"][0],
                           canonical(key, "expert_bias", 2, (dm.E,)))
    assert jnp.array_equal(p["global"]["embed"], canonical(
        key, "embed", 0, (dm.V, dm.d), shift=dm.shift("embed")))
    assert "head" not in p["global"] and spec.tie_embeddings


def test_sound_program_passes_and_control_fails():
    cfg = load_json(DATA / "tiny-lfm2.json")
    cell = Cell(name="tiny-lfm2", chips=1, config=cfg,
                mix=load_json(DATA / "tiny-closed.json"), limits=LIMITS,
                end_to_end=[], per_layer=[])
    built = run.build(cell, SEED, jax.devices()[0])    # warms every shape
    w = drive.run_window(built.engine, built.backend, traffic_mod.Traffic(
        cell.mix, built.spec.vocab_size, SEED, 5.0), 5.0, drive.CompileLog())
    assert w.stats1["moe_layer_calls"] > w.stats0["moe_layer_calls"]
    uids = check.sample(w, SEED)
    found = check.readings(cell, built.key, w, uids, control=True)
    assert found["tokens"] >= 64, found
    assert check.judge(LIMITS, found["program"], len(uids))[0], found
    assert not check.judge(LIMITS, found["control"], len(uids))[0], found


def _record(stats0, stats1, spans, op_s=None, t=(0.0, 10.0)):
    dm = ref.dims(json.loads(CONFIG.read_text()))
    trace = None
    if op_s is not None:
        trace = SimpleNamespace(op_seconds=lambda pat: sum(
            s for n, s in op_s.items() if "ragged-dot" in n))
    window = SimpleNamespace(seconds=10.0, stats0=stats0, stats1=stats1,
                             spans=spans)
    return SimpleNamespace(dims=dm, window=window, trace=trace,
                           trace_t0=t[0], trace_t1=t[1],
                           peaks={"bf16_flops": 197e12,
                                  "hbm_bytes_per_s": 819e9})


def test_expert_readers_read_the_counters():
    roof, mfu = reader("moe_roofline.lfm2"), reader("mfu.lfm2")
    ctx = np.array([100, 200])
    spans = [drive.Span("decode", 1.0, 1.02, tokens=2, contexts=ctx)] * 10
    # a program without the counters: both readers are silent
    bare = _record({}, {}, spans, op_s={"ragged-dot-none": 0.1})
    assert roof(bare) is None and mfu(bare) is None
    dm = bare.dims
    n_moe = dm.L - dm.dense
    zero = {"moe_rows": 0, "moe_experts_hit": 0, "moe_layer_calls": 0}
    after = {"moe_rows": 10 * n_moe * 8, "moe_experts_hit": 10 * n_moe * 5,
             "moe_layer_calls": 10 * n_moe}
    rec = _record(zero, after, spans, op_s={"ragged-dot-none": 0.04,
                                            "ragged-dot-metadata": 0.01,
                                            "fusion": 9.0})
    need = 10 * n_moe * expert_matmul_bytes(dm, 5, 8)
    assert roof(rec) == pytest.approx(100 * need / 819e9 / 0.05)
    flops = 10 * decode_flops(dm, ctx) + after["moe_rows"] * expert_row_flops(
        dm)
    assert mfu(rec) == pytest.approx(100 * flops / (10.0 * 197e12))
    # one expert's gate, up and down weights dominate what a hit costs
    assert expert_matmul_bytes(dm, 1, 0) == 3 * dm.d * dm.Fe * 2
    assert token_matmul_flops(dm) > 0
