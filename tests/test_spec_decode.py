"""Self-speculative decoding: n-gram draft table semantics, multi-query
paged-attention kernel/ref parity, window-vs-sequential logit identity,
and end-to-end scheduler equivalence (spec_k > 1 must be token-for-token
the spec_k = 1 greedy engine for every cache dtype, including under
preemption).

The one invariant everything here defends: speculation changes HOW MANY
tokens an iteration commits, never WHICH tokens.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED
from repro.kernels import ops, ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import lm
from repro.quant.quantize import (lane_major_scales, pack_int4,
                                  quantize_kv_int4, quantize_kv_int8)
from repro.serve import paged_cache as pc
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   SchedulerConfig)
from repro.serve.spec_decode import NGramDraftTable

from tolerance import assert_acceptance_in_band


# ---------------------------------------------------------------------------
# Draft table
# ---------------------------------------------------------------------------

def test_ngram_table_lookup_semantics():
    t = NGramDraftTable(2)
    t.extend([5, 7, 9, 5, 7])
    # last 2-gram (5, 7) occurred before at end-position 1 -> continue
    # with the tokens that followed it: 9, 5
    assert t.propose(2) == [9, 5]
    assert t.propose(0) == []
    # novel tail -> miss
    t.extend([42])
    assert t.propose(3) == []


def test_ngram_table_periodic_extrapolation():
    """A short-period repeating tail must fill a window wider than the
    period (the proposal continues from itself)."""
    t = NGramDraftTable(2)
    t.extend([1, 2, 3, 1, 2, 3, 1, 2])
    # prior (1, 2) ends at position 4 -> continuation 3, 1, 2, then
    # periodic extrapolation 3, 1, 2, ...
    assert t.propose(7) == [3, 1, 2, 3, 1, 2, 3]


def test_ngram_table_validation_and_len():
    with pytest.raises(ValueError):
        NGramDraftTable(0)
    t = NGramDraftTable(3)
    assert t.propose(4) == []          # fewer tokens than the gram size
    t.extend([1, 2])
    assert len(t) == 2 and t.propose(4) == []


# ---------------------------------------------------------------------------
# Multi-query paged attention: kernel vs ref, window vs single-query
# ---------------------------------------------------------------------------

def _quantize_pools(quant, kf, vf):
    if quant == "fp32":
        return kf, vf, None, None
    if quant == "int8":
        k8, ks = quantize_kv_int8(kf)
        v8, vs = quantize_kv_int8(vf)
        return k8, v8, lane_major_scales(ks), lane_major_scales(vs)
    k4, ks = quantize_kv_int4(kf)
    v4, vs = quantize_kv_int4(vf)
    return (pack_int4(k4, axis=1), pack_int4(v4, axis=1),
            lane_major_scales(ks), lane_major_scales(vs))


def _window_fixture(seed=0, B=4, K=3, H=4, KV=2, D=16, page=8, pps=4):
    rng = np.random.default_rng(seed)
    P = B * pps + 1
    q = jnp.asarray(rng.normal(size=(B, K, H, D)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[:B * pps].reshape(B, pps), jnp.int32)
    # lengths INCLUDE the window; one slot whose context is only the
    # window itself (length == K) pins the base == 0 edge
    lengths = jnp.asarray([5, 21, K, 26], jnp.int32)
    return q, kf, vf, bt, lengths


@pytest.mark.parametrize("quant,tol", [("fp32", 1e-6), ("int8", 1e-5),
                                       ("int4", 1e-4)])
@pytest.mark.parametrize("window", [0, 7])
def test_window_kernel_matches_ref(quant, tol, window):
    """The K-query Pallas body (interpret mode) against the gather ref,
    all cache dtypes, causal-inside-window + sliding window."""
    q, kf, vf, bt, lengths = _window_fixture()
    kp, vp, ks, vs = _quantize_pools(quant, kf, vf)
    o_ref = ref.paged_attention_ref(q, kp, vp, bt, lengths, window=window,
                                    k_scale=ks, v_scale=vs)
    o_pal = paged_attention_pallas(q, kp, vp, bt, lengths, window=window,
                                   k_scale=ks, v_scale=vs, interpret=True)
    assert o_ref.shape == q.shape
    assert float(jnp.max(jnp.abs(o_pal - o_ref))) <= tol


@pytest.mark.parametrize("quant", ["fp32", "int8", "int4"])
def test_window_ref_reduces_to_single_query(quant):
    """Query j of a K-window == the single-query op at the truncated
    length — the causal-inside-window contract, exactly."""
    K = 3
    q, kf, vf, bt, lengths = _window_fixture(seed=7, K=K)
    kp, vp, ks, vs = _quantize_pools(quant, kf, vf)
    o_win = ref.paged_attention_ref(q, kp, vp, bt, lengths,
                                    k_scale=ks, v_scale=vs)
    for j in range(K):
        o_j = ref.paged_attention_ref(q[:, j], kp, vp, bt,
                                      lengths - (K - 1 - j),
                                      k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(o_win[:, j]), np.asarray(o_j),
                                   rtol=2e-6, atol=2e-6)


def test_window_ops_dispatch_and_zero_length():
    """ops.paged_attention routes 4-D q through the same impl rules;
    a fully-masked window (lengths == K on the null-page table row and
    lengths == 0 is impossible mid-serve, but the all-masked query of
    slot base 0 must not NaN)."""
    q, kf, vf, bt, lengths = _window_fixture(seed=3)
    outs = {impl: ops.paged_attention(q, kf, vf, bt, lengths, impl=impl)
            for impl in ("ref", "pallas", "auto")}
    assert float(jnp.max(jnp.abs(outs["pallas"] - outs["ref"]))) <= 1e-6
    np.testing.assert_array_equal(np.asarray(outs["auto"]),
                                  np.asarray(outs["ref"]))
    assert not bool(jnp.any(jnp.isnan(outs["ref"])))


# ---------------------------------------------------------------------------
# decode_window_paged == sequential decode_step_paged, position by position
# ---------------------------------------------------------------------------

def _setup(layers=2, width=64, vocab=128):
    spec = ASSIGNED["granite-3-8b"].scaled_down(layers=layers, width=width,
                                                vocab=vocab)
    params = lm.init(jax.random.PRNGKey(0), spec)
    return spec, params


@pytest.mark.parametrize("cache_dtype", ["fp32", "int4"])
def test_decode_window_matches_sequential_steps(cache_dtype):
    """Feeding K tokens through ONE decode_window_paged call produces,
    at every position, the same logits (argmax-stable fixture: same
    greedy tokens) as committing them one decode_step_paged at a time —
    and the rolled-back cache pos lets sequential decode continue
    exactly (the verify-accept contract)."""
    spec, params = _setup()
    page, K = 8, 3
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 128, size=13).astype(np.int32)
    layout = lm.PagedLayout(num_pages=16, page_size=page, pages_per_slot=6)

    def init_slot():
        n_prompt = pc.pages_needed(len(prompt), page)
        spad = n_prompt * page
        padded = np.zeros((1, spad), np.int32)
        padded[0, :len(prompt)] = prompt
        logits, pre = lm.prefill(params, spec, {"tokens": jnp.asarray(padded)},
                                 max_seq=spad, impl="naive",
                                 true_len=len(prompt))
        cache = lm.init_cache(spec, 1, 48, cache_dtype, paged=layout)
        pages = list(range(1, 7))
        cache = pc.write_prompt(cache, spec, 0, pages[:n_prompt], pre,
                                len(prompt))
        cache["block_tables"] = cache["block_tables"].at[0].set(
            jnp.asarray(pages, jnp.int32))
        return int(jnp.argmax(logits[0, 0])), cache

    tok0, cache_seq = init_slot()
    # sequential: K committed steps
    seq_logits, toks = [], [tok0]
    for _ in range(K):
        l, cache_seq = lm.decode_step(params, spec, cache_seq,
                                      jnp.asarray([[toks[-1]]], jnp.int32))
        seq_logits.append(l[:, 0])
        toks.append(int(jnp.argmax(l[0, 0])))

    # window: one verify pass over [tok0, greedy1, greedy2]
    _, cache_win = init_slot()
    window = jnp.asarray([toks[:K]], jnp.int32)
    lens = jnp.asarray([K], jnp.int32)
    wl, cache_win = lm.decode_window_paged(params, spec, cache_win,
                                           window, lens)
    assert wl.shape[1] == K
    for j in range(K):
        a, b = np.asarray(seq_logits[j][0]), np.asarray(wl[0, j])
        assert np.argmax(a) == np.argmax(b)
        # tight for BOTH dtypes: the window and sequential paths write
        # identical quantized rows and read the same pages, so the
        # int4 quantization error cancels out of this comparison
        rel = float(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9))
        assert rel < 1e-5, rel
    # pos was NOT advanced by the window (the caller commits)
    assert int(cache_win["pos"][0]) == len(prompt)


# ---------------------------------------------------------------------------
# End-to-end scheduler equivalence
# ---------------------------------------------------------------------------

def _reqs(seed=0, n=5, vocab=128, new_lo=8, new_hi=16):
    rng = np.random.default_rng(seed)
    # periodic templates (a 4- and a 5-token motif tiled to 20 and 25
    # tokens, like the benchmark's repetitive spec workload) so the
    # n-gram drafter has repeats to draft from and verify windows run
    t1 = np.tile(rng.integers(0, vocab, size=4), 5).astype(np.int32)
    t2 = np.tile(rng.integers(0, vocab, size=5), 5).astype(np.int32)
    reqs = []
    for i in range(n):
        t = (t1, t2)[i % 2]
        suf = rng.integers(0, vocab,
                           size=int(rng.integers(4, 11))).astype(np.int32)
        reqs.append(Request(i, np.concatenate([t, suf]),
                            int(rng.integers(new_lo, new_hi))))
    return reqs


def _run_engine(spec, params, reqs, spec_k, cache_dtype="fp32",
                num_pages=32, page_size=16, slots=3, max_seq=96):
    cfg = SchedulerConfig(max_slots=slots, page_size=page_size,
                          max_seq=max_seq, num_pages=num_pages,
                          cache_dtype=cache_dtype, spec_k=spec_k)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    eng.alloc.check()
    return done, eng


@pytest.mark.parametrize("cache_dtype", ["fp32", "int8", "int4"])
def test_spec_engine_matches_greedy(cache_dtype):
    """spec_k=4 engine output == spec_k=1 engine output token-for-token
    for every cache dtype (prefix cache on: shared pages + CoW + suffix
    prefill all cross the window path), with every page reference
    unwound, and the measured draft acceptance within the band of the
    drafter replayed over the greedy streams."""
    spec, params = _setup()
    reqs = _reqs()
    base, _ = _run_engine(spec, params, reqs, 1, cache_dtype)
    done, eng = _run_engine(spec, params, reqs, 4, cache_dtype)
    for a, b in zip(base, done):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    # the speculative run really speculated, and committed windows cut
    # the iteration count below one-token-per-slot-per-step
    assert eng.stats["spec_steps"] > 0
    assert eng.stats["spec_accepted"] > 0
    assert_acceptance_in_band(eng.stats, reqs, base, 4, context=cache_dtype)
    base_iters = _run_engine(spec, params, reqs, 1, cache_dtype)[1] \
        .stats["iterations"]
    assert eng.stats["iterations"] < base_iters


def test_spec_engine_preemption_parity():
    """A pool too small for all admitted contexts forces preemption;
    the speculative engine (whose windows allocate decode pages ahead)
    still matches sequential greedy and unwinds every reference."""
    spec, params = _setup()
    rng = np.random.default_rng(2)
    T = rng.integers(0, 128, size=16).astype(np.int32)
    reqs = [Request(i, np.concatenate(
        [T, rng.integers(0, 128, size=6).astype(np.int32)]), 12)
        for i in range(4)]
    base, e1 = _run_engine(spec, params, reqs, 1, "fp32", num_pages=11,
                           page_size=8, slots=4, max_seq=48)
    done, e2 = _run_engine(spec, params, reqs, 4, "fp32", num_pages=11,
                           page_size=8, slots=4, max_seq=48)
    assert e1.stats["preemptions"] >= 1
    assert e2.stats["preemptions"] >= 1
    for a, b in zip(base, done):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    e2.prefix_cache.flush()
    e2.alloc.check()
    assert e2.alloc.free_pages == e2.layout.num_pages - 1


def test_spec_k1_backend_contract():
    """spec_k=1 runs the pre-speculative decode program (the K=1 jit),
    and the backend decode contract returns (out (B, 1), n_emit ==
    active, ok == active for finite logits) — the shape every existing
    parity test leans on."""
    spec, params = _setup()
    cfg = SchedulerConfig(max_slots=2, page_size=16, max_seq=64,
                          num_pages=12)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    reqs = _reqs(n=2, new_lo=4, new_hi=6)
    for r in reqs:
        eng.submit(r)
    eng.step()
    tokens = np.zeros((2, 1), np.int32)
    active = np.zeros((2,), np.int32)
    for i, slot in enumerate(eng.slots):
        if slot is not None:
            tokens[i, 0] = slot.last_token
            active[i] = 1
    out, n_emit, ok = eng.backend.decode(tokens, active)
    assert out.shape == (2, 1)
    np.testing.assert_array_equal(n_emit, active)
    np.testing.assert_array_equal(np.asarray(ok), active)
