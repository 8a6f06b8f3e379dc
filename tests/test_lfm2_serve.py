"""LFM2-MoE on the serve path at a small size on the CPU: gated
short-convolution layers with per-slot state beside the KV pages, and a
dropless routed-expert layer over the experts a chip holds.

The program (``ModelSpec`` and weights from ``chipbench/adapters/
lfm2_moe.py``, seeded) is held to the plain float32 reference
(``chipbench/reference/lfm2_moe.py``): prefill through the paged cache
— cold, bucket-padded and chunked — then decoding, in logits.  Both run
in float32 here, so only summation order separates them: agreement to
1e-4 of the largest logit, where one bfloat16 rounding anywhere on the
path reads about 1e-2.
"""
import dataclasses
import functools
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.adapters import lfm2_moe as adapter
from chipbench.reference import lfm2_moe as ref
from chipbench.weights import base_key
from repro.models import layers as L
from repro.models import lm
from repro.serve import paged_cache as pc
from repro.serve.backend import EXPERT_COUNTERS, SingleDeviceBackend
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   SchedulerConfig, _bucket, _pow2_pages)
from repro.serve.spans import SpanRecorder

ROOT = Path(__file__).resolve().parents[1]
CONFIG = ROOT / "chipbench" / "tests" / "data" / "tiny-lfm2.json"
SEED = 2**35 + 9
PAGE, MAX_SEQ = 16, 512
#: Largest logit difference over the largest |logit| (module docstring).
REL_TOL = 1e-4


def _tiny(weight_dtype="float32"):
    cfg = json.loads(CONFIG.read_text())
    cfg["program"] = dict(cfg["program"], weight_dtype=weight_dtype)
    spec = adapter.program_spec(cfg)
    key = base_key(SEED)
    return cfg, spec, key, adapter.program_params(cfg, spec, key)


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


def _sched(**kw):
    base = dict(max_slots=4, page_size=PAGE, max_seq=MAX_SEQ, num_pages=97)
    base.update(kw)
    return SchedulerConfig(**base)


def _tokens(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


_ref_jit = jax.jit(ref.logits_all, static_argnames=("dm", "prec"))


def _ref_logits(cfg, key, seq):
    """Reference logits (len(seq), V) of one sequence (padded to the
    reference's attention block)."""
    T = -(-len(seq) // ref.Q_BLOCK) * ref.Q_BLOCK
    padded = np.zeros(T, np.int32)
    padded[:len(seq)] = seq
    return np.asarray(_ref_jit(key, jnp.asarray(padded),
                               dm=ref.dims(cfg)))[:len(seq)]


def _close(got, want, context=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert rel <= REL_TOL, f"{context}: logits differ by {rel:.2e}"


@functools.partial(jax.jit, static_argnames=("spec",))
def _decode(params, cache, tokens, *, spec):
    return lm.decode_step_paged(params, spec, cache, tokens)


def _admit(backend, prompt, slot, chunk=0):
    """Write ``prompt`` into ``slot`` through the backend's admission
    programs: one cold bucket-padded prefill, or prefill chunks of at
    most ``chunk`` tokens (the first cold, the rest over the pages
    already written).  Returns the first sampled token."""
    P = len(prompt)
    pages = list(range(1 + slot * 32, 1 + slot * 32 + pc.pages_needed(P + 64,
                                                                    PAGE)))
    row = np.zeros(MAX_SEQ // PAGE, np.int32)
    row[:len(pages)] = pages
    if not chunk:
        padded = np.zeros((1, _bucket(P, PAGE, MAX_SEQ)), np.int32)
        padded[0, :P] = prompt
        return backend.admit_full(padded, slot, P, row)
    done = 0
    while done < P:
        n = min(chunk, P - done)
        padded = np.zeros((1, _bucket(n, PAGE, MAX_SEQ)), np.int32)
        padded[0, :n] = prompt[done:done + n]
        npp = _pow2_pages(pc.pages_needed(done, PAGE), MAX_SEQ // PAGE)
        tok = backend.prefill_chunk(padded, slot, done, n, row,
                                    n_prefix_pages=npp)
        done += n
    return tok


@pytest.mark.parametrize("P,chunk", [(13, 0), (37, 0), (37, 16), (50, 32)])
def test_prefill_then_decode_matches_reference(tiny, P, chunk):
    """Cold or chunked prefill through the paged cache, then decoding,
    agrees in logits with the reference's full forward pass, at prompt
    lengths off every bucket (the conv state is left at the true end)."""
    cfg, spec, key, params = tiny
    seq = _tokens(P + 6, seed=P)
    want = _ref_logits(cfg, key, seq)
    backend = SingleDeviceBackend(params, spec, _sched())
    tok0 = _admit(backend, seq[:P], slot=1, chunk=chunk)
    assert tok0 == int(np.argmax(want[P - 1]))
    cache = backend.cache
    for i in range(P, P + 6):
        toks = np.zeros((4, 1), np.int32)
        toks[1, 0] = seq[i]
        logits, cache = _decode(params, cache, jnp.asarray(toks), spec=spec)
        _close(logits[1, 0], want[i], f"decode at {i}")


def test_bucket_padded_prefill_logits(tiny):
    """The cold prefill's own logits at the prompt's true end."""
    cfg, spec, key, params = tiny
    seq = _tokens(21, seed=3)
    want = _ref_logits(cfg, key, seq)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :21] = seq
    logits, cache = lm.prefill(params, spec, {"tokens": jnp.asarray(padded)},
                               true_len=21)
    _close(logits[0, 0], want[20], "prefill")
    convs = [e["conv_state"] for g in cache["groups"] for e in g
             if "conv_state" in e]
    assert len(convs) == spec.num_conv_layers()
    assert all(c.shape == (1, spec.conv_width - 1, spec.d_model)
               for c in convs)


def test_request_logits_do_not_depend_on_batch(tiny):
    """No drops: one request's decode logits are the same alone and
    batched with others at other contexts."""
    cfg, spec, key, params = tiny
    a = _tokens(29, seed=11)
    alone = SingleDeviceBackend(params, spec, _sched())
    _admit(alone, a, slot=0)
    batched = SingleDeviceBackend(params, spec, _sched())
    _admit(batched, a, slot=0)
    for slot, n in ((1, 61), (2, 7), (3, 44)):
        _admit(batched, _tokens(n, seed=n), slot=slot)
    toks = jnp.asarray(_tokens(4, seed=5)[:, None])
    la, _ = _decode(params, alone.cache, toks.at[1:].set(0), spec=spec)
    lb, _ = _decode(params, batched.cache, toks, spec=spec)
    _close(la[0, 0], lb[0, 0], "slot 0 alone vs batched")


def test_idle_slots_are_routed_nowhere(tiny):
    """A decode step's expert counters count only the slots it decodes:
    one live slot among idle ones counts the rows it counts alone."""
    _, spec, _, params = tiny
    prompt = _tokens(23, seed=3)
    toks = _tokens(4, seed=6)[:, None]

    def rows_of_one_step(slots):
        backend = SingleDeviceBackend(params, spec, _sched(max_slots=slots))
        stats = {}
        backend.bind_spans(SpanRecorder(stats))
        _admit(backend, prompt, slot=0)
        before = dict(stats)
        active = np.zeros(slots, np.int32)
        active[0] = 1
        backend.decode(toks[:slots], active)
        return {k: stats[k] - before[k] for k in EXPERT_COUNTERS}

    one = rows_of_one_step(1)
    assert one["moe_layer_calls"] == spec.num_layers - spec.dense_layers
    assert rows_of_one_step(4) == one


def test_expert_shares_sum_to_the_uncut_layer(tiny):
    """Expert parallelism 2: a MoE layer's part from experts 0-3 and
    from 4-7 of 8, with the attention or conv part every chip computes
    counted once, sums to the uncut reference layer."""
    cfg, spec, key, _ = tiny
    dm = ref.dims(dict(cfg, num_experts=8))
    assert dm.held == (0, 8) and dm.E == 8
    l = 1                                   # a conv layer with experts
    x = jax.random.normal(jax.random.PRNGKey(4), (24, dm.d))

    @jax.jit
    def layer(x):
        a = ref._rmsnorm(x, ref._w(dm, key, "op_norm", l, (dm.d,)), dm.eps)
        y = x + ref._conv(dm, key, l, a, "f32")
        m = ref._rmsnorm(y, ref._w(dm, key, "ffn_norm", l, (dm.d,)), dm.eps)
        uncut = y + ref._experts(dm, key, l, m, "f32")
        p = {"router_w": ref._w(dm, key, "router", l, (dm.d, dm.E)),
             "expert_bias": ref._w(dm, key, "expert_bias", l, (dm.E,))}
        wi = jnp.concatenate([ref._w(dm, key, "gate", l, (8, dm.d, dm.Fe)),
                              ref._w(dm, key, "up", l, (8, dm.d, dm.Fe))],
                             -1)
        wo = ref._w(dm, key, "down", l, (8, dm.Fe, dm.d))
        cut, rows = y, []
        for lo, hi in ((0, 4), (4, 8)):
            s = spec.with_(moe=dataclasses.replace(spec.moe, held=(lo, hi)))
            out, r = L.routed_experts(
                s, dict(p, experts_wi=wi[lo:hi], experts_wo=wo[lo:hi]), m)
            cut, rows = cut + out, rows + [r]
        return uncut, cut, rows

    with jax.default_matmul_precision("highest"):
        uncut, cut, rows = layer(x)
    _close(cut, uncut, "shares")
    # every (row, expert) assignment lands in exactly one share
    assert all(r.shape == (4,) for r in rows)
    assert sum(int(r.sum()) for r in rows) == 24 * dm.top_k


def test_lane_dense_pools_keep_tokens_through_swap_and_chunking(tiny):
    """The tiny spec's heads are 64 wide, so its fp32 pools are stored
    lane-dense (``kernels/page_layout.py``).  A slot's greedy tokens are
    the same uninterrupted, after its pages went to the host and came
    back into other pages (the old ones zeroed), and after a chunked
    prefill; the backend counts every attention layer as lane-dense,
    and none for int8 pools."""
    _, spec, _, params = tiny
    prompt = _tokens(41, seed=8)
    pages = list(range(33, 33 + pc.pages_needed(len(prompt) + 64, PAGE)))
    fresh = [p + 20 for p in pages]

    def greedy(chunk=0, swap_at=None):
        backend = SingleDeviceBackend(params, spec, _sched())
        stats = {}
        backend.bind_spans(SpanRecorder(stats))
        out = [_admit(backend, prompt, slot=1, chunk=chunk)]
        active = np.zeros(4, np.int32)
        active[1] = 1
        for i in range(10):
            if i == swap_at:
                blob = backend.swap_out(pages)
                backend.swap_in(blob, fresh)
                backend.swap_in(jax.tree_util.tree_map(np.zeros_like, blob),
                                pages)
                backend.write_block_entries(
                    [(1, j, p) for j, p in enumerate(fresh)])
            toks = np.zeros((4, 1), np.int32)
            toks[1, 0] = out[-1]
            nxt, _, ok = backend.decode(toks, active)
            assert ok[1]
            out.append(int(nxt[1, 0]))
        return out, stats

    want, stats = greedy()
    n_attn = sum(k.startswith("attn") for k in spec.layer_kinds())
    assert stats["kv_lane_dense_layers"] == n_attn == 1
    assert greedy(swap_at=4)[0] == want
    assert greedy(chunk=16)[0] == want
    int8 = SingleDeviceBackend(params, spec, _sched(cache_dtype="int8"))
    stats = {}
    int8.bind_spans(SpanRecorder(stats))
    assert stats["kv_lane_dense_layers"] == 0


def _serve(spec, params, requests, **kw):
    eng = ContinuousBatchingEngine(params, spec, _sched(**kw))
    done = eng.run([Request(r.uid, r.prompt, r.max_new_tokens)
                    for r in requests])
    return {c.uid: list(c.tokens) for c in done}, eng


def _requests(n=6, new=10):
    rng = np.random.default_rng(7)
    return [Request(i, _tokens(int(rng.integers(5, 60)), seed=100 + i),
                    new) for i in range(n)]


def test_slot_reuse_chunking_and_preemption_give_the_same_tokens():
    """More requests than slots (slots reused), chunked prefill beside
    other slots' decoding, and a pool small enough to preempt by
    recompute all emit the tokens each request gets served alone."""
    _, spec, _, params = _tiny()
    reqs = _requests()
    alone = {}
    for r in reqs:
        alone.update(_serve(spec, params, [r], max_slots=1)[0])
    reused, _ = _serve(spec, params, reqs, max_slots=2)
    chunked, _ = _serve(spec, params, reqs, prefill_chunk_tokens=16,
                        debug_invariants=True)
    assert reused == alone and chunked == alone
    # 8-token pages: four 18-23-token prompts fit, their 16 new tokens
    # each do not
    rng = np.random.default_rng(2)
    reqs = [Request(i, _tokens(int(rng.integers(18, 24)), seed=200 + i), 16)
            for i in range(4)]
    small = dict(page_size=8, max_seq=128)
    alone = {}
    for r in reqs:
        alone.update(_serve(spec, params, [r], max_slots=1, **small)[0])
    tight, eng = _serve(spec, params, reqs, num_pages=13,
                        debug_invariants=True, **small)
    assert eng.stats["preemptions"] > 0
    assert tight == alone


def test_engine_turns_off_what_cannot_restore_conv_state():
    """A conv spec gets no prefix cache and no host pool (preemption
    recomputes), says why in its stats, and refuses verify windows."""
    _, spec, _, params = _tiny()
    eng = ContinuousBatchingEngine(params, spec, _sched(
        host_pool_bytes=1 << 26))
    assert eng.prefix_cache is None and eng.host_pool is None
    assert eng.stats["off.prefix_cache.conv_state"] == 1
    assert eng.stats["off.host_pool.conv_state"] == 1
    with pytest.raises(ValueError, match="conv state"):
        ContinuousBatchingEngine(params, spec, _sched(spec_k=3))
    eng.run(_requests(n=4, new=12)[:4])
    assert eng.stats["swap_outs"] == eng.stats["idle_parks"] == 0
    assert eng.stats["moe_layer_calls"] > 0
    assert 0 < eng.stats["moe_experts_hit"] <= eng.stats["moe_rows"]


def test_embedding_draw_is_not_degenerate():
    """At the published width and depth (two held experts and a
    1,024-token vocabulary, to fit the CPU), with the configuration's
    ``weight_shift`` the argmax of a row is mostly not the row's own
    token (the tied head's self-match), so the served tokens depend on
    the context; drawn at the common scale, every argmax is the current
    token.  The share depends on the width, so it is read at the
    published one."""
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / "lfm2-8b-a1b-ep2.json").read_text())
    cfg.update(num_experts=2, vocab_size=1024)
    key = base_key(SEED)
    seq = jnp.asarray(_tokens(ref.Q_BLOCK, seed=21, vocab=1024))

    def current_share(c):
        logits = _ref_jit(key, seq, dm=ref.dims(c))
        return float(jnp.mean(jnp.argmax(logits, -1) == seq))

    assert current_share(cfg) < 0.2
    assert current_share(dict(cfg, weight_shift={})) > 0.9
