"""Continuous-batching scheduler + paged KV cache tests.

Covers the three tentpole invariants: (1) the page allocator never
leaks or double-owns a page under random admit/evict traffic, (2) the
paged decode path is numerically the contiguous-cache path, and (3) the
scheduler's greedy output is token-for-token the static per-request
``generate`` on a mixed-length batch.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import lm
from repro.serve import paged_cache as pc
from repro.serve.engine import ServeConfig, generate
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   SchedulerConfig, _bucket)


def _setup(layers=2, width=64, vocab=128):
    spec = ASSIGNED["granite-3-8b"].scaled_down(layers=layers, width=width,
                                                vocab=vocab)
    params = lm.init(jax.random.PRNGKey(0), spec)
    return spec, params


# ---------------------------------------------------------------------------
# Page allocator invariants
# ---------------------------------------------------------------------------

def test_page_allocator_random_admit_evict():
    """Fuzz alloc/free (single-reference traffic): after every operation
    no page is leaked, double-owned, or both free and live.  Refcounted
    share/evict interleavings are fuzzed in tests/test_prefix_cache.py."""
    rng = np.random.default_rng(0)
    alloc = pc.PageAllocator(64)
    live = {}                       # uid -> pages
    uid = 0
    for _ in range(500):
        if live and (rng.random() < 0.45 or alloc.free_pages < 4):
            victim = rng.choice(list(live))
            alloc.free(live.pop(victim))
        else:
            n = int(rng.integers(1, 5))
            if alloc.can_alloc(n):
                live[uid] = alloc.alloc(n)
                uid += 1
        alloc.check()
    for pages in live.values():
        alloc.free(pages)
    alloc.check()
    assert alloc.free_pages == 63    # everything back except the null page


def test_page_allocator_rejects_double_free():
    alloc = pc.PageAllocator(8)
    pages = alloc.alloc(2)
    alloc.free(pages)
    with pytest.raises(ValueError):
        alloc.free(pages)
    with pytest.raises(MemoryError):
        alloc.alloc(99)


# ---------------------------------------------------------------------------
# Paged attention op: Pallas kernel vs gather reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 7])
def test_paged_attention_kernel_matches_ref(window):
    rng = np.random.default_rng(0)
    B, H, KV, D, page, P, pps = 4, 4, 2, 16, 8, 16, 3
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[:B * pps].reshape(B, pps), jnp.int32)
    lengths = jnp.asarray([5, 20, 0, 24], jnp.int32)
    o_ref = ref.paged_attention_ref(q, kp, vp, bt, lengths, window=window)
    o_pal = paged_attention_pallas(q, kp, vp, bt, lengths, window=window,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(o_pal[2]))) == 0.0   # length-0 slot -> zeros


# ---------------------------------------------------------------------------
# Paged decode == contiguous decode
# ---------------------------------------------------------------------------

def _paged_single_seq(spec, params, prompt, page=8, steps=6, dtype=jnp.float32):
    """Prefill one prompt into pages and greedy-decode ``steps`` tokens."""
    n_prompt = pc.pages_needed(len(prompt), page)
    spad = n_prompt * page
    padded = np.zeros((1, spad), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, pre = lm.prefill(params, spec, {"tokens": jnp.asarray(padded)},
                             max_seq=spad, impl="naive",
                             true_len=len(prompt))
    layout = lm.PagedLayout(num_pages=16, page_size=page, pages_per_slot=6)
    cache = lm.init_cache(spec, 1, 48, dtype, paged=layout)
    pages = list(range(1, 7))
    cache = pc.write_prompt(cache, spec, 0, pages[:n_prompt], pre,
                            len(prompt))
    bt = cache["block_tables"]
    cache["block_tables"] = bt.at[0].set(jnp.asarray(pages, jnp.int32))
    tok = jnp.argmax(logits[:, 0], -1)[:, None]
    outs = [logits]
    for _ in range(steps):
        l, cache = lm.decode_step(params, spec, cache, tok)
        outs.append(l)
        tok = jnp.argmax(l[:, 0], -1)[:, None]
    return outs


def test_paged_decode_matches_contiguous():
    """Same prompt through the paged and contiguous cache paths: prefill
    logits (the same program at a page-padded width) within a few f32
    ulps, decode logits equal to float tolerance."""
    spec, params = _setup()
    prompt = np.random.default_rng(1).integers(0, 128, size=11).astype(np.int32)
    paged = _paged_single_seq(spec, params, prompt)
    logits, cache = lm.prefill(params, spec, {"tokens": jnp.asarray(prompt[None])},
                               max_seq=48, impl="naive")
    np.testing.assert_allclose(np.asarray(paged[0]), np.asarray(logits),
                               rtol=1e-6, atol=1e-6)
    tok = jnp.argmax(logits[:, 0], -1)[:, None]
    for step in range(6):
        logits, cache = lm.decode_step(params, spec, cache, tok)
        np.testing.assert_allclose(np.asarray(paged[step + 1]),
                                   np.asarray(logits), rtol=1e-5, atol=1e-5)
        tok = jnp.argmax(logits[:, 0], -1)[:, None]


def test_paged_int8_cache_close_to_float():
    """int8 pages (per-token-per-head scales): greedy tokens unchanged,
    logits within ~1% on the tiny model."""
    spec, params = _setup()
    prompt = np.random.default_rng(2).integers(0, 128, size=13).astype(np.int32)
    f32 = _paged_single_seq(spec, params, prompt, steps=4)
    i8 = _paged_single_seq(spec, params, prompt, steps=4, dtype=jnp.int8)
    for a, b in zip(f32, i8):
        rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
        assert rel < 0.05
        assert jnp.argmax(a[:, 0], -1) == jnp.argmax(b[:, 0], -1)


def test_init_paged_cache_rejects_recurrent():
    spec = ASSIGNED["zamba2-1.2b"].scaled_down()
    layout = lm.PagedLayout(num_pages=4, page_size=8)
    with pytest.raises(NotImplementedError):
        lm.init_cache(spec, 1, 32, paged=layout)


# ---------------------------------------------------------------------------
# Scheduler end-to-end: token equivalence + page hygiene
# ---------------------------------------------------------------------------

def test_scheduler_matches_static_generate_mixed_lengths():
    """Mixed-length workload through the continuous-batching engine is
    token-for-token the per-request static generate, and every page is
    returned to the allocator."""
    spec, params = _setup()
    rng = np.random.default_rng(0)
    shapes = [(8, 5), (13, 7), (24, 3), (5, 9), (17, 4), (30, 6), (9, 8)]
    reqs = [Request(i, rng.integers(0, 128, size=l).astype(np.int32), n)
            for i, (l, n) in enumerate(shapes)]
    cfg = SchedulerConfig(max_slots=3, page_size=8, max_seq=64, num_pages=30,
                          debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run(list(reqs))
    assert [c.uid for c in done] == list(range(len(reqs)))
    scfg = ServeConfig(max_seq=64, attention_impl="naive")
    for r, c in zip(reqs, done):
        out = generate(params, spec, {"tokens": jnp.asarray(r.prompt[None])},
                       r.max_new_tokens - 1, scfg)
        np.testing.assert_array_equal(np.asarray(out["tokens"][0]), c.tokens)
    eng.alloc.check()
    # pool capped at the addressable max (slots * pages_per_slot + null)
    assert eng.layout.num_pages == min(cfg.num_pages, 3 * 8 + 1)
    # the prefix store retains pages by refcount; flushing returns all
    eng.prefix_cache.flush()
    eng.alloc.check()
    assert eng.alloc.free_pages == eng.layout.num_pages - 1
    assert eng.stats["finished"] == len(reqs)
    # 3 slots for 7 requests forces slot reuse across admissions
    assert eng.stats["admitted"] == len(reqs)


def test_scheduler_queue_backpressure():
    """More outstanding pages than the pool: admission must wait for
    frees, never OOM, and still finish everything."""
    spec, params = _setup()
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, 128, size=20).astype(np.int32), 6)
            for i in range(6)]
    # pool fits ~2 requests' worth of pages at a time
    cfg = SchedulerConfig(max_slots=4, page_size=8, max_seq=48, num_pages=9,
                          debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run(list(reqs))
    assert len(done) == 6 and all(len(c.tokens) == 6 for c in done)
    eng.alloc.check()


def test_scheduler_rejects_oversized_request():
    spec, params = _setup()
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=32, num_pages=16)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    with pytest.raises(ValueError):
        eng.submit(Request(0, np.zeros(30, np.int32), 8))


def test_scheduler_rejects_request_larger_than_pool():
    """A request needing more pages than the pool can EVER free must be
    rejected at submit (it could never admit -> run() would spin)."""
    spec, params = _setup()
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=64, num_pages=4)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(Request(0, np.zeros(40, np.int32), 8))   # 6 pages > 3


def test_prompt_bucketing():
    assert _bucket(5, 16, 512) == 16
    assert _bucket(17, 16, 512) == 32
    assert _bucket(33, 16, 512) == 64
    assert _bucket(500, 16, 512) == 512


def test_prompt_bucketing_unaligned_max_seq():
    """The bucket cap is max_seq rounded UP to a page multiple: it is a
    page-granular compute width (the scatter works whole pages), so a
    raw cap would truncate the page count and drop the prompt tail."""
    assert _bucket(39, 16, 40) == 48       # 3 true pages must survive
    assert _bucket(40, 16, 40) == 48
    assert _bucket(1, 16, 40) == 16        # 1-token prompt: one page
    assert _bucket(16, 16, 40) == 16       # exact page fill
    assert _bucket(512, 16, 512) == 512    # aligned cap unchanged


def test_scheduler_unaligned_max_seq_boundary():
    """Prompts whose bucket rounds past an unaligned max_seq but whose
    true pages fit: the full prompt KV must land in the pages (the seed
    capped the padded width at raw max_seq, truncating the scatter page
    count and silently dropping the last partial page's rows)."""
    spec, params = _setup()
    rng = np.random.default_rng(7)
    shapes = [(38, 2), (32, 8), (1, 4)]    # tail page, exact pages, 1 token
    reqs = [Request(i, rng.integers(0, 128, size=l).astype(np.int32), n)
            for i, (l, n) in enumerate(shapes)]
    cfg = SchedulerConfig(max_slots=1, page_size=16, max_seq=40, num_pages=8)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run(list(reqs))
    scfg = ServeConfig(max_seq=48, attention_impl="naive")
    for r, c in zip(reqs, done):
        out = generate(params, spec, {"tokens": jnp.asarray(r.prompt[None])},
                       r.max_new_tokens - 1, scfg)
        np.testing.assert_array_equal(np.asarray(out["tokens"][0]), c.tokens)
    eng.alloc.check()


def test_paged_cache_plan_budget():
    """plan_paged_cache fits the pool inside the byte budget and the
    scheduler layout respects it."""
    from repro.core.analytical import plan_paged_cache
    spec, _ = _setup()
    plan = plan_paged_cache(spec, budget_bytes=2e6, page_size=16)
    assert plan.total_bytes <= 2e6
    assert plan.num_pages >= 2
    layout = pc.make_layout(spec, max_seq=128, page_size=16,
                            kv_budget_bytes=2e6, max_slots=4)
    assert layout.num_pages <= plan.num_pages
    assert layout.num_pages <= 4 * layout.slots_pages(128) + 1


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------

class _RecordingBackend:
    """Transparent proxy over a ``PagedKVBackend`` recording the padded
    width of every prefill call, so tests can assert the scheduler's
    per-iteration chunk-budget accounting against what actually reached
    the device."""

    def __init__(self, inner):
        self._inner = inner
        self.calls = []            # (kind, padded_width)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def admit_full(self, padded, slot, true_len, row):
        self.calls.append(("full", len(padded)))
        return self._inner.admit_full(padded, slot, true_len, row)

    def admit_prefix(self, padded, slot, prefix_len, true_len, row, *,
                     n_prefix_pages):
        self.calls.append(("prefix", len(padded)))
        return self._inner.admit_prefix(padded, slot, prefix_len, true_len,
                                        row, n_prefix_pages=n_prefix_pages)

    def prefill_chunk(self, padded, slot, prefix_len, true_len, row, *,
                      n_prefix_pages):
        self.calls.append(("chunk", len(padded)))
        return self._inner.prefill_chunk(padded, slot, prefix_len, true_len,
                                         row, n_prefix_pages=n_prefix_pages)


@pytest.mark.parametrize("cache_dtype", ["fp32", "int4"])
def test_chunked_prefill_outputs_identical(cache_dtype):
    """Chunked admission is a SCHEDULING change only: outputs must be
    token-for-token the unchunked engine's, every iteration's padded
    prefill tokens must fit the budget, and long prompts must actually
    split (prefill_chunks > 0)."""
    spec, params = _setup()
    rng = np.random.default_rng(5)
    shapes = [(40, 5), (9, 7), (33, 4), (21, 6), (56, 3), (14, 8)]
    reqs = [Request(i, rng.integers(0, 128, size=l).astype(np.int32), n)
            for i, (l, n) in enumerate(shapes)]
    budget = 16
    outs = {}
    for chunk in (0, budget):
        cfg = SchedulerConfig(max_slots=3, page_size=8, max_seq=80,
                              num_pages=40, cache_dtype=cache_dtype,
                              prefill_chunk_tokens=chunk,
                              debug_invariants=True)
        eng = ContinuousBatchingEngine(params, spec, cfg)
        rec = _RecordingBackend(eng.backend)
        eng.backend = rec
        for r in reqs:
            eng.submit(Request(r.uid, r.prompt.copy(), r.max_new_tokens))
        done = []
        while eng.num_active or eng.queue:
            before = len(rec.calls)
            done.extend(eng.step())
            if chunk:
                spent = sum(w for _, w in rec.calls[before:])
                assert spent <= budget, rec.calls[before:]
        eng.alloc.check()
        outs[chunk] = sorted(done, key=lambda c: c.uid)
        if chunk:
            assert eng.stats["prefill_chunks"] > 0
            # both engines prefill every prompt token exactly once
            assert eng.stats["prefill_tokens"] == sum(l for l, _ in shapes)
    for a, b in zip(outs[0], outs[budget]):
        assert a.uid == b.uid
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_chunked_prefill_composes_with_prefix_cache():
    """Prefix-cache hits shrink the suffix the chunks cover; hit
    accounting and outputs stay identical to the unchunked prefix-on
    engine, and completed chunked prompts register for later hits."""
    spec, params = _setup()
    rng = np.random.default_rng(7)
    template = rng.integers(0, 128, size=24).astype(np.int32)
    reqs = []
    for i in range(6):
        suffix = rng.integers(0, 128,
                              size=int(rng.integers(6, 14))).astype(np.int32)
        reqs.append(Request(i, np.concatenate([template, suffix]),
                            int(rng.integers(4, 7))))
    stats = {}
    outs = {}
    for chunk in (0, 16):
        cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=64,
                              num_pages=40, enable_prefix_cache=True,
                              prefill_chunk_tokens=chunk,
                              debug_invariants=True)
        eng = ContinuousBatchingEngine(params, spec, cfg)
        done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                        for r in reqs])
        eng.alloc.check()
        outs[chunk] = sorted(done, key=lambda c: c.uid)
        stats[chunk] = dict(eng.stats)
    for a, b in zip(outs[0], outs[16]):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert stats[16]["prefix_hit_tokens"] > 0
    assert stats[16]["prefix_hit_tokens"] == stats[0]["prefix_hit_tokens"]
    assert stats[16]["prefill_tokens"] == stats[0]["prefill_tokens"]


def test_chunked_prefill_under_preemption_and_recompute_stats():
    """Chunking + pool pressure: preempted victims re-chunk on
    recompute, outputs stay the static per-request generate, and
    recompute traffic lands in its own counters — ``prompt_tokens`` /
    ``prefix_hit_tokens`` keep meaning ARRIVED work, not work inflated
    by the scheduler's own evictions."""
    spec, params = _setup()
    rng = np.random.default_rng(11)
    reqs = [Request(i, rng.integers(0, 128, size=16).astype(np.int32), 20)
            for i in range(5)]
    cfg = SchedulerConfig(max_slots=4, page_size=8, max_seq=48,
                          num_pages=10, prefill_chunk_tokens=16,
                          debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    eng.alloc.check()
    assert eng.stats["preemptions"] > 0, "pool sized to force preemption"
    scfg = ServeConfig(max_seq=48, attention_impl="naive")
    for r, c in zip(reqs, sorted(done, key=lambda c: c.uid)):
        out = generate(params, spec, {"tokens": jnp.asarray(r.prompt[None])},
                       r.max_new_tokens - 1, scfg)
        np.testing.assert_array_equal(np.asarray(out["tokens"][0]), c.tokens)
    # recompute accounting is separate and honest
    assert eng.stats["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    assert eng.stats["prefix_hit_tokens"] == 0
    assert eng.stats["recompute_prompt_tokens"] > 0


def test_prefill_chunk_tokens_validation():
    spec, params = _setup()
    for bad in (4, 12):            # below page size / not a multiple
        cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=32,
                              num_pages=16, prefill_chunk_tokens=bad)
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(params, spec, cfg)


# ---------------------------------------------------------------------------
# Preemption landing mid-speculative-window
# ---------------------------------------------------------------------------

class _BlockTableAuditBackend(_RecordingBackend):
    """Proxy asserting every lazily-grown block-table write matches the
    HOST's view of the owning slot at write time.  A preemption that
    lands while decode windows are queued used to be able to flush a
    victim's stale page updates — rows for a slot that was just
    released, or page ids the host no longer owns."""

    def __init__(self, inner, eng_ref):
        super().__init__(inner)
        self._eng = eng_ref

    def write_block_entries(self, updates):
        for row, idx, page in updates:
            slot = self._eng()['eng'].slots[row]
            assert slot is not None, \
                f"block-table write for empty slot row {row}"
            assert slot.pages[idx] == page, \
                (row, idx, page, slot.pages)
        return self._inner.write_block_entries(updates)


def test_spec_window_preemption_block_tables_consistent():
    """Forced preemption while spec_k=4 windows are in flight: every
    surviving slot's device block table stays consistent with host
    pages (audited at each write), outputs equal the non-speculative
    greedy engine, and both preemption and speculation actually
    happened."""
    spec, params = _setup()
    rng = np.random.default_rng(13)
    reqs = [Request(i, rng.integers(0, 128, size=16).astype(np.int32), 20)
            for i in range(5)]

    def go(k):
        cfg = SchedulerConfig(max_slots=4, page_size=8, max_seq=48,
                              num_pages=10, spec_k=k,
                              debug_invariants=True)
        eng = ContinuousBatchingEngine(params, spec, cfg)
        holder = {'eng': eng}
        eng.backend = _BlockTableAuditBackend(eng.backend, lambda: holder)
        done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                        for r in reqs])
        eng.alloc.check()
        return eng, sorted(done, key=lambda c: c.uid)

    base_eng, base = go(1)
    spec_eng, spec_done = go(4)
    assert spec_eng.stats["preemptions"] > 0
    assert spec_eng.stats["spec_steps"] > 0
    for a, b in zip(base, spec_done):
        assert a.uid == b.uid
        np.testing.assert_array_equal(a.tokens, b.tokens)


# ---------------------------------------------------------------------------
# Host-tier KV swapping + multi-turn sessions
# ---------------------------------------------------------------------------

def test_host_page_pool_bookkeeping():
    """Byte-budgeted host pool: park/peek/take/drop with exact byte
    accounting, duplicate keys rejected, over-budget parks raise
    (callers degrade to recompute), and ``check()`` holds throughout."""
    blob = [np.zeros((2, 8, 2, 4), np.float32)]
    rec = pc.ParkedKV(context=np.arange(5, dtype=np.int32), written=4,
                      n_pages=2, blob=blob, nbytes=pc.blob_nbytes(blob))
    pool = pc.HostPagePool(3 * rec.nbytes)
    assert pool.can_park(rec.nbytes)
    pool.park(("sess", 1), rec)
    pool.check()
    assert ("sess", 1) in pool and len(pool) == 1
    assert pool.used_bytes == rec.nbytes
    assert pool.free_bytes == 2 * rec.nbytes
    with pytest.raises(ValueError):
        pool.park(("sess", 1), rec)           # duplicate key
    big = pc.ParkedKV(context=rec.context, written=4, n_pages=2,
                      blob=blob, nbytes=3 * rec.nbytes)
    assert not pool.can_park(big.nbytes)
    with pytest.raises(MemoryError):
        pool.park(("sess", 2), big)
    assert pool.peek(("sess", 1)) is rec      # peek never removes
    assert pool.take(("sess", 1)) is rec
    assert pool.used_bytes == 0 and len(pool) == 0
    assert pool.resumed_total == 1
    pool.park(("uid", 7), rec)
    assert pool.drop(("uid", 7)) and not pool.drop(("uid", 7))
    pool.check()
    with pytest.raises(ValueError):
        pc.HostPagePool(0)


@pytest.mark.parametrize("cache_dtype", ["fp32", "int8", "int4"])
def test_swap_tier_replaces_preemption_token_identical(cache_dtype):
    """Pool pressure with a host pool: the victim SWAPS instead of
    preempting, its resume scatters the parked pages back and prefills
    one token, and every output is token-for-token the recompute-only
    engine's.  The pool drains fully — no blob outlives its request."""
    spec, params = _setup()
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(1, 128,
                                    size=int(rng.integers(12, 28))).astype(
                        np.int32), 16)
            for i in range(5)]

    def go(host_bytes):
        cfg = SchedulerConfig(max_slots=3, page_size=8, max_seq=64,
                              num_pages=12, cache_dtype=cache_dtype,
                              host_pool_bytes=host_bytes,
                              debug_invariants=True)
        eng = ContinuousBatchingEngine(params, spec, cfg)
        done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                        for r in reqs])
        return eng, sorted(done, key=lambda c: c.uid)

    base_eng, base = go(None)
    swap_eng, got = go(50e6)
    assert base_eng.stats["preemptions"] > 0, "pool sized to force pressure"
    assert swap_eng.stats["swap_outs"] > 0
    assert swap_eng.stats["swap_ins"] == swap_eng.stats["swap_outs"]
    assert swap_eng.stats["swapped_in_pages"] == \
        swap_eng.stats["swapped_out_pages"]
    assert swap_eng.stats["preemptions"] < base_eng.stats["preemptions"]
    for a, b in zip(base, got):
        assert a.uid == b.uid
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert len(swap_eng.host_pool) == 0 and swap_eng.host_pool.used_bytes == 0
    swap_eng.alloc.check()


def test_session_rejoins_idle_slot_in_place():
    """A finished turn with a session id holds its slot IDLE (KV on
    device); the next turn extends the context and rejoins with a
    suffix-only prefill.  Tokens match a sessionless engine that
    re-prefills the full transcript, and the hit accounting shows the
    prefill actually skipped the held context."""
    spec, params = _setup()
    rng = np.random.default_rng(2)
    p1 = rng.integers(1, 128, size=14).astype(np.int32)
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=96, num_pages=24,
                          debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    t1 = eng.run([Request(0, p1.copy(), 8, session=7)])[0]
    assert eng.num_idle == 1 and eng.num_active == 0
    assert eng.pending_cost == 0          # idle slots are not device load

    extra = rng.integers(1, 128, size=6).astype(np.int32)
    p2 = np.concatenate([p1, t1.tokens, extra])
    # a queued follow-up turn charges its SUFFIX, not the held context
    eng.submit(Request(1, p2.copy(), 8, session=7))
    assert eng.pending_cost < _bucket(len(p2), cfg.page_size,
                                      cfg.max_seq) + 8
    done = []
    while eng.num_active or eng.queue:
        done.extend(eng.step())
    t2 = done[0]
    assert eng.stats["session_reuses"] == 1
    assert eng.stats["session_hit_tokens"] >= len(p1) + len(t1.tokens) - 1

    fresh = ContinuousBatchingEngine(params, spec, cfg)
    ref2 = fresh.run([Request(1, p2.copy(), 8)])[0]
    np.testing.assert_array_equal(t2.tokens, ref2.tokens)

    eng.end_session(7)
    assert eng.num_idle == 0
    eng.prefix_cache.flush() if eng.prefix_cache is not None else None
    eng.alloc.check()


def test_idle_slot_kv_immutable_under_unrelated_traffic():
    """An idle session slot's held pages are byte-immutable while other
    requests decode.  Inactive lanes still WRITE their (junk) KV every
    decode step at their pinned pos 0, and only a NULL block-table row
    — reset at the idle transition — steers those writes onto the
    sacrificial null page.  Regression: the row used to stay installed
    across the idle window, so every unrelated decode iteration wrote
    junk into the held context's first page (plus one write at the old
    pos) and the rejoined turn decoded over corrupted KV.  The token-
    identity tests alone missed it at toy width (argmax happened not
    to flip), so this pins the page BYTES, not the outputs."""
    spec, params = _setup()
    rng = np.random.default_rng(6)
    p1 = rng.integers(1, 128, size=14).astype(np.int32)
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=96, num_pages=24,
                          host_pool_bytes=50e6,
                          idle_park_iterations=10_000,   # timer never fires
                          debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    t1 = eng.run([Request(0, p1.copy(), 8, session=7)])[0]
    idle = next(s for s in eng.slots if s is not None and s.idle)
    before = eng.backend.swap_out(idle.pages)
    eng.run([Request(100 + i,
                     rng.integers(1, 128, size=10).astype(np.int32), 6)
             for i in range(3)])
    assert eng.num_idle == 1 and eng.stats["idle_parks"] == 0
    after = eng.backend.swap_out(idle.pages)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the rejoin over those pages still matches a fresh engine
    extra = rng.integers(1, 128, size=6).astype(np.int32)
    p2 = np.concatenate([p1, t1.tokens, extra])
    t2 = eng.run([Request(1, p2.copy(), 8, session=7)])[0]
    assert eng.stats["session_reuses"] == 1
    fresh = ContinuousBatchingEngine(params, spec, cfg)
    ref2 = fresh.run([Request(1, p2.copy(), 8)])[0]
    np.testing.assert_array_equal(t2.tokens, ref2.tokens)
    eng.end_session(7)
    eng.alloc.check()


def test_session_parks_to_host_and_swaps_back():
    """The idle timer parks a session's KV to the host pool (device
    pages freed); the next turn swaps it back in and continues
    token-identically.  ``end_session`` drops a parked record too."""
    spec, params = _setup()
    rng = np.random.default_rng(3)
    p1 = rng.integers(1, 128, size=14).astype(np.int32)
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=96, num_pages=24,
                          host_pool_bytes=50e6, idle_park_iterations=2,
                          debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    t1 = eng.run([Request(0, p1.copy(), 8, session=7)])[0]
    # unrelated traffic advances the iteration clock past the threshold
    eng.run([Request(100 + i,
                     rng.integers(1, 128, size=10).astype(np.int32), 6)
             for i in range(3)])
    assert eng.stats["idle_parks"] == 1 and eng.num_parked == 1
    assert eng.num_idle == 0                    # slot itself is free again
    assert eng.host_pool.used_bytes > 0         # the KV lives on the host now

    extra = rng.integers(1, 128, size=6).astype(np.int32)
    p2 = np.concatenate([p1, t1.tokens, extra])
    t2 = eng.run([Request(1, p2.copy(), 8, session=7)])[0]
    assert eng.stats["swap_ins"] == 1
    fresh = ContinuousBatchingEngine(params, spec, cfg)
    ref2 = fresh.run([Request(1, p2.copy(), 8)])[0]
    np.testing.assert_array_equal(t2.tokens, ref2.tokens)

    # second turn finished -> idle again; end_session releases it
    eng.end_session(7)
    assert eng.num_idle == 0 and eng.num_parked == 0
    eng.alloc.check()


def test_session_without_host_pool_degrades_to_recompute():
    """No host pool: an idle session slot that must yield its pages is
    simply DROPPED and the next turn cold-prefills the transcript —
    sessions never wedge the engine, they just lose the optimization."""
    spec, params = _setup()
    rng = np.random.default_rng(4)
    p1 = rng.integers(1, 128, size=14).astype(np.int32)
    # tiny pool, no prefix store: the evict tier can't save the idle
    # session's pages, so new traffic must drop them
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=64, num_pages=6,
                          enable_prefix_cache=False, debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    t1 = eng.run([Request(0, p1.copy(), 8, session=7)])[0]
    assert eng.num_idle == 1
    eng.run([Request(100 + i,
                     rng.integers(1, 128, size=14).astype(np.int32), 8)
             for i in range(3)])
    assert eng.stats["idle_drops"] >= 1 and eng.num_idle == 0
    p2 = np.concatenate([p1, t1.tokens])
    t2 = eng.run([Request(1, p2.copy(), 8, session=7)])[0]
    fresh = ContinuousBatchingEngine(params, spec, cfg)
    ref2 = fresh.run([Request(1, p2.copy(), 8)])[0]
    np.testing.assert_array_equal(t2.tokens, ref2.tokens)
    eng.end_session(7)
    eng.alloc.check()


def test_session_stale_prompt_drops_and_admits_cold():
    """A follow-up turn that does NOT extend the held context (client
    edited history) invalidates the session state and admits cold —
    correctness never depends on the client replaying faithfully."""
    spec, params = _setup()
    rng = np.random.default_rng(5)
    p1 = rng.integers(1, 128, size=14).astype(np.int32)
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=64, num_pages=24,
                          debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    eng.run([Request(0, p1.copy(), 8, session=7)])
    assert eng.num_idle == 1
    p2 = rng.integers(1, 128, size=20).astype(np.int32)  # unrelated prompt
    t2 = eng.run([Request(1, p2.copy(), 8, session=7)])[0]
    assert eng.stats["session_reuses"] == 0
    assert eng.stats["idle_drops"] == 1
    fresh = ContinuousBatchingEngine(params, spec, cfg)
    ref2 = fresh.run([Request(1, p2.copy(), 8)])[0]
    np.testing.assert_array_equal(t2.tokens, ref2.tokens)
    eng.alloc.check()


# ---------------------------------------------------------------------------
# Ring-paged sliding-window KV (windowed slots)
# ---------------------------------------------------------------------------

def _windowed_setup(window=8, layers=2, width=64, vocab=128):
    """A uniformly attn_local stack (gemma3 scaled down keeps only
    local layers at 2 layers with a 5:1 ratio) — the shape ring
    eviction auto-detects on."""
    spec = ASSIGNED["gemma3-4b"].scaled_down(
        layers=layers, width=width, vocab=vocab).with_(
        sliding_window=window, local_global_ratio=5)
    params = lm.init(jax.random.PRNGKey(0), spec)
    return spec, params


@pytest.mark.parametrize("spec_k", [1, 3])
def test_ring_engine_token_identical_to_mask_only(spec_k):
    """Ring eviction (windowed_kv auto-detected) vs the mask-only
    reference (windowed attention math, full-attention memory) on
    streams running many laps past the window: token-for-token
    identical, per-slot pages bounded at ring_pages (debug_invariants
    asserts it every step), and the ring actually recycled.  spec_k=3
    runs the same comparison under self-speculative decoding, whose
    rollbacks repeatedly land verify windows across the ring wrap."""
    spec, params = _windowed_setup(window=8)
    rng = np.random.default_rng(4)
    reqs = [Request(i, rng.integers(1, 128,
                                    size=int(rng.integers(5, 14))).astype(
                        np.int32), int(rng.integers(18, 30)))
            for i in range(6)]

    def go(windowed_kv):
        cfg = SchedulerConfig(max_slots=3, page_size=4, max_seq=48,
                              num_pages=40, spec_k=spec_k,
                              windowed_kv=windowed_kv,
                              debug_invariants=True)
        eng = ContinuousBatchingEngine(params, spec, cfg)
        done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                        for r in reqs])
        eng.alloc.check()
        return eng, sorted(done, key=lambda c: c.uid)

    ring_eng, ring_done = go(None)
    ref_eng, ref_done = go(False)
    assert ring_eng.ring and ring_eng.window == 8
    assert not ref_eng.ring and ref_eng.window == 0
    R = pc.ring_pages(8, 4, spec_k)
    assert ring_eng.layout.slots_pages(48) == R
    assert ring_eng.stats["ring_recycled_pages"] > 0
    for a, b in zip(ring_done, ref_done):
        assert a.uid == b.uid
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_ring_engine_matches_static_generate_under_pressure():
    """windowed_kv=True (assertive mode) with a pool too small for the
    mask-only layout: the ring bound is what makes the workload fit,
    preemption still fires, and every output matches the static
    windowed generate (naive attention honors the same sliding
    window).  Shared prefix pages crossing out of the window must be
    RELEASED to the store, not freed — the drain check catches either
    direction of refcount corruption."""
    spec, params = _windowed_setup(window=8)
    rng = np.random.default_rng(9)
    tmpl = rng.integers(1, 128, size=9).astype(np.int32)
    reqs = []
    for i in range(5):
        suf = rng.integers(1, 128, size=int(rng.integers(2, 6))).astype(
            np.int32)
        reqs.append(Request(i, np.concatenate([tmpl, suf]), 20))
    cfg = SchedulerConfig(max_slots=3, page_size=4, max_seq=40, num_pages=8,
                          windowed_kv=True, debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    assert eng.stats["ring_recycled_pages"] > 0
    assert eng.stats["ring_shared_released"] > 0
    scfg = ServeConfig(max_seq=40, attention_impl="naive")
    for r, c in zip(reqs, sorted(done, key=lambda c: c.uid)):
        out = generate(params, spec, {"tokens": jnp.asarray(r.prompt[None])},
                       r.max_new_tokens - 1, scfg)
        np.testing.assert_array_equal(np.asarray(out["tokens"][0]), c.tokens)
    eng.alloc.check()
    if eng.prefix_cache is not None:
        eng.prefix_cache.flush()
    eng.alloc.check()
    assert eng.alloc.free_pages == eng.layout.num_pages - 1


def test_windowed_kv_gating():
    """windowed_kv=True must refuse stacks with ANY global-attention
    layer (one block table serves all layers); auto-detect (None) must
    quietly fall back to mask-only there, and stay off when the spec
    has no sliding window at all."""
    spec_global, params_g = _setup()          # granite: full attention
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=32,
                          num_pages=16, windowed_kv=True)
    with pytest.raises(ValueError, match="windowed_kv"):
        ContinuousBatchingEngine(params_g, spec_global, cfg)
    # 6 gemma3 layers at ratio 5 include one global layer -> no ring
    spec_mixed = ASSIGNED["gemma3-4b"].scaled_down(
        layers=6, width=64, vocab=128).with_(
        sliding_window=8, local_global_ratio=5)
    assert "attn_global" in list(spec_mixed.layer_kinds())
    assert pc.ring_window(spec_mixed, None) == 0
    with pytest.raises(ValueError):
        pc.ring_window(spec_mixed, True)
    cfg_off = SchedulerConfig(max_slots=2, page_size=8, max_seq=32,
                              num_pages=16, windowed_kv=None)
    eng = ContinuousBatchingEngine(params_g, spec_global, cfg_off)
    assert not eng.ring and eng.window == 0


def test_ring_session_rejoin_past_window():
    """Session turns on a ring engine: the held slot's ring has wrapped
    by the time the follow-up turn arrives, the rejoin suffix-prefills
    only the new tokens, and the transcript matches a fresh ring engine
    that re-prefills the full history."""
    spec, params = _windowed_setup(window=8)
    rng = np.random.default_rng(6)
    p1 = rng.integers(1, 128, size=7).astype(np.int32)
    cfg = SchedulerConfig(max_slots=2, page_size=4, max_seq=64, num_pages=24,
                          windowed_kv=True, debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    t1 = eng.run([Request(0, p1.copy(), 12, session=3)])[0]
    assert eng.num_idle == 1
    extra = rng.integers(1, 128, size=5).astype(np.int32)
    p2 = np.concatenate([p1, t1.tokens, extra])
    t2 = eng.run([Request(1, p2.copy(), 10, session=3)])[0]
    assert eng.stats["session_reuses"] == 1
    fresh = ContinuousBatchingEngine(params, spec, cfg)
    ref2 = fresh.run([Request(1, p2.copy(), 10)])[0]
    np.testing.assert_array_equal(t2.tokens, ref2.tokens)
    eng.end_session(3)
    eng.alloc.check()
