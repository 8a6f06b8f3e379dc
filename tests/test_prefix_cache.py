"""Refcounted prefix caching + lazy allocation/preemption invariants.

Five blocks:

* refcounted ``PageAllocator`` fuzz — random interleavings of
  alloc/share/free/evict against a model of expected refcounts, both as
  a hypothesis property (where dev deps are installed) and as an
  always-on numpy interleaving sweep;
* windowed (ring) slot fuzz — the same store/allocator under ring
  advancement: slots hold at most ``R`` pages, advancing over an
  exclusive entry recycles the page in place, and advancing over a
  SHARED entry (a prefix page falling out of the window) must
  decrement the sharer's reference and never free it under the store
  or other holders;
* ``PrefixCache`` store semantics (cumulative hashing, LRU eviction
  that skips shared pages, collision guard, flush);
* scheduler equivalence — prefix caching ON is token-for-token prefix
  caching OFF and per-request static ``generate``, fp32 and int8,
  including a shared prefix ending mid-page (copy-on-write path);
* preemption — a workload sized to force eviction completes with
  correct outputs, the victim's re-run prefill hits its own cached
  prefix pages, and the allocator drains clean.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED
from repro.models import lm
from repro.serve import paged_cache as pc
from repro.serve.engine import ServeConfig, generate
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   SchedulerConfig)


def _setup(layers=2, width=64, vocab=128):
    spec = ASSIGNED["granite-3-8b"].scaled_down(layers=layers, width=width,
                                                vocab=vocab)
    params = lm.init(jax.random.PRNGKey(0), spec)
    return spec, params


# ---------------------------------------------------------------------------
# Refcounted allocator: model-based fuzz
# ---------------------------------------------------------------------------

def _fuzz_allocator_ops(seed: int, steps: int = 120, num_pages: int = 33):
    """One random interleaving of alloc/share/free(+evict-like drains)
    against a reference refcount model; check() after every op."""
    rng = np.random.default_rng(seed)
    alloc = pc.PageAllocator(num_pages)
    model = {}                            # page -> refcount
    for _ in range(steps):
        op = rng.random()
        live = [p for p in model]
        if op < 0.35 and alloc.can_alloc(1 + int(rng.integers(0, 4))):
            n = 1 + int(rng.integers(0, 4))
            if alloc.can_alloc(n):
                for p in alloc.alloc(n):
                    assert p != pc.NULL_PAGE and p not in model
                    model[p] = 1
        elif op < 0.55 and live:
            p = int(rng.choice(live))
            alloc.share([p])
            model[p] += 1
        elif op < 0.9 and live:
            p = int(rng.choice(live))
            alloc.free([p])
            model[p] -= 1
            if model[p] == 0:
                del model[p]
        elif live:                        # evict-like: drain a whole page
            p = int(rng.choice(live))
            alloc.free([p] * model[p])
            del model[p]
        assert alloc._ref == model
        alloc.check()
    for p, c in list(model.items()):
        alloc.free([p] * c)
    alloc.check()
    assert alloc.free_pages == num_pages - 1


def test_allocator_fuzz_numpy_interleavings():
    """200 random interleavings (always runs, no dev deps needed)."""
    for seed in range(200):
        _fuzz_allocator_ops(seed)


def test_allocator_share_free_null_rejected():
    alloc = pc.PageAllocator(8)
    pages = alloc.alloc(2)
    alloc.share(pages)
    alloc.free(pages)
    alloc.free(pages)                     # second release drains to zero
    with pytest.raises(ValueError):
        alloc.free(pages)                 # over-release
    with pytest.raises(ValueError):
        alloc.share([pages[0]])           # share of a free page
    with pytest.raises(ValueError):
        alloc.share([pc.NULL_PAGE])
    with pytest.raises(MemoryError):
        alloc.alloc(99)
    alloc.check()


def _drive_evict_cow_share(ops):
    """Walk one op tape of ``PrefixCache.evict`` interleaved with
    admission-style sharing and mid-page copy-on-write: requests share
    cached full pages, CoW a partial hit into a fresh page (the SOURCE
    page stays the store's — exactly what ``copy_page`` does on
    device), and LRU eviction drains only store-only pages.  Asserts
    no request-held page is ever freed out from under its holder and
    the allocator invariants hold after every op."""
    alloc = pc.PageAllocator(24)
    store = pc.PrefixCache(alloc, page_size=4)
    base = np.arange(1000, dtype=np.int32)
    chains = []                     # registered prompts
    requests = {}                   # rid -> pages held (with multiplicity)
    next_rid = 0
    for kind, arg in ops:
        if kind == 0:               # register a fresh unique chain
            plen = 3 + arg % 9
            prompt = np.concatenate(
                [np.asarray([2000 + len(chains)], np.int32),
                 base[:plen]])
            n = pc.pages_needed(len(prompt), 4)
            if alloc.can_alloc(n):
                pages = alloc.alloc(n)
                store.register_prompt(prompt, pages)
                alloc.free(pages)   # owner finishes; store-only now
                chains.append(prompt)
        elif kind == 1 and chains:  # admission hit: share + CoW
            prompt = chains[arg % len(chains)]
            ext = np.concatenate([prompt, base[900:901]])
            m = store.lookup(ext)
            held = list(m.full_pages)
            if held:
                alloc.share(held)
            if m.partial is not None and alloc.can_alloc(1):
                # CoW: sharer appends into a COPY; source stays
                held.extend(alloc.alloc(1))
            if held:
                requests[next_rid] = held
                next_rid += 1
        elif kind == 2 and requests:   # a request finishes
            rid = sorted(requests)[arg % len(requests)]
            alloc.free(requests.pop(rid))
        elif kind == 3:             # pressure: LRU evict
            want = 1 + arg % 4
            before = alloc.free_pages
            freed = store.evict(want)
            assert freed <= want
            assert alloc.free_pages == before + freed
        # no request-held page may lose its reference
        for pages in requests.values():
            for p in set(pages):
                assert alloc.refcount(p) >= pages.count(p)
        alloc.check()
    for pages in requests.values():
        alloc.free(pages)
    store.flush()
    alloc.check()
    assert alloc.free_pages == 23


def test_prefix_store_evict_cow_share_numpy_interleavings():
    """150 random evict x CoW x share tapes (always runs, no dev
    deps needed — the hypothesis property below shrinks failures
    where it is installed)."""
    for seed in range(150):
        rng = np.random.default_rng(seed)
        ops = [(int(rng.integers(0, 4)), int(rng.integers(0, 10 ** 6)))
               for _ in range(120)]
        _drive_evict_cow_share(ops)


def _drive_windowed_ring_slots(ops, R=3):
    """Walk one op tape of WINDOWED (ring) slots against the refcounted
    store: slots admit on prefix hits (shared pages land at ring
    entries), their write head advances page by page, and once a slot
    holds ``R`` pages an advance lands on the ring's oldest entry — an
    EXCLUSIVE page is recycled in place (no allocator traffic at all),
    a SHARED page (a cached prefix page that just fell out of the
    window) gets the slot's reference decremented while the store and
    any co-holders keep it alive.  Mirrors
    ``scheduler._ring_extend``'s exact allocator discipline; asserts
    the ring bound, holder refcounts and allocator invariants after
    every op, and a clean drain."""
    alloc = pc.PageAllocator(20)
    store = pc.PrefixCache(alloc, page_size=4)
    base = np.arange(1000, dtype=np.int32)
    chains = []
    slots = {}                      # sid -> {"pages": [...], "abs": int}
    next_sid = 0
    recycled = released = 0
    for kind, arg in ops:
        if kind == 0:               # register a fresh chain in the store
            plen = 3 + arg % 9
            prompt = np.concatenate(
                [np.asarray([2000 + len(chains)], np.int32), base[:plen]])
            n = pc.pages_needed(len(prompt), 4)
            if alloc.can_alloc(n):
                pages = alloc.alloc(n)
                store.register_prompt(prompt, pages)
                alloc.free(pages)   # owner finishes; store-only now
                chains.append(prompt)
        elif kind == 1 and chains:  # admit a windowed slot on a hit
            prompt = chains[arg % len(chains)]
            ext = np.concatenate([prompt, base[900:902]])
            m = store.lookup(ext)
            held = list(m.full_pages[:R])   # ring slots hold <= R entries
            if held:
                alloc.share(held)
                slots[next_sid] = {"pages": held, "abs": len(held)}
                next_sid += 1
        elif kind == 2 and slots:   # advance a slot's write head one page
            s = slots[sorted(slots)[arg % len(slots)]]
            if len(s["pages"]) < R:
                if alloc.can_alloc(1):
                    s["pages"].append(alloc.alloc(1)[0])
                    s["abs"] += 1
            else:
                e = s["abs"] % R
                old = s["pages"][e]
                if alloc.refcount(old) == 1:
                    recycled += 1   # exclusive: reuse in place, no traffic
                    s["abs"] += 1
                elif alloc.can_alloc(1):
                    before = alloc.refcount(old)
                    s["pages"][e] = alloc.alloc(1)[0]
                    alloc.free([old])
                    assert alloc.refcount(old) == before - 1 >= 1, \
                        "a shared prefix page falling out of the window " \
                        "must decrement, never free under its holders"
                    s["abs"] += 1
                    released += 1
        elif kind == 3 and slots:   # a slot finishes
            sid = sorted(slots)[arg % len(slots)]
            alloc.free(slots.pop(sid)["pages"])
        elif kind == 4:             # pressure: LRU evict store-only pages
            want = 1 + arg % 4
            before_free = alloc.free_pages
            freed = store.evict(want)
            assert freed <= want
            assert alloc.free_pages == before_free + freed
        for s in slots.values():
            assert len(s["pages"]) <= R, "ring bound violated"
            assert s["abs"] >= len(s["pages"])
            for p in set(s["pages"]):
                assert alloc.refcount(p) >= s["pages"].count(p)
        alloc.check()
    for s in slots.values():
        alloc.free(s["pages"])
    store.flush()
    alloc.check()
    assert alloc.free_pages == 19
    return recycled, released


def test_windowed_ring_slots_numpy_interleavings():
    """150 random windowed-slot tapes (always runs); across the sweep
    both ring paths — in-place recycle AND shared-entry release — must
    actually fire, or the tape generator stopped exercising the ring."""
    recycled = released = 0
    for seed in range(150):
        rng = np.random.default_rng(seed)
        ops = [(int(rng.integers(0, 5)), int(rng.integers(0, 10 ** 6)))
               for _ in range(120)]
        r, s = _drive_windowed_ring_slots(ops)
        recycled += r
        released += s
    assert recycled > 0 and released > 0


# hypothesis property: random op tapes never violate the invariants.
# Imported guardedly (NOT module-level importorskip) so the numpy sweep
# above still runs where dev deps are absent.
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 10 ** 6)),
                    min_size=1, max_size=150))
    @settings(max_examples=200, deadline=None)
    def test_allocator_refcount_property(ops):
        """Free XOR refcount>=1 for every page, null page never handed
        out, refcounts hit zero exactly when all sharers release —
        under arbitrary alloc/share/free/drain interleavings."""
        alloc = pc.PageAllocator(17)
        model = {}
        for kind, arg in ops:
            live = sorted(model)
            if kind == 0:
                n = 1 + arg % 4
                if alloc.can_alloc(n):
                    for p in alloc.alloc(n):
                        assert p != pc.NULL_PAGE and p not in model
                        model[p] = 1
            elif kind == 1 and live:
                p = live[arg % len(live)]
                alloc.share([p])
                model[p] += 1
            elif kind == 2 and live:
                p = live[arg % len(live)]
                alloc.free([p])
                model[p] -= 1
                if model[p] == 0:
                    del model[p]
            elif kind == 3 and live:
                p = live[arg % len(live)]
                alloc.free([p] * model.pop(p))
            assert alloc._ref == model
            alloc.check()
        for p, c in list(model.items()):
            alloc.free([p] * c)
        alloc.check()
        assert alloc.free_pages == 16
if _HAVE_HYPOTHESIS:
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 10 ** 6)),
                    min_size=1, max_size=120))
    @settings(max_examples=150, deadline=None)
    def test_prefix_store_evict_cow_share_property(ops):
        """Shrinking search over the same evict x CoW x share tape
        walker the numpy sweep drives (``_drive_evict_cow_share``)."""
        _drive_evict_cow_share(ops)

    @given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 10 ** 6)),
                    min_size=1, max_size=120))
    @settings(max_examples=150, deadline=None)
    def test_windowed_ring_slots_property(ops):
        """Shrinking search over the windowed-slot tape walker
        (``_drive_windowed_ring_slots``)."""
        _drive_windowed_ring_slots(ops)
else:
    @pytest.mark.skip(reason="hypothesis not installed (see "
                             "requirements-dev.txt); the numpy "
                             "interleaving sweep covers the invariants")
    def test_allocator_refcount_property():
        pass

    @pytest.mark.skip(reason="hypothesis not installed (see "
                             "requirements-dev.txt); the engine-level "
                             "prefix/preemption tests cover evict + CoW")
    def test_prefix_store_evict_cow_share_property():
        pass

    @pytest.mark.skip(reason="hypothesis not installed (see "
                             "requirements-dev.txt); the numpy "
                             "interleaving sweep covers the ring "
                             "invariants")
    def test_windowed_ring_slots_property():
        pass


# ---------------------------------------------------------------------------
# Prefix store semantics
# ---------------------------------------------------------------------------

def test_prefix_store_lookup_full_partial_and_cap():
    alloc = pc.PageAllocator(16)
    store = pc.PrefixCache(alloc, page_size=4)
    prompt = np.arange(10, dtype=np.int32)        # 2 full pages + 2 tail
    pages = alloc.alloc(3)
    store.insert(prompt[:4], pages[0], 4)
    store.insert(prompt[:8], pages[1], 4)
    store.insert(prompt[:10], pages[2], 2)

    m = store.lookup(prompt)                      # same prompt: cap at len-1
    # tail entry holds ALL 10 tokens; with only 9 matchable it can't hit
    assert m.full_pages == pages[:2] and m.tokens == 8 and m.partial is None

    ext = np.concatenate([prompt, np.arange(100, 103, dtype=np.int32)])
    m = store.lookup(ext)                         # extension: full tail reuse
    assert m.full_pages == pages[:2]
    assert m.partial == (pages[2], 2) and m.tokens == 10

    other = ext.copy()
    other[2] = 99                                 # diverges inside page 0
    m = store.lookup(other)
    assert m.full_pages == [] and m.partial is None and m.tokens == 0

    store.flush()
    alloc.free(pages)
    alloc.check()
    assert alloc.free_pages == 15


def test_prefix_store_evict_skips_shared_pages():
    alloc = pc.PageAllocator(8)
    store = pc.PrefixCache(alloc, page_size=4)
    a, b = alloc.alloc(2)
    store.insert(np.arange(4, dtype=np.int32), a, 4)
    store.insert(np.arange(8, dtype=np.int32), b, 4)
    alloc.free([b])                               # b now store-only
    alloc.free([a])
    alloc.share([a])                              # a shared by a "request"
    assert store.evict(2) == 1                    # only b can drain
    assert alloc.refcount(a) == 2 and alloc.refcount(b) == 0
    assert len(store) == 1                        # a's entry survives
    store.flush()
    alloc.free([a])
    alloc.check()


def test_prefix_store_keys_are_content_addressed():
    """Same-length, different-content prefixes never cross-match: the
    key is (length, blake2b-128 of ALL prefix tokens), so divergence
    anywhere in the prefix — not just the final chunk — misses."""
    alloc = pc.PageAllocator(8)
    store = pc.PrefixCache(alloc, page_size=4)
    pages = alloc.alloc(2)
    a = np.arange(8, dtype=np.int32)
    store.insert(a[:4], pages[0], 4)
    store.insert(a[:8], pages[1], 4)
    b = a.copy()
    b[1] = 77                                     # diverge in page 0
    m = store.lookup(np.concatenate([b, b]))
    assert m.tokens == 0 and m.full_pages == []
    c = a.copy()
    c[5] = 77                                     # diverge in page 1 only
    m = store.lookup(np.concatenate([c, c]))
    assert m.full_pages == [pages[0]] and m.tokens == 4


# ---------------------------------------------------------------------------
# Equivalence: prefix ON == prefix OFF == static generate
# ---------------------------------------------------------------------------

def _templated_reqs(rng, n, template_len, vocab=128):
    """Half the templates end mid-page for page_size 16; one request is
    an exact-prefix EXTENSION of another, exercising copy-on-write."""
    t1 = rng.integers(0, vocab, size=template_len).astype(np.int32)
    t2 = rng.integers(0, vocab, size=template_len + 5).astype(np.int32)
    reqs = []
    for i in range(n):
        t = (t1, t2)[i % 2]
        suf = rng.integers(0, vocab,
                           size=int(rng.integers(4, 11))).astype(np.int32)
        reqs.append(Request(i, np.concatenate([t, suf]),
                            int(rng.integers(3, 7))))
    # exact extension of request 0's full prompt -> mid-page partial hit
    reqs.append(Request(n, np.concatenate(
        [reqs[0].prompt, rng.integers(0, vocab, size=7).astype(np.int32)]), 4))
    return reqs


def _run_engine(params, spec, reqs, dtype="fp32", prefix=True, **kw):
    cfg = SchedulerConfig(max_slots=kw.get("slots", 3), page_size=16,
                          max_seq=kw.get("max_seq", 96),
                          num_pages=kw.get("num_pages", 48),
                          cache_dtype=dtype, enable_prefix_cache=prefix,
                          debug_invariants=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    return eng, done


@pytest.mark.parametrize("dtype", ["fp32", "int8", "int4"])
def test_prefix_cache_on_off_token_identical(dtype):
    """Scheduler output with prefix caching ON is token-for-token the
    OFF path, for all three cache dtypes (int4 = nibble-packed pages,
    where the CoW mid-page case also splits a shared byte)."""
    spec, params = _setup()
    rng = np.random.default_rng(0)
    reqs = _templated_reqs(rng, 6, template_len=20)
    eng_off, off = _run_engine(params, spec, reqs, dtype, prefix=False)
    eng_on, on = _run_engine(params, spec, reqs, dtype, prefix=True)
    assert [c.uid for c in on] == [c.uid for c in off]
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.prompt_len == b.prompt_len
    assert eng_on.stats["prefix_hit_tokens"] > 0
    assert eng_on.stats["cow_copies"] >= 1          # extension request
    # hits skip at least 30% of the prefill tokens
    assert eng_on.stats["prefill_tokens"] <= \
        0.7 * eng_off.stats["prefill_tokens"]
    # store retains pages by refcount until flushed; then fully clean
    eng_on.alloc.check()
    eng_on.prefix_cache.flush()
    eng_on.alloc.check()
    assert eng_on.alloc.free_pages == eng_on.layout.num_pages - 1


def test_prefix_cache_matches_static_generate_fp32():
    spec, params = _setup()
    rng = np.random.default_rng(1)
    reqs = _templated_reqs(rng, 4, template_len=20)
    _, done = _run_engine(params, spec, reqs, "fp32", prefix=True)
    scfg = ServeConfig(max_seq=96, attention_impl="naive")
    for r, c in zip(reqs, done):
        out = generate(params, spec, {"tokens": jnp.asarray(r.prompt[None])},
                       r.max_new_tokens - 1, scfg)
        np.testing.assert_array_equal(np.asarray(out["tokens"][0]), c.tokens)


# ---------------------------------------------------------------------------
# Preemption
# ---------------------------------------------------------------------------

def test_preemption_under_pressure_correct_and_clean():
    """A pool too small for all admitted contexts forces preemption; the
    drained outputs still match per-request static generate and every
    page reference unwinds."""
    spec, params = _setup()
    rng = np.random.default_rng(2)
    T = rng.integers(0, 128, size=16).astype(np.int32)
    reqs = [Request(i, np.concatenate(
        [T, rng.integers(0, 128, size=6).astype(np.int32)]), 12)
        for i in range(4)]
    cfg = SchedulerConfig(max_slots=4, page_size=8, max_seq=48, num_pages=11,
                          enable_prefix_cache=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    assert eng.stats["preemptions"] >= 1
    assert len(done) == 4 and all(len(c.tokens) == 12 for c in done)
    assert all(c.prompt_len == 22 for c in done)    # original, not resumed
    scfg = ServeConfig(max_seq=48, attention_impl="naive")
    for r, c in zip(reqs, done):
        out = generate(params, spec, {"tokens": jnp.asarray(r.prompt[None])},
                       r.max_new_tokens - 1, scfg)
        np.testing.assert_array_equal(np.asarray(out["tokens"][0]), c.tokens)
    eng.alloc.check()
    eng.prefix_cache.flush()
    eng.alloc.check()
    assert eng.alloc.free_pages == eng.layout.num_pages - 1


def test_preempted_victim_rerun_reuses_cached_prefix():
    """Distinct prompts (no cross-request sharing): any prefix hit must
    come from the victim's own cached pages on re-admission."""
    spec, params = _setup()
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, 128, size=16).astype(np.int32), 12)
            for i in range(2)]
    # 7 usable pages, page 8: both admit at 2 pages; growth toward 4
    # pages each cannot fit -> the newest slot is evicted, its 2 prompt
    # pages survive in the store (refcount), and its re-run prefill
    # matches them while the survivor still holds its own 4
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=48, num_pages=8,
                          enable_prefix_cache=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    assert eng.stats["preemptions"] >= 1
    # the victim's re-run is a recompute resume: its self-hit lands in
    # the recompute counters, while prefix_hit_tokens stays 0 because
    # the two prompts are distinct (no cross-request sharing arrived)
    assert eng.stats["recompute_hit_tokens"] >= 16  # victim's own pages
    assert eng.stats["prefix_hit_tokens"] == 0
    scfg = ServeConfig(max_seq=48, attention_impl="naive")
    for r, c in zip(reqs, done):
        out = generate(params, spec, {"tokens": jnp.asarray(r.prompt[None])},
                       r.max_new_tokens - 1, scfg)
        np.testing.assert_array_equal(np.asarray(out["tokens"][0]), c.tokens)
    eng.alloc.check()


def test_admission_degrades_match_instead_of_livelocking():
    """Regression: when pinning a matched prefix makes the last pages a
    request needs unevictable, admission must degrade the match (drop
    the partial, then the full hits) rather than spin forever."""
    spec, params = _setup()
    rng = np.random.default_rng(5)
    A = rng.integers(0, 128, size=55).astype(np.int32)
    B = np.concatenate([A, rng.integers(0, 128, size=5).astype(np.int32)])
    # 4 usable pages (page 16): A leaves 3 full + 1 tail entry filling
    # the whole pool; B matches all 4 but needs one fresh page
    cfg = SchedulerConfig(max_slots=1, page_size=16, max_seq=64, num_pages=5,
                          enable_prefix_cache=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run([Request(0, A, 9), Request(1, B, 4)])
    assert len(done) == 2 and len(done[1].tokens) == 4
    assert eng.stats["prefix_hit_tokens"] > 0     # degraded, not disabled
    scfg = ServeConfig(max_seq=64, attention_impl="naive")
    out = generate(params, spec, {"tokens": jnp.asarray(B[None])}, 3, scfg)
    np.testing.assert_array_equal(np.asarray(out["tokens"][0]),
                                  done[1].tokens)
    eng.alloc.check()


def test_submit_rejects_never_admittable_under_lazy_allocation():
    """Lazy allocation must still bound admission by the SOLO worst case:
    a request whose full context outsizes the pool can never finish and
    is rejected at submit."""
    spec, params = _setup()
    cfg = SchedulerConfig(max_slots=2, page_size=8, max_seq=64, num_pages=4)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(Request(0, np.zeros(40, np.int32), 8))   # 6 pages > 3
    # boundary: exactly fills the pool solo -> admissible
    eng.submit(Request(1, np.zeros(12, np.int32), 12))      # 3 pages == 3
    done = eng.run([])
    assert len(done) == 1 and len(done[0].tokens) == 12
    eng.alloc.check()
