"""Quantized paged-attention fast path: kernel/ref parity for int8 and
nibble-packed int4 pages, dispatch consistency across cache dtypes, the
int4 read-modify-write pool plumbing, and the end-to-end
``cache_dtype="int4"`` scheduler run.

The Pallas kernel body executes in interpret mode on this CPU
container; ``kernels/ref.py`` (gather + dequant-after-gather) is the
oracle.  Fixtures are argmax-stable: int4 KV error on the scaled-down
models stays ~2-3% of the logit range, which the greedy-token
assertions pin.
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
                                  quantize_kv_int4, quantize_kv_int8,
                                  unpack_int4)
from repro.serve import paged_cache as pc
from repro.serve.engine import ServeConfig, generate
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   SchedulerConfig)


def _quantize_pools(quant, kf, vf):
    """Float pools -> (k_pages, v_pages, k_scale, v_scale) per layout.
    Scales come back LANE-MAJOR (P, KV, page) — the pool layout."""
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


def _pool_fixture(seed=0, B=4, H=4, KV=2, D=16, page=8, pps=3):
    rng = np.random.default_rng(seed)
    P = B * pps + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[:B * pps].reshape(B, pps), jnp.int32)
    return q, kf, vf, bt


# ---------------------------------------------------------------------------
# Kernel vs reference parity (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant,tol", [("int8", 1e-5), ("int4", 1e-4)])
@pytest.mark.parametrize("window", [0, 7])
@pytest.mark.parametrize("H,KV,D", [(4, 2, 16), (8, 1, 32), (4, 4, 16)])
def test_quantized_kernel_matches_ref(quant, tol, window, H, KV, D):
    """Ragged lengths (incl. a zero-length slot and odd lengths that end
    mid-byte for int4), GQA group folding, sliding window."""
    q, kf, vf, bt = _pool_fixture(seed=H * 31 + KV, H=H, KV=KV, D=D)
    lengths = jnp.asarray([5, 21, 0, 24], jnp.int32)
    kp, vp, ks, vs = _quantize_pools(quant, kf, vf)
    o_ref = ref.paged_attention_ref(q, kp, vp, bt, lengths, window=window,
                                    k_scale=ks, v_scale=vs)
    o_pal = paged_attention_pallas(q, kp, vp, bt, lengths, window=window,
                                   k_scale=ks, v_scale=vs, interpret=True)
    assert float(jnp.max(jnp.abs(o_pal - o_ref))) <= tol
    assert float(jnp.max(jnp.abs(o_pal[2]))) == 0.0   # length-0 slot -> zeros


def test_int4_ref_matches_unpacked_fp32_oracle():
    """The int4 ref path IS dequant-after-gather: unpacking the pool by
    hand and running the float ref on q*scale pages matches exactly."""
    q, kf, vf, bt = _pool_fixture(seed=3)
    lengths = jnp.asarray([7, 13, 2, 24], jnp.int32)
    kp, vp, ks, vs = _quantize_pools("int4", kf, vf)
    kd = unpack_int4(kp, axis=1).astype(jnp.float32) * \
        jnp.moveaxis(ks, -1, -2)[..., None]
    vd = unpack_int4(vp, axis=1).astype(jnp.float32) * \
        jnp.moveaxis(vs, -1, -2)[..., None]
    a = ref.paged_attention_ref(q, kd, vd, bt, lengths)
    b = ref.paged_attention_ref(q, kp, vp, bt, lengths, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


def test_pack_unpack_int4_axis_roundtrip():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-7, 8, size=(5, 8, 3, 4)), jnp.int8)
    for axis in (0, 1):
        if q.shape[axis] % 2:
            continue
        p = pack_int4(q, axis=axis)
        assert p.shape[axis] == q.shape[axis] // 2
        np.testing.assert_array_equal(np.asarray(unpack_int4(p, axis=axis)),
                                      np.asarray(q))


# ---------------------------------------------------------------------------
# Ring block tables: the O(window) sliding-window pool layout
# ---------------------------------------------------------------------------

RING_PAGE, RING_R, RING_WINDOW = 8, 3, 13
# B=5 lengths cover: empty slot, single page, ring-capacity boundary
# (exactly R full pages, unwrapped), just-wrapped ending mid-byte for
# int4, and a deep wrap several laps in
RING_LENGTHS = [0, 8, 24, 25, 41]


def _ring_fixture(seed, lengths, wq=0, B=5, H=4, KV=2, D=16):
    """A flat pool + the SAME tokens laid out as the ring writer leaves
    them: absolute page ``ap`` scattered to ring entry ``ap % R`` in
    order, later laps overwriting earlier ones; never-written entries
    hold garbage (they must be masked, which is what the tests pin)."""
    page, R = RING_PAGE, RING_R
    rng = np.random.default_rng(seed)
    pps = (max(lengths) + page - 1) // page
    Pf = B * pps + 1
    qshape = (B, wq, H, D) if wq else (B, H, D)
    q = jnp.asarray(rng.normal(size=qshape), jnp.float32)
    kf = rng.normal(size=(Pf, page, KV, D))
    vf = rng.normal(size=(Pf, page, KV, D))
    bt_flat = np.arange(1, Pf).reshape(B, pps)
    Pr = B * R + 1
    kr = rng.normal(size=(Pr, page, KV, D))          # stale-entry garbage
    vr = rng.normal(size=(Pr, page, KV, D))
    bt_ring = np.arange(1, Pr).reshape(B, R)
    for b, ln in enumerate(lengths):
        for ap in range((int(ln) - 1) // page + 1 if ln else 0):
            kr[bt_ring[b, ap % R]] = kf[bt_flat[b, ap]]
            vr[bt_ring[b, ap % R]] = vf[bt_flat[b, ap]]
    return (q, jnp.asarray(kf, jnp.float32), jnp.asarray(vf, jnp.float32),
            jnp.asarray(bt_flat, jnp.int32), jnp.asarray(kr, jnp.float32),
            jnp.asarray(vr, jnp.float32), jnp.asarray(bt_ring, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("quant", ["fp32", "int8", "int4"])
def test_ring_ref_matches_flat_oracle(quant):
    """The ring layout must be pure relabeling: the flat ref (full
    O(context) table) with the same window is the oracle, and page
    contents are identical where valid, so agreement is exact — any
    drift means the ring token math read a stale or wrong entry.
    Quantized pools quantize per token row, so garbage rows in
    recycled/unwritten ring pages cannot leak into valid rows (int4's
    mid-byte nibble neighbour included: length 25 ends mid-byte)."""
    q, kf, vf, btf, kr, vr, btr, lengths = _ring_fixture(7, RING_LENGTHS)
    kpf, vpf, ksf, vsf = _quantize_pools(quant, kf, vf)
    kpr, vpr, ksr, vsr = _quantize_pools(quant, kr, vr)
    o_flat = ref.paged_attention_ref(q, kpf, vpf, btf, lengths,
                                     window=RING_WINDOW, k_scale=ksf,
                                     v_scale=vsf)
    o_ring = ref.paged_attention_ref(q, kpr, vpr, btr, lengths,
                                     window=RING_WINDOW, ring=True,
                                     k_scale=ksr, v_scale=vsr)
    np.testing.assert_allclose(np.asarray(o_flat), np.asarray(o_ring),
                               rtol=2e-6, atol=2e-6)
    assert float(jnp.max(jnp.abs(o_ring[0]))) == 0.0   # empty slot


@pytest.mark.parametrize("quant,tol", [("fp32", 2e-6), ("int8", 1e-5),
                                       ("int4", 1e-4)])
def test_ring_kernel_matches_ref(quant, tol):
    """Pallas ring mode (grid over the R ring entries, ring token
    positions in the mask) vs the gather ref, all cache dtypes, at the
    page-boundary / wrap lengths."""
    q, _, _, _, kr, vr, btr, lengths = _ring_fixture(8, RING_LENGTHS)
    kp, vp, ks, vs = _quantize_pools(quant, kr, vr)
    o_ref = ref.paged_attention_ref(q, kp, vp, btr, lengths,
                                    window=RING_WINDOW, ring=True,
                                    k_scale=ks, v_scale=vs)
    o_pal = paged_attention_pallas(q, kp, vp, btr, lengths,
                                   window=RING_WINDOW, ring=True,
                                   k_scale=ks, v_scale=vs, interpret=True)
    assert float(jnp.max(jnp.abs(o_pal - o_ref))) <= tol
    assert float(jnp.max(jnp.abs(o_pal[0]))) == 0.0


@pytest.mark.parametrize("quant,tol", [("fp32", 2e-6), ("int8", 1e-5),
                                       ("int4", 1e-4)])
def test_ring_verify_window_rollback_across_wrap(quant, tol):
    """Spec-k verify windows on a ring: K=4 queries share one pass, and
    the lengths put the EARLIEST query's window start on the ring's
    oldest live entry — the post-rollback re-verify after a rejected
    draft crossed the wrap (``ring_pages``'s +1 straddle page is what
    guarantees that entry was never recycled).  Flat oracle + kernel
    parity; exactness vs the oracle pins the per-query ring masks."""
    WQ = 4
    lengths = [17, 24, 28, 33, 41]   # boundary, just-wrapped, deep wrap
    q, kf, vf, btf, kr, vr, btr, ln = _ring_fixture(9, lengths, wq=WQ)
    kpf, vpf, ksf, vsf = _quantize_pools(quant, kf, vf)
    kpr, vpr, ksr, vsr = _quantize_pools(quant, kr, vr)
    o_flat = ref.paged_attention_ref(q, kpf, vpf, btf, ln,
                                     window=RING_WINDOW, k_scale=ksf,
                                     v_scale=vsf)
    o_ring = ref.paged_attention_ref(q, kpr, vpr, btr, ln,
                                     window=RING_WINDOW, ring=True,
                                     k_scale=ksr, v_scale=vsr)
    np.testing.assert_allclose(np.asarray(o_flat), np.asarray(o_ring),
                               rtol=2e-6, atol=2e-6)
    o_pal = paged_attention_pallas(q, kpr, vpr, btr, ln,
                                   window=RING_WINDOW, ring=True,
                                   k_scale=ksr, v_scale=vsr, interpret=True)
    assert float(jnp.max(jnp.abs(o_pal - o_ring))) <= tol


# ---------------------------------------------------------------------------
# ops dispatch: identical rules for all three cache dtypes
# ---------------------------------------------------------------------------

def test_resolve_paged_impl_rules(monkeypatch):
    assert ops._resolve_paged_impl("ref") == "ref"
    assert ops._resolve_paged_impl("pallas") == "pallas"
    assert ops._resolve_paged_impl("auto") == "ref"        # CPU container
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    assert ops._resolve_paged_impl("auto") == "pallas"     # TPU: all dtypes
    with pytest.raises(ValueError):
        ops._resolve_paged_impl("bogus")


@pytest.mark.parametrize("quant,tol", [("fp32", 1e-6), ("int8", 1e-5),
                                       ("int4", 1e-4)])
def test_ops_impl_override_consistent(quant, tol):
    """impl="pallas" (kernel body, interpret off-TPU) and impl="ref"
    agree for every cache dtype; auto lowers the ref path on CPU."""
    q, kf, vf, bt = _pool_fixture(seed=11)
    lengths = jnp.asarray([5, 20, 0, 23], jnp.int32)
    kp, vp, ks, vs = _quantize_pools(quant, kf, vf)
    outs = {impl: ops.paged_attention(q, kp, vp, bt, lengths, k_scale=ks,
                                      v_scale=vs, impl=impl)
            for impl in ("ref", "pallas", "auto")}
    assert float(jnp.max(jnp.abs(outs["pallas"] - outs["ref"]))) <= tol
    np.testing.assert_array_equal(np.asarray(outs["auto"]),
                                  np.asarray(outs["ref"]))


# ---------------------------------------------------------------------------
# int4 pool layout + single-sequence decode equivalence
# ---------------------------------------------------------------------------

def _setup(layers=2, width=64, vocab=128):
    spec = ASSIGNED["granite-3-8b"].scaled_down(layers=layers, width=width,
                                                vocab=vocab)
    params = lm.init(jax.random.PRNGKey(0), spec)
    return spec, params


def test_init_paged_cache_int4_layout():
    spec, _ = _setup()
    layout = lm.PagedLayout(num_pages=8, page_size=16, pages_per_slot=3)
    cache = lm.init_cache(spec, 2, 48, "int4", paged=layout)
    entry = cache["groups"][0][0]
    assert entry["k_pages"].shape == (8, 8, spec.num_kv_heads, spec.head_dim)
    assert entry["k_pages"].dtype == jnp.int8
    # lane-major scales: token dim last (one (8, 128) f32 tile per page)
    assert entry["k_scale"].shape == (8, spec.num_kv_heads, 16)
    assert lm.paged_page_size(cache, spec.head_dim) == 16
    assert lm._paged_quant(entry) == "int4"
    with pytest.raises(ValueError):
        lm.init_paged_cache(spec, 1, 48,
                            lm.PagedLayout(num_pages=4, page_size=9), "int4")
    with pytest.raises(ValueError):
        lm.init_paged_cache(spec, 1, 48,
                            lm.PagedLayout(num_pages=4, page_size=8), "intX")


def _paged_single_seq(spec, params, prompt, page=8, steps=6, dtype=jnp.float32):
    """Prefill one prompt into pages and greedy-decode ``steps`` tokens
    (odd prompt length -> decode writes start mid-byte for int4)."""
    n_prompt = pc.pages_needed(len(prompt), page)
    spad = n_prompt * page
    padded = np.zeros((1, spad), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, pre = lm.prefill(params, spec, {"tokens": jnp.asarray(padded)},
                             max_seq=spad, impl="naive", true_len=len(prompt))
    layout = lm.PagedLayout(num_pages=16, page_size=page, pages_per_slot=6)
    cache = lm.init_cache(spec, 1, 48, dtype, paged=layout)
    pages = list(range(1, 7))
    cache = pc.write_prompt(cache, spec, 0, pages[:n_prompt], pre, len(prompt))
    cache["block_tables"] = cache["block_tables"].at[0].set(
        jnp.asarray(pages, jnp.int32))
    tok = jnp.argmax(logits[:, 0], -1)[:, None]
    outs = [logits]
    for _ in range(steps):
        l, cache = lm.decode_step(params, spec, cache, tok)
        outs.append(l)
        tok = jnp.argmax(l[:, 0], -1)[:, None]
    return outs


def test_paged_int4_cache_close_to_float():
    """int4 pages (nibble-packed, per-token-per-head scales): greedy
    tokens unchanged, logits within a few % on the tiny model — decode
    writes exercise the mid-byte read-modify-write (13-token prompt)."""
    spec, params = _setup()
    prompt = np.random.default_rng(2).integers(0, 128, size=13).astype(np.int32)
    f32 = _paged_single_seq(spec, params, prompt, steps=4)
    i4 = _paged_single_seq(spec, params, prompt, steps=4, dtype="int4")
    for a, b in zip(f32, i4):
        rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
        assert rel < 0.10
        assert jnp.argmax(a[:, 0], -1) == jnp.argmax(b[:, 0], -1)


# ---------------------------------------------------------------------------
# End-to-end: int4 scheduler == fp32 greedy decode (argmax-stable fixture)
# ---------------------------------------------------------------------------

def _templated_reqs(rng, n, template_len, vocab=128):
    t1 = rng.integers(0, vocab, size=template_len).astype(np.int32)
    t2 = rng.integers(0, vocab, size=template_len + 5).astype(np.int32)
    reqs = []
    for i in range(n):
        t = (t1, t2)[i % 2]
        suf = rng.integers(0, vocab,
                           size=int(rng.integers(4, 11))).astype(np.int32)
        reqs.append(Request(i, np.concatenate([t, suf]),
                            int(rng.integers(3, 7))))
    reqs.append(Request(n, np.concatenate(
        [reqs[0].prompt, rng.integers(0, vocab, size=7).astype(np.int32)]), 4))
    return reqs


def test_scheduler_int4_matches_fp32_greedy():
    """cache_dtype="int4" through the full continuous-batching engine
    (prefix cache on: shared pages, CoW, suffix prefill) is
    token-for-token the fp32 static greedy decode on this argmax-stable
    fixture, and every page reference unwinds."""
    spec, params = _setup()
    rng = np.random.default_rng(0)
    reqs = _templated_reqs(rng, 6, template_len=20)
    cfg = SchedulerConfig(max_slots=3, page_size=16, max_seq=96,
                          num_pages=48, cache_dtype="int4",
                          enable_prefix_cache=True)
    eng = ContinuousBatchingEngine(params, spec, cfg)
    done = eng.run([Request(r.uid, r.prompt.copy(), r.max_new_tokens)
                    for r in reqs])
    assert eng.stats["prefix_hit_tokens"] > 0
    assert eng.stats["cow_copies"] >= 1
    scfg = ServeConfig(max_seq=96, attention_impl="naive")
    for r, c in zip(reqs, done):
        out = generate(params, spec, {"tokens": jnp.asarray(r.prompt[None])},
                       r.max_new_tokens - 1, scfg)
        np.testing.assert_array_equal(np.asarray(out["tokens"][0]), c.tokens)
    eng.alloc.check()
    eng.prefix_cache.flush()
    eng.alloc.check()
    assert eng.alloc.free_pages == eng.layout.num_pages - 1
