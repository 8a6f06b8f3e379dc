"""Pallas TPU kernel: paged decode attention (single- or multi-query).

The serve scheduler stores KV in fixed-size pages owned by a block
table per slot (vLLM-style), so decode never touches padding beyond a
slot's live context.  The kernel streams one page per grid step along
the 'arbitrary' dim; the block table and per-slot lengths ride in as
scalar-prefetch operands so the K/V index maps can chase page ids
(``bt_ref[b, p]``) when scheduling DMAs.

Two kernel bodies share the page-dequant plumbing:

* ``_paged_kernel`` — ONE query token per slot (the classic decode
  step), q (B, H, D).
* ``_paged_window_kernel`` — a K-token DECODE WINDOW per slot
  (q (B, K, H, D)): the speculative-decoding verify step scores all K
  drafted positions in one pass, causally masked inside the window
  (query j sits at absolute position ``lengths[b] - K + j``).  Each
  page's K/V block crosses HBM ONCE for all K queries — that K-way
  amortization of page (and, one level up, weight) traffic is the
  whole speculative-decode win on a memory-bound decode roofline.
  The window is unrolled in python (K is a small static 2..8), so
  every per-query op stays on leading, untiled dims.

Online softmax carries (m, l, acc) scratch across pages, exactly like
``flash_attention.py`` — a fully-masked slot (length 0) emits zeros.
GQA folds query heads onto kv heads inside the kernel ((KV, G, D)
layout), so K/V pages are fetched once per kv head group.

Quantized pages are the FAST path, not a fallback: int8 pages stream
in as int8 plus per-token-per-head f32 scale pages (extra block-table-
indexed operands) and are dequantized in VMEM inside the online-softmax
loop; packed-int4 pages (two nibbles per byte along the token dim,
``quant.quantize.pack_int4(axis=1)`` layout) are unpacked in-kernel.
Decode is memory-bound on every edge roofline the paper profiles, so
moving ~4x (int8) / ~8x (int4) fewer HBM bytes per page — with no fp32
gather materialization — is where the paper's 2-3x quantized speedup
lives.  ``ops.paged_attention`` dispatches all three cache dtypes here
on TPU; ``kernels/ref.py`` holds the gather oracle.

Scale pages are LANE-MAJOR: one page's scales are a (KV, page) f32
block with the token dim along the lanes, so a whole page's scales fit
one (8, 128) f32 tile on TPU.  (The former (page, KV, 1) row-major
blocks tile-padded their trailing dims to (8, 128) PER TOKEN — for
small-KV models that streamed up to two orders of magnitude more
physical scale bytes than the logical KV*4 B/token the analytical
model counts; ``analytical.scale_page_tile_bytes`` quantifies both
layouts.)

For multi-device serving, ``ops.paged_attention_sharded`` runs this
kernel per shard of a KV-head-partitioned pool under ``shard_map`` —
heads are embarrassingly parallel, so no collective enters the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _unpack_nibbles(packed: jnp.ndarray, page: int) -> jnp.ndarray:
    """(page//2, KV, D) packed int8 -> (page, KV, D) f32 in [-8, 7].

    Low nibble = even token, high nibble = odd token (the
    ``pack_int4(axis=token)`` pool layout).  Sign-extension runs in
    int32 on the VPU; the stack/reshape interleave only touches the
    leading (non-tiled) dim, so it lowers on TPU and in interpret mode.
    """
    p32 = packed.astype(jnp.int32)
    lo = p32 & 0x0F
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = (p32 >> 4) & 0x0F
    hi = jnp.where(hi >= 8, hi - 16, hi)
    inter = jnp.stack([lo, hi], axis=1)            # (page//2, 2, KV, D)
    return inter.reshape(page, *packed.shape[1:]).astype(jnp.float32)


def _dequant_page(k_ref, v_ref, ks_ref, vs_ref, quant: str, page: int):
    """Materialize one page's K/V block as f32 (page, KV, D) in VMEM —
    shared by the single-query and window kernels.  Quantized pages
    cross HBM narrow and dequantize here; scale blocks are lane-major
    (KV, page) and transpose to broadcast over (page, KV, D)."""
    if quant == "none":
        return k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32)
    ks = jnp.swapaxes(ks_ref[0], 0, 1)[:, :, None]
    vs = jnp.swapaxes(vs_ref[0], 0, 1)[:, :, None]
    if quant == "int8":
        # dequant in VMEM: the page crossed HBM as 1 byte/value
        return (k_ref[0].astype(jnp.float32) * ks,
                v_ref[0].astype(jnp.float32) * vs)
    return (_unpack_nibbles(k_ref[0], page) * ks,          # int4
            _unpack_nibbles(v_ref[0], page) * vs)


def _page_tokens(p, length, page: int, n_pages: int, mode: str):
    """Absolute token positions (1, 1, page) covered by grid step ``p``.

    mode == "full": entry p holds absolute page p (the classic layout).
    mode == "skip": the grid was shrunk to the last ``n_pages`` live
    pages — entry p maps to absolute page ``lo + p`` with
    ``lo = max(last_page - (n_pages - 1), 0)``, so fully-out-of-window
    pages are never streamed (the index map chases the same offset).
    mode == "ring": the block table is a ring of ``n_pages`` entries;
    entry j holds absolute page ``last - ((last - j) mod n_pages)``
    (negative => never written yet, masked via tok < 0).
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    if mode == "full":
        return p * page + iota
    last = jax.lax.div(length - 1, page)      # length >= 1 on live rows
    if mode == "skip":
        lo = jnp.maximum(last - (n_pages - 1), 0)
        return (lo + p) * page + iota
    ap = last - jnp.remainder(last - p, n_pages)          # ring
    return ap * page + iota


def _paged_kernel(bt_ref, len_ref, q_ref, *rest, scale: float, page: int,
                  n_pages: int, window: int, kv_heads: int, grp: int,
                  quant: str, mode: str):
    if quant == "none":
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    else:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    q = q_ref[0].astype(jnp.float32) * scale              # (H, D)
    k, v = _dequant_page(k_ref, v_ref, ks_ref, vs_ref, quant, page)
    D = q.shape[-1]
    qg = q.reshape(kv_heads, grp, D)
    s = jnp.einsum("kgd,tkd->kgt", qg, k,
                   preferred_element_type=jnp.float32)    # (KV, G, page)

    tok = _page_tokens(p, length, page, n_pages, mode)
    valid = tok < length
    if mode == "ring":
        valid &= tok >= 0
    if window:
        valid &= tok > (length - 1 - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                   # (KV, G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    e = jnp.exp(s - m_new) * valid.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "kgt,tkd->kgd", e, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _done():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).reshape(
            kv_heads * grp, D).astype(o_ref.dtype)


def _paged_window_kernel(bt_ref, len_ref, q_ref, *rest, scale: float,
                         page: int, n_pages: int, window: int, kv_heads: int,
                         grp: int, quant: str, wq: int, mode: str):
    """K-query decode-window body: per-query online-softmax state in a
    leading ``wq`` scratch dim, one K/V page load shared by all K
    queries.  Query j attends absolute positions <= length - wq + j
    (``length`` counts the whole window), so the window masks causally
    against itself; the sliding window is applied per query position.
    """
    if quant == "none":
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    else:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = len_ref[b] - wq                  # abs position of query j=0
    k, v = _dequant_page(k_ref, v_ref, ks_ref, vs_ref, quant, page)
    D = q_ref.shape[-1]
    # (wq, H, D) -> (wq, KV, G, D): leading-dim split only
    qg = (q_ref[0].astype(jnp.float32) * scale).reshape(
        wq, kv_heads, grp, D)
    tok = _page_tokens(p, len_ref[b], page, n_pages, mode)
    for j in range(wq):                     # static unroll: wq is 2..8
        s = jnp.einsum("kgd,tkd->kgt", qg[j], k,
                       preferred_element_type=jnp.float32)
        valid = tok <= base + j
        if mode == "ring":
            valid &= tok >= 0
        if window:
            valid &= (base + j - tok) < window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[j]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - m_new) * valid.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[j] = alpha * l_ref[j] + jnp.sum(e, axis=-1, keepdims=True)
        acc_ref[j] = acc_ref[j] * alpha + jnp.einsum(
            "kgt,tkd->kgd", e, v, preferred_element_type=jnp.float32)
        m_ref[j] = m_new

    @pl.when(p == n_pages - 1)
    def _done():
        for j in range(wq):
            l = l_ref[j]
            safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, j] = (acc_ref[j] / safe).reshape(
                kv_heads * grp, D).astype(o_ref.dtype)


def paged_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                           lengths: jnp.ndarray, *, window: int = 0,
                           ring: bool = False,
                           scale: float | None = None,
                           k_scale: jnp.ndarray | None = None,
                           v_scale: jnp.ndarray | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, D) single-query, or (B, K, H, D) for a K-token decode
    window (``lengths`` then counts the context INCLUDING the window;
    query j attends positions <= lengths - K + j); k_pages/v_pages:
    (P, page, KV, D) float — or int8 with lane-major
    ``k_scale``/``v_scale`` (P, KV, page) f32, or nibble-packed int4
    (P, page//2, KV, D) (packing inferred from the scale's token dim);
    block_tables: (B, pages_per_slot) int32; lengths: (B,) int32.

    ``window > 0`` with a flat block table SKIPS fully-out-of-window
    entries: the page grid dim shrinks to the last
    ``ceil((window + K - 1)/page) + 1`` live pages and the K/V index
    map chases ``bt[b, lo_b + p]`` with a per-slot ``lo_b`` computed
    from ``lengths`` — decode page traffic is O(window), not
    O(context), with bitwise-identical results to streaming-then-
    masking.  ``ring=True`` declares the block table a RING of
    ``block_tables.shape[1]`` entries (entry j holds absolute page
    ``last - ((last - j) mod R)``, stale entries masked), the layout
    the serve scheduler uses to bound per-slot KV at O(window)."""
    if q.ndim == 4:
        B, WQ, H, D = q.shape
    else:
        WQ = 0                                # single-query kernel
        B, H, D = q.shape
    KV = k_pages.shape[2]
    if k_scale is not None:
        page = k_scale.shape[-1]
        quant = "int8" if k_pages.shape[1] == page else "int4"
        if quant == "int4" and k_pages.shape[1] * 2 != page:
            raise ValueError(
                f"int4 pages {k_pages.shape} do not pack scale token dim "
                f"{page}")
    else:
        page = k_pages.shape[1]
        quant = "none"
    n_entries = block_tables.shape[1]
    grp = H // KV
    sc = scale if scale is not None else 1.0 / (D ** 0.5)

    if ring:
        mode, n_pages = "ring", n_entries
    elif window:
        # last page holding an in-window key for the EARLIEST query
        # (abs pos lengths - max(WQ,1)) .. the newest page, inclusive
        span = window + max(WQ, 1) - 1
        win_pages = min(n_entries, -(-span // page) + 1)
        mode = "skip" if win_pages < n_entries else "full"
        n_pages = win_pages
    else:
        mode, n_pages = "full", n_entries

    if WQ:
        q_spec = pl.BlockSpec((1, WQ, H, D),
                              lambda b, p, bt, ln: (b, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((B, WQ, H, D), q.dtype)
        scratch = [
            pltpu.VMEM((WQ, KV, grp, 1), jnp.float32),    # running max
            pltpu.VMEM((WQ, KV, grp, 1), jnp.float32),    # running denom
            pltpu.VMEM((WQ, KV, grp, D), jnp.float32),    # accumulator
        ]
        kernel = functools.partial(
            _paged_window_kernel, scale=sc, page=page, n_pages=n_pages,
            window=window, kv_heads=KV, grp=grp, quant=quant, wq=WQ,
            mode=mode)
    else:
        q_spec = pl.BlockSpec((1, H, D), lambda b, p, bt, ln: (b, 0, 0))
        out_shape = jax.ShapeDtypeStruct((B, H, D), q.dtype)
        scratch = [
            pltpu.VMEM((KV, grp, 1), jnp.float32),        # running max
            pltpu.VMEM((KV, grp, 1), jnp.float32),        # running denom
            pltpu.VMEM((KV, grp, D), jnp.float32),        # accumulator
        ]
        kernel = functools.partial(
            _paged_kernel, scale=sc, page=page, n_pages=n_pages,
            window=window, kv_heads=KV, grp=grp, quant=quant, mode=mode)
    if mode == "skip":
        # chase the same shifted entry the kernel body masks against:
        # only the last n_pages live pages ever cross HBM
        def _entry(b, p, ln):
            last = jax.lax.div(ln[b] - 1, page)
            return jnp.maximum(last - (n_pages - 1), 0) + p
    else:
        def _entry(b, p, ln):
            return p
    kv_spec = pl.BlockSpec(
        (1, k_pages.shape[1], KV, D),
        lambda b, p, bt, ln: (bt[b, _entry(b, p, ln)], 0, 0, 0))
    in_specs = [q_spec, kv_spec]
    operands = [q, k_pages]
    if quant != "none":
        # lane-major scale block: the whole page's scales in one
        # (KV, page) tile (token dim on the lanes)
        s_spec = pl.BlockSpec(
            (1, KV, page),
            lambda b, p, bt, ln: (bt[b, _entry(b, p, ln)], 0, 0))
        in_specs += [s_spec, kv_spec, s_spec]
        operands += [k_scale, v_pages, v_scale]
    else:
        in_specs += [kv_spec]
        operands += [v_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # block_tables, lengths
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=(f"paged_attention_decode_{quant}"
              + (f"_w{WQ}" if WQ else "")
              + (f"_{mode}" if mode != "full" else "")),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
