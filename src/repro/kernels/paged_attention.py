"""Pallas TPU kernel: paged decode attention (single- or multi-query).

The serve scheduler stores KV in fixed-size pages owned by a block
table per slot (vLLM-style).  The kernel runs one grid step per slot
and streams only that slot's LIVE block-table entries: a
``lax.fori_loop`` whose trip count comes from ``lengths[b]`` walks the
live range in blocks of ``pages_per_block`` pages.  Each block is a
gather: one async copy per page chases ``bt_ref[b, entry]`` from the
pool, left in HBM, into a VMEM block, and the next
block's copies start before the current block is computed (double
buffering).  Entries past a slot's live range are never copied, and a
length-0 slot streams nothing and writes zeros; ``live_entries`` gives
the same count on the host.

The live range per slot, by mode (``_live_range``):

* ``full``: entries ``[0, cdiv(len, page))`` — entry e holds absolute
  page e.
* ``skip`` (sliding window over a flat table): only the last
  ``ceil((window + K - 1)/page) + 1`` live entries ``[lo, last + 1)``,
  so decode page traffic is O(window), not O(context).
* ``ring``: the block table is a ring of R entries (entry j holds
  absolute page ``last - ((last - j) mod R)``); the ``min(R, last + 1)``
  written entries are streamed and stale positions masked.

One kernel body serves the classic decode step, ONE query token per
slot (q (B, H, D)), and a K-token DECODE WINDOW per slot (q (B, K, H,
D)): the speculative-decoding verify step scores all K drafted
positions in one pass, causally masked inside the window (query j sits
at absolute position ``lengths[b] - K + j``).  Each block of K/V
crosses HBM ONCE for all K queries — that K-way amortization of page
(and, one level up, weight) traffic is the whole speculative-decode win
on a memory-bound decode roofline.

A page is copied as its (page * KV, D) rows in (token, kv head) order,
so one matmul scores every (query, head) row against every K row of a
chunk and a mask keeps each head's own kv head (GQA), with no transpose
in VMEM.  Pools stored lane-dense (``page_layout``: head_dim under 128
lanes, each kv head's page whole rows of 128) are copied as they lie,
(KV * rows, 128) a page, and each 128-lane row is scored as the g =
128 / D tokens it holds side by side.  Online softmax carries f32
(m, l, acc) scratch across chunks,
exactly like ``flash_attention.py``.  Every row is masked by its
absolute token position, and in the chunk that holds the end of the
live range, the pages past it are zero-filled instead of copied, so they
contribute nothing.

Quantized pages are the FAST path, not a fallback: int8 pages stream
in as int8 plus per-token-per-head f32 scale pages (loaded page by page
beside the K/V pages from scale pools that ride in VMEM whole) and are
dequantized in VMEM inside the online-softmax loop; packed-int4 pages
(two nibbles per byte along the token dim,
``quant.quantize.pack_int4(axis=1)`` layout) are unpacked in-kernel.  Decode is memory-bound on every edge roofline the paper
profiles, so moving ~4x (int8) / ~8x (int4) fewer HBM bytes per page —
with no fp32 gather materialization — is where the paper's 2-3x
quantized speedup lives.  ``ops.paged_attention`` dispatches all three
cache dtypes here on TPU; ``kernels/ref.py`` holds the gather oracle.

Scale pages are LANE-MAJOR: one page's scales are a (KV, page) f32
block with the token dim along the lanes, so a whole page's scales fit
one (8, 128) f32 tile on TPU.  (The former (page, KV, 1) row-major
blocks tile-padded their trailing dims to (8, 128) PER TOKEN — for
small-KV models that streamed up to two orders of magnitude more
physical scale bytes than the logical KV*4 B/token the analytical
model counts; ``analytical.scale_page_tile_bytes`` quantifies both
layouts.)

``pages_per_block`` follows from the shapes alone: enough pages for
``BLOCK_TOKENS`` tokens, capped by the table width and by a VMEM budget
for the double-buffered K/V (and scale) blocks at their tile-padded
size.

For multi-device serving, ``ops.paged_attention_sharded`` runs this
kernel per shard of a KV-head-partitioned pool under ``shard_map`` —
heads are embarrassingly parallel, so no collective enters the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import page_layout

NEG_INF = -1e30

#: Tokens a block of pages aims to hold (the table and VMEM permitting).
BLOCK_TOKENS = 512
#: (token, kv head) rows of K/V the online softmax folds in at a time.
CHUNK_ROWS = 1024
#: VMEM for the double-buffered K/V (and scale) blocks, tile-padded:
#: half of the 16 MiB default scoped VMEM, the rest left to a chunk's
#: dequantized values and the softmax temporaries.
BLOCK_VMEM_BYTES = 8 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a (rows, cols) block in (32 / itemsize, 128) tiles."""
    item = jnp.dtype(dtype).itemsize
    return _round_up(rows, 32 // item) * _round_up(cols, 128) * item


def pages_per_block(page: int, n_entries: int, page_shape, page_dtype,
                    scaled: bool) -> int:
    """Pages per streamed block: ``BLOCK_TOKENS // page`` (at least
    one), at most ``n_entries``, halved until K and V (and their scale
    pages), double-buffered, fit ``BLOCK_VMEM_BYTES``.  ``page_shape``
    is one pool page, (page or page//2, KV, D) or lane-dense
    (KV, rows, 128)."""
    per_page = _tile_bytes(int(np.prod(page_shape[:-1])), page_shape[-1],
                           page_dtype)
    if scaled:                  # (page or page//2, KV, D) pages
        per_page += _tile_bytes(page_shape[1], page, jnp.float32)
    n = max(1, min(n_entries, BLOCK_TOKENS // page))
    while n > 1 and 2 * 2 * n * per_page > BLOCK_VMEM_BYTES:
        n //= 2
    return n


def _mode(n_entries: int, page: int, window: int, ring: bool, wq: int):
    """(mode, span): how the block table maps to absolute pages, and how
    many entries the live range may hold."""
    if ring:
        return "ring", n_entries
    if window:
        # last page holding an in-window key for the EARLIEST query
        # (abs pos lengths - max(wq, 1)) .. the newest page, inclusive
        span = min(n_entries, -(-(window + max(wq, 1) - 1) // page) + 1)
        if span < n_entries:
            return "skip", span
    return "full", n_entries


def _live_range(length, page: int, n_entries: int, span: int, xp=jnp):
    """(lo, cnt, last): the slot's first live entry, how many entries it
    streams and its newest absolute page.  Works on scalars in the
    kernel (``xp=jnp``) and on host arrays (``xp=np``)."""
    last = (xp.maximum(length, 1) - 1) // page
    hi = xp.minimum(last + 1, n_entries)
    lo = xp.maximum(hi - span, 0)
    return lo, xp.where(length > 0, hi - lo, 0), last


def live_entries(lengths, page: int, n_entries: int, *, window: int = 0,
                 ring: bool = False, wq: int = 0) -> np.ndarray:
    """Pages ``paged_attention_pallas`` streams for each slot (host
    side, by the kernel's own loop bound): ``lengths`` as the kernel
    gets them, ``n_entries`` the block table's width, ``wq`` the decode
    window (0 for the single-query step)."""
    _, span = _mode(n_entries, page, window, ring, wq)
    _, cnt, _ = _live_range(np.asarray(lengths, np.int64), page, n_entries,
                            span, xp=np)
    return cnt


def _idiv(x, n: int):
    """x // n for non-negative int32 vectors (a shift for powers of 2)."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1)
    return jnp.floor((x.astype(jnp.float32) + 0.5) / n).astype(jnp.int32)


def _imod(x, n: int):
    """x % n for non-negative int32 vectors."""
    return x - _idiv(x, n) * n


def _unpack_nibbles(packed: jnp.ndarray, tokens: int) -> jnp.ndarray:
    """(tokens//2, KV, D) packed int8 -> (tokens, KV, D) f32 in [-8, 7].

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
    inter = jnp.stack([lo, hi], axis=1)            # (tokens//2, 2, KV, D)
    return inter.reshape(tokens, *packed.shape[1:]).astype(jnp.float32)


def _dequant_page(x, xs, quant: str, page: int, kv: int):
    """A chunk of n pool pages (n, page or page//2 times KV, D) as f32
    (n * page * KV, D) rows in (token, kv head) order.  Quantized pages
    crossed HBM narrow and dequantize here in VMEM, each page by its own
    lane-major (KV, page) scales (``xs`` (n, KV, page)), transposed to
    broadcast over (page, KV, D)."""
    n, rows, D = x.shape
    if quant == "none":
        return x.reshape(n * rows, D).astype(jnp.float32)
    t = n * page
    s = jnp.swapaxes(xs, 1, 2).reshape(t, kv)[:, :, None]
    if quant == "int8":
        # the page crossed HBM as 1 byte/value
        x = x.astype(jnp.float32).reshape(t, kv, D)
    else:
        x = _unpack_nibbles(x.reshape(t // 2, kv, D), t)          # int4
    return (x * s).reshape(t * kv, D)


def _chunk_tokens(t, e0, lo, cnt, last, length, page: int, n_entries: int,
                  mode: str):
    """(tok, live): the absolute token position of each column whose
    token lies ``t`` tokens after the first of entry ``e0``, and whether
    it lies in an entry the slot streams.  Entries map to absolute pages
    linearly except across a ring's wrap point (entries past
    ``last mod R`` hold the previous lap)."""
    live = t < (lo + cnt - e0) * page
    tok = e0 * page + t
    if mode == "ring":
        r = last % n_entries
        tok = tok + (last - r) * page
        tok = jnp.where(t >= (r + 1 - e0) * page, tok - n_entries * page,
                        tok)
    return tok, live & (tok >= 0) & (tok < length)


def _stream(bt_ref, len_ref, pages, scales, bufs, sem, *, page: int,
            ppb: int, cpb: int, n_entries: int, span: int, compute):
    """Walk slot ``b``'s live entries in blocks of ``ppb`` pages: copy
    block j+1 while block j is computed, ``cpb`` pages at a time, by
    ``compute(arrays, e0)`` (``e0`` the chunk's first entry).

    ``pages`` are the HBM K/V pools, copied page by page (async, one
    DMA semaphore per pool and buffer); ``scales`` the VMEM-resident
    scale pools, whose pages are loaded beside them.  ``bufs`` hold the
    double-buffered blocks in operand order (k, [k_scale], v,
    [v_scale]).  Past the live range, in the chunk that holds its end,
    v's pages (and scale pages) are zero-filled instead of copied, so
    masked rows never meet stale VMEM."""
    b = pl.program_id(0)
    lo, cnt, _ = _live_range(len_ref[b], page, n_entries, span)
    hi = lo + cnt
    page_bufs = bufs[::2] if scales else bufs
    scale_bufs = bufs[1::2] if scales else []
    v_bufs = bufs[len(bufs) // 2:]

    def copies(slot, i, e):
        pid = bt_ref[b, e]
        return pid, [pltpu.make_async_copy(src.at[pid], buf.at[slot, i],
                                           sem.at[o, slot])
                     for o, (src, buf) in enumerate(zip(pages, page_bufs))]

    def each(first, end, f):
        def body(i, carry):
            f(i)
            return carry
        jax.lax.fori_loop(first, end, body, 0)

    def start(j, slot):
        e0 = lo + j * ppb
        n = jnp.minimum(hi - e0, ppb)

        def one(i):
            pid, cs = copies(slot, i, e0 + i)
            for c in cs:
                c.start()
            for src, buf in zip(scales, scale_bufs):
                buf[slot, i] = src[pid]

        def zero(i):
            for buf in v_bufs:
                buf[slot, i] = jnp.zeros(buf.shape[2:], buf.dtype)
        each(0, n, one)
        each(n, jnp.minimum((n + cpb - 1) // cpb * cpb, ppb), zero)

    def wait(j, slot):
        e0 = lo + j * ppb

        def one(i):
            for c in copies(slot, i, e0 + i)[1]:
                c.wait()
        each(0, jnp.minimum(hi - e0, ppb), one)

    n_blocks = (cnt + ppb - 1) // ppb

    @pl.when(n_blocks > 0)
    def _():
        start(0, 0)

    def body(j):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            start(j + 1, 1 - slot)

        wait(j, slot)
        e0 = lo + j * ppb
        each(0, (jnp.minimum(hi - e0, ppb) + cpb - 1) // cpb,
             lambda c: compute([buf[slot, pl.ds(c * cpb, cpb)]
                                for buf in bufs], e0 + c * cpb))

    each(0, n_blocks, body)


def _paged_kernel(bt_ref, len_ref, q_ref, *rest, scale: float, page: int,
                  ppb: int, cpb: int, n_entries: int, span: int, window: int,
                  kv_heads: int, heads: int, wq: int, quant: str, mode: str,
                  dense_rows: int):
    """One slot's decode attention.  q rows are (query, head) pairs:
    query j of the K-token window (K = ``wq``, 1 for the plain step)
    sits at absolute position ``length - K + j`` and attends positions
    up to its own (causal inside the window), within the sliding
    ``window`` if one is set.  Each chunk's K/V arrive as (token, kv
    head) rows, so one matmul scores every query head against every row
    and a mask keeps the rows of the head's own kv head (GQA).

    Lane-dense pages (``dense_rows`` > 0, ``page_layout``) arrive as
    ``dense_rows`` rows of 128 lanes per kv head, each holding g = 128/D
    consecutive tokens side by side: lanes [s·D, (s+1)·D) of every row
    are scored as one set of columns, token s of the row, and all g sets
    fold into the same online softmax."""
    n = 2 if quant == "none" else 4
    ops, (o_ref, *rest) = rest[:n], rest[n:]
    bufs, (sem, m_ref, l_ref, acc_ref) = rest[:n], rest[n:]
    pages, scales = (ops, ()) if quant == "none" else (ops[::2], ops[1::2])
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = len_ref[pl.program_id(0)]
    lo, cnt, last = _live_range(length, page, n_entries, span)
    q = q_ref[0].astype(jnp.float32) * scale               # (R, D)
    D = q.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0)
    q_pos = length - wq + _idiv(row, heads)                 # (R, 1)
    q_kv = _idiv(_imod(row, heads), heads // kv_heads)

    def columns(arrays):
        """[(k, v, t, head)]: each set of key/value columns (T, D) with
        its token's offset ``t`` from the chunk's first token and its kv
        head, both (1, T)."""
        k, ks, v, vs = ((arrays[0], None, arrays[1], None)
                        if quant == "none" else arrays)
        k = _dequant_page(k, ks, quant, page, kv_heads)     # (T, lanes)
        v = _dequant_page(v, vs, quant, page, kv_heads)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, k.shape[0]), 1)
        if not dense_rows:
            return [(k, v, _idiv(col, kv_heads), _imod(col, kv_heads))]
        per_row = k.shape[1] // D
        t = (_idiv(col, kv_heads * dense_rows) * page
             + _imod(col, dense_rows) * per_row)
        head = _imod(_idiv(col, dense_rows), kv_heads)
        return [(k[:, s * D:(s + 1) * D], v[:, s * D:(s + 1) * D], t + s,
                 head) for s in range(per_row)]

    def compute(arrays, e0):
        scored = []
        for k, v, t, head in columns(arrays):
            tok, live = _chunk_tokens(t, e0, lo, cnt, last, length, page,
                                      n_entries, mode)
            valid = live & (tok <= q_pos) & (head == q_kv)
            if window:
                valid &= (q_pos - tok) < window
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            scored.append((jnp.where(valid, s, NEG_INF), valid, v))  # (R, T)
        m_prev = m_ref[...]
        m_new = m_prev
        for s, _, _ in scored:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l, acc = alpha * l_ref[...], acc_ref[...] * alpha
        for s, valid, v in scored:
            e = jnp.exp(s - m_new) * valid.astype(jnp.float32)
            l = l + jnp.sum(e, axis=-1, keepdims=True)
            acc = acc + jnp.dot(e, v, preferred_element_type=jnp.float32)
        l_ref[...], acc_ref[...], m_ref[...] = l, acc, m_new

    _stream(bt_ref, len_ref, pages, scales, bufs, sem, page=page, ppb=ppb,
            cpb=cpb, n_entries=n_entries, span=span, compute=compute)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


# jitted so that a model's layers, which share shapes, trace and lower
# the kernel once
@functools.partial(jax.jit, static_argnames=("window", "ring", "scale",
                                             "interpret"))
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
    (P, page, KV, D) float, or lane-dense (P, KV, page·D/128, 128) float
    (``page_layout``) — or int8 with lane-major
    ``k_scale``/``v_scale`` (P, KV, page) f32, or nibble-packed int4
    (P, page//2, KV, D) (packing inferred from the scale's token dim);
    block_tables: (B, pages_per_slot) int32; lengths: (B,) int32.

    Each slot streams only its live entries (``live_entries``).
    ``window > 0`` with a flat block table SKIPS fully-out-of-window
    entries: only the last ``ceil((window + K - 1)/page) + 1`` live
    pages are streamed, from a per-slot first entry computed from
    ``lengths`` — decode page traffic is O(window), not O(context),
    with bitwise-identical results to streaming-then-masking.
    ``ring=True`` declares the block table a RING of
    ``block_tables.shape[1]`` entries (entry j holds absolute page
    ``last - ((last - j) mod R)``, stale entries masked), the layout
    the serve scheduler uses to bound per-slot KV at O(window)."""
    if q.ndim == 4:
        B, WQ, H, D = q.shape
    else:
        WQ = 0                                # single-query kernel
        B, H, D = q.shape
    P = k_pages.shape[0]
    tok, KV = page_layout.pool_dims(k_pages.shape, D)
    dense_rows = (k_pages.shape[-2]
                  if page_layout.is_lane_dense(k_pages.shape, D) else 0)
    if k_scale is not None:
        page = k_scale.shape[-1]
        quant = "int8" if tok == page else "int4"
        if quant == "int4" and tok * 2 != page:
            raise ValueError(
                f"int4 pages {k_pages.shape} do not pack scale token dim "
                f"{page}")
    else:
        page = tok
        quant = "none"
    n_entries = block_tables.shape[1]
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    if D % 128 and not dense_rows:
        # a page is copied whole, so its minor dim has to fill the
        # 128 lanes: zero-pad head_dim (q's pad meets zeros, v's pad
        # lands in output lanes that are sliced off).  Only for pools
        # ``page_layout`` does not store lane-dense.
        def pad(x):
            return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, -D % 128)])
        o = paged_attention_pallas(
            pad(q), pad(k_pages), pad(v_pages), block_tables, lengths,
            window=window, ring=ring, scale=sc, k_scale=k_scale,
            v_scale=v_scale, interpret=interpret)
        return o[..., :D]
    mode, span = _mode(n_entries, page, window, ring, WQ)
    ppb = pages_per_block(page, span, k_pages.shape[1:], k_pages.dtype,
                          quant != "none")
    cpb = max(c for c in range(1, ppb + 1)
              if ppb % c == 0 and (c == 1 or c * page * KV <= CHUNK_ROWS))
    R = max(WQ, 1) * H
    kernel = functools.partial(
        _paged_kernel, scale=sc, page=page, ppb=ppb, cpb=cpb,
        n_entries=n_entries, span=span, window=window, kv_heads=KV, heads=H,
        wq=max(WQ, 1), quant=quant, mode=mode, dense_rows=dense_rows)

    # K/V pools stay in HBM, each page one (page * KV, D) copy, or
    # (KV * dense_rows, 128) lane-dense; the small scale pools ride in
    # VMEM whole (an HBM page slice narrower than 128 lanes cannot be a
    # copy's source)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    vmem = pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM)
    k_rows = k_pages.reshape(P, -1, k_pages.shape[-1])
    v_rows = v_pages.reshape(P, -1, v_pages.shape[-1])
    if quant == "none":
        operands, specs = [k_rows, v_rows], [hbm, hbm]
    else:
        operands = [k_rows, k_scale, v_rows, v_scale]
        specs = [hbm, vmem, hbm, vmem]
    q_spec = pl.BlockSpec((1, R, D), lambda b, bt, ln: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # block_tables, lengths
        grid=(B,),
        in_specs=[q_spec] + specs,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((2, ppb, *x.shape[1:]), x.dtype)
                        for x in operands] + [
            pltpu.SemaphoreType.DMA((2, 2)),              # (k, v) x buffer
            pltpu.VMEM((R, 1), jnp.float32),              # running max
            pltpu.VMEM((R, 1), jnp.float32),              # running denom
            pltpu.VMEM((R, D), jnp.float32),              # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=(f"paged_attention_decode_{quant}"
              + (f"_w{WQ}" if WQ else "")
              + (f"_{mode}" if mode != "full" else "")),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(B, R, D), *operands)
    return out.reshape(q.shape)
