"""Pure-jnp oracles for every Pallas kernel (the ground truth the
shape/dtype sweep tests assert against)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import page_layout
from repro.quant.qtypes import QuantizedTensor
from repro.quant.quantize import dequantize, quantize_values


def quant_matmul_ref(x: jnp.ndarray, w: QuantizedTensor,
                     out_dtype=None) -> jnp.ndarray:
    """x (..., K) @ dequant(w) (K, N)."""
    wf = dequantize(w, out_dtype=jnp.float32)
    out = jnp.dot(x.astype(jnp.float32), wf, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int = 0,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """q (B, Sq, H, D), k/v (B, Sk, KV, D) -> (B, Sq, H, D). GQA-aware."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    rep = H // KV
    kr = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    s = scale if scale is not None else 1.0 / (D ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * s
    q_idx = jnp.arange(Sq)[:, None] + (Sk - Sq)   # align ends (decode-friendly)
    k_idx = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= q_idx >= k_idx
    if window:
        mask &= (q_idx - k_idx) < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def _ring_positions(lengths: jnp.ndarray, n_entries: int,
                    page: int) -> jnp.ndarray:
    """Per-slot absolute token positions (B, n_entries * page) for a RING
    block table: entry j of slot b holds absolute page
    ``last - ((last - j) mod R)`` with ``last = (lengths[b]-1)//page``
    (negative => entry never written; callers mask ``pos < 0``)."""
    last = jnp.maximum(lengths[:, None] - 1, 0) // page          # (B, 1)
    j = jnp.arange(n_entries)[None]                              # (1, R)
    ap = last - jnp.mod(last - j, n_entries)                     # (B, R)
    pos = ap[:, :, None] * page + jnp.arange(page)[None, None]
    return pos.reshape(lengths.shape[0], n_entries * page)


def paged_attention_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                        lengths: jnp.ndarray, *, window: int = 0,
                        ring: bool = False,
                        scale: Optional[float] = None,
                        k_scale: Optional[jnp.ndarray] = None,
                        v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Gather-based paged decode attention (one query token per slot).

    q: (B, H, D); k_pages/v_pages: (P, page, KV, D), or lane-dense
    (P, KV, page·D/128, 128) (``page_layout``); block_tables:
    (B, pages_per_slot) page ids into the pool; lengths: (B,) number of
    valid context tokens per slot (the current token's k/v already
    written).  Fully-masked slots (length 0) return zeros.  For int8
    pages pass k_scale/v_scale in the LANE-MAJOR (P, KV, page) f32
    layout; for nibble-packed int4 pages (P, page//2, KV, D) pass the
    same full-token-dim scales (packing is inferred from the shape
    mismatch).  Pages are dequantized after the gather — the fp32
    materialization the Pallas kernel exists to avoid.

    A 4-D q (B, K, H, D) is the MULTI-QUERY decode window (speculative
    verify): query j of slot b sits at absolute position
    ``lengths[b] - K + j``, so the window is causally masked inside
    itself and the result is (B, K, H, D) — see
    ``paged_attention_window_ref``.
    """
    if q.ndim == 4:
        return paged_attention_window_ref(
            q, k_pages, v_pages, block_tables, lengths, window=window,
            ring=ring, scale=scale, k_scale=k_scale, v_scale=v_scale)
    from repro.quant.quantize import unpack_int4
    B, H, D = q.shape
    page, KV = page_layout.pool_dims(k_pages.shape, D)
    if k_scale is not None:
        page = k_scale.shape[-1]
    if k_scale is not None and k_pages.shape[1] != page:     # packed int4
        k_pages = unpack_int4(k_pages, axis=1)
        v_pages = unpack_int4(v_pages, axis=1)
    G = H // KV
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    k = page_layout.pages_from_pool(k_pages[block_tables], D).astype(
        jnp.float32)                                   # (B, n, page, KV, D)
    v = page_layout.pages_from_pool(v_pages[block_tables], D).astype(
        jnp.float32)
    if k_scale is not None:
        # lane-major (B, n, KV, page) -> broadcastable (B, n, page, KV, 1)
        k = k * jnp.moveaxis(k_scale[block_tables], -1, -2)[..., None]
    if v_scale is not None:
        v = v * jnp.moveaxis(v_scale[block_tables], -1, -2)[..., None]
    S = block_tables.shape[1] * page
    k = k.reshape(B, S, KV, D)
    v = v.reshape(B, S, KV, D)
    qg = q.reshape(B, KV, G, D).astype(jnp.float32) * sc
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k)           # (B, KV, G, S)
    if ring:
        idx = _ring_positions(lengths, block_tables.shape[1], page)
        valid = (idx >= 0) & (idx < lengths[:, None])
    else:
        idx = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        valid = idx < lengths[:, None]
    if window:
        valid &= idx > (lengths[:, None] - 1 - window)
    s = jnp.where(valid[:, None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m) * valid[:, None, None]
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v)
    return out.reshape(B, H, D).astype(q.dtype)


def paged_attention_window_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                               v_pages: jnp.ndarray,
                               block_tables: jnp.ndarray,
                               lengths: jnp.ndarray, *, window: int = 0,
                               ring: bool = False,
                               scale: Optional[float] = None,
                               k_scale: Optional[jnp.ndarray] = None,
                               v_scale: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
    """Multi-query paged decode attention: a K-token step window per slot.

    q: (B, K, H, D) — K consecutive query tokens whose k/v rows are
    already scattered into the pool; ``lengths`` counts the context
    INCLUDING the whole window, so query j's absolute position is
    ``lengths[b] - K + j`` and it attends tokens at positions
    ``<= lengths[b] - K + j`` (causal inside the window).  K=1 is
    exactly ``paged_attention_ref``.  Quantized-page handling (int8
    pages + lane-major scales, nibble-packed int4) is identical to the
    single-query path.  The speculative-decode verify step runs all K
    drafted positions through this in ONE pass, which is what amortizes
    the page (and weight) traffic K-ways.
    """
    from repro.quant.quantize import unpack_int4
    B, K, H, D = q.shape
    page, KV = page_layout.pool_dims(k_pages.shape, D)
    if k_scale is not None:
        page = k_scale.shape[-1]
    if k_scale is not None and k_pages.shape[1] != page:     # packed int4
        k_pages = unpack_int4(k_pages, axis=1)
        v_pages = unpack_int4(v_pages, axis=1)
    G = H // KV
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    k = page_layout.pages_from_pool(k_pages[block_tables], D).astype(
        jnp.float32)                                   # (B, n, page, KV, D)
    v = page_layout.pages_from_pool(v_pages[block_tables], D).astype(
        jnp.float32)
    if k_scale is not None:
        k = k * jnp.moveaxis(k_scale[block_tables], -1, -2)[..., None]
    if v_scale is not None:
        v = v * jnp.moveaxis(v_scale[block_tables], -1, -2)[..., None]
    S = block_tables.shape[1] * page
    k = k.reshape(B, S, KV, D)
    v = v.reshape(B, S, KV, D)
    qg = q.reshape(B, K, KV, G, D).astype(jnp.float32) * sc
    s = jnp.einsum("bjkgd,btkd->bjkgt", qg, k)         # (B, K, KV, G, S)
    q_abs = lengths[:, None] - K + jnp.arange(K)[None]           # (B, K)
    if ring:
        idx = _ring_positions(lengths, block_tables.shape[1], page)[:, None]
        valid = (idx >= 0) & (idx <= q_abs[..., None])           # (B, K, S)
    else:
        idx = jnp.arange(S)[None, None]
        valid = idx <= q_abs[..., None]                          # (B, K, S)
    if window:
        valid &= (q_abs[..., None] - idx) < window
    s = jnp.where(valid[:, :, None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m) * valid[:, :, None, None]
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bjkgt,btkd->bjkgd", p, v)
    return out.reshape(B, K, H, D).astype(q.dtype)


def quantize_rowwise_ref(x: jnp.ndarray, bits: int = 8):
    """Per-row symmetric quantization of a 2-D tensor -> (q, scale)."""
    from repro.quant.qtypes import QuantConfig
    cfg = QuantConfig(bits=bits, symmetric=True, granularity="channel", axis=0)
    q, scale, _ = quantize_values(x, cfg)
    return q, scale.reshape(x.shape[0], 1)
