"""jit'd public wrappers around the Pallas kernels.

On non-TPU backends (this CPU container) the kernels execute with
interpret=True; model code can also force the pure-jnp reference path
(`impl="ref"`), which is what the dry-run lowers (pallas_call does not
lower on the CPU host-platform backend).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import page_layout, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.quantize_kernel import quantize_rowwise_pallas
from repro.quant.qtypes import QuantizedTensor


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def quant_matmul(x: jnp.ndarray, w: QuantizedTensor, *, impl: str = "auto",
                 out_dtype=None, bm: int = 128, bn: int = 128, bk: int = 128):
    """x (M, K) @ dequant(w). impl: auto | pallas | ref.

    auto -> Pallas on TPU, pure-jnp ref elsewhere (interpret-mode grids
    lower to giant XLA while-loops; the ref path is what the CPU dry-run
    and tests should lower unless explicitly exercising the kernel)."""
    if impl == "ref" or (impl == "auto" and _default_interpret()):
        return ref.quant_matmul_ref(x, w, out_dtype=out_dtype)
    cfg = w.config
    if cfg.granularity == "group":
        group = cfg.group_size
        scale = w.scale.reshape(w.shape[0] // group, 1, w.shape[1])
    elif cfg.granularity == "channel":
        group = 0
        scale = w.scale.reshape(1, w.shape[1])
    else:
        group = 0
        scale = jnp.broadcast_to(w.scale.reshape(1, 1), (1, w.shape[1]))
    if w.zero is not None:
        return ref.quant_matmul_ref(x, w, out_dtype=out_dtype)  # asym: ref path
    return quant_matmul_pallas(
        x, w.q, scale, bits=cfg.bits, group=group, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype, interpret=_default_interpret())


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: Optional[float] = None, impl: str = "auto",
                    bq: int = 128, bk: int = 128):
    if impl == "ref" or (impl == "auto" and _default_interpret()):
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale, bq=bq, bk=bk,
                                  interpret=_default_interpret())


def _resolve_paged_impl(impl: str) -> str:
    """Dispatch decision for ``paged_attention`` — identical for fp32,
    int8, and int4 pages: explicit ``impl`` wins; ``auto`` takes the
    Pallas kernel on TPU and the gather reference elsewhere (interpret-
    mode grids lower to giant XLA while-loops on CPU)."""
    if impl in ("ref", "pallas"):
        return impl
    if impl != "auto":
        raise ValueError(f"impl {impl!r} (want auto | pallas | ref)")
    return "ref" if _default_interpret() else "pallas"


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    window: int = 0, ring: bool = False,
                    scale: Optional[float] = None,
                    k_scale=None, v_scale=None, impl: str = "auto"):
    """Paged decode attention: q (B, H, D) against a page pool — or
    q (B, K, H, D) for a K-token decode window (the speculative-decode
    verify step; ``lengths`` then counts the context INCLUDING the
    window and query j attends positions <= lengths - K + j, causal
    inside the window).

    Quantized pages are the FAST path: on TPU ``auto`` dispatches fp32,
    int8 (lane-major ``k_scale``/``v_scale`` (P, KV, page) f32), and
    nibble-packed int4 pages (k/v (P, page//2, KV, D), full-token-dim
    scales) to the same scalar-prefetch Pallas kernel, which dequantizes
    int8 and unpacks int4 in VMEM inside the online-softmax loop —
    ~4x/~8x fewer HBM bytes per page and no fp32 gather
    materialization.  The reference dequant-after-gather path is the
    oracle (and the CPU lowering); ``impl="pallas"`` forces the kernel
    body (interpret-mode off-TPU) for any cache dtype.

    ``window > 0`` SKIPS fully-out-of-window pages (the grid shrinks to
    the last O(window) live pages); ``ring=True`` additionally declares
    the block table a ring of ``block_tables.shape[1]`` entries — the
    O(window)-bounded layout the serve scheduler installs for uniformly
    sliding-window (`attn_local`) stacks."""
    if _resolve_paged_impl(impl) == "ref":
        return ref.paged_attention_ref(
            q, k_pages, v_pages, block_tables, lengths, window=window,
            ring=ring, scale=scale, k_scale=k_scale, v_scale=v_scale)
    return paged_attention_pallas(
        q, k_pages, v_pages, block_tables, lengths, window=window,
        ring=ring, scale=scale, k_scale=k_scale, v_scale=v_scale,
        interpret=_default_interpret())


def paged_attention_sharded(mesh, q, k_pages, v_pages, block_tables,
                            lengths, *, window: int = 0, ring: bool = False,
                            scale: Optional[float] = None,
                            k_scale=None, v_scale=None, axis: str = "model",
                            impl: str = "auto", gather_output: bool = True):
    """Tensor-parallel paged decode attention over a KV-head-sharded pool.

    The page pools (and lane-major scale pages) live sharded over the
    KV-head dim on ``mesh``'s ``axis``; block tables and per-slot
    lengths are replicated host state.  Attention heads never mix, so
    each shard runs the plain ``paged_attention`` op — the Pallas
    kernel on TPU — over its own KV-head slice with NO collective
    inside the op; ``shard_map`` slices q to the shard's head group
    (a no-op reshard when the caller already computed q from
    column-parallel wq).

    ``gather_output=True`` constrains the (B, H, D) output back to
    replicated so a caller with REPLICATED weights executes the exact
    single-device wo projection (the PR-4/5 bitwise-parity contract,
    still used by the odd-KV replicate fallback).
    ``gather_output=False`` leaves the output HEAD-SHARDED, so a
    row-parallel wo consumes its local head slice natively and GSPMD
    inserts the single psum of the megatron block — no replicated
    gather of attention output or weights anywhere on the path.

    Requires ``axis`` to divide both the query and the KV head counts
    (``parallel.sharding.ShardingRules.cache_entry_pspec`` enforces the
    fallback-to-replicated policy before pools ever get here).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    # q/output heads sharded; a 4-D q is the K-token decode window
    # (B, K, H, D) — same head axis, one extra replicated window dim
    qs = (P(None, None, axis, None) if q.ndim == 4
          else P(None, axis, None))
    kv = page_layout.kv_axis(k_pages.shape, q.shape[-1])
    ps = P(*[axis if i == kv else None for i in range(4)])  # pools: KV dim
    ss = P(None, axis, None)                      # lane-major scales
    bs, ls = P(None, None), P(None)
    if k_scale is not None:
        def local(lq, kp, vp, ks, vs, bt, ln):
            return paged_attention(lq, kp, vp, bt, ln, window=window,
                                   ring=ring, scale=scale, k_scale=ks,
                                   v_scale=vs, impl=impl)
        f = jax.shard_map(local, mesh=mesh,
                          in_specs=(qs, ps, ps, ss, ss, bs, ls),
                          out_specs=qs, check_vma=False)
        o = f(q, k_pages, v_pages, k_scale, v_scale, block_tables, lengths)
    else:
        def local(lq, kp, vp, bt, ln):
            return paged_attention(lq, kp, vp, bt, ln, window=window,
                                   ring=ring, scale=scale, impl=impl)
        f = jax.shard_map(local, mesh=mesh, in_specs=(qs, ps, ps, bs, ls),
                          out_specs=qs, check_vma=False)
        o = f(q, k_pages, v_pages, block_tables, lengths)
    if not gather_output:
        return o                                  # head-sharded, per qs
    return jax.lax.with_sharding_constraint(o, NamedSharding(mesh, P()))


def quantize_rowwise(x, *, bits: int = 8, impl: str = "auto", bm: int = 128):
    if impl == "ref" or x.shape[0] % bm != 0 or \
            (impl == "auto" and _default_interpret()):
        return ref.quantize_rowwise_ref(x, bits=bits)
    return quantize_rowwise_pallas(x, bits=bits, bm=bm,
                                   interpret=_default_interpret())
