"""Pallas TPU kernel: dequantizing matmul  X @ dequant(W_q).

TPU adaptation of the paper's low-bit GEMM discussion (§II: "hardware
supports efficient low-bit GEMM ... necessitating custom CUDA kernels"):
on TPU the MXU computes in bf16/f32, so INT8/INT4 weights are a
*memory-bandwidth* optimization — W_q streams HBM->VMEM at 1 or 0.5
bytes/weight (int4 nibble-packed) and is dequantized in-register inside
the kernel, immediately before the MXU dot.  Scales are fused: per-channel
(one f32 per output column) or per-group (one per `group` rows of K).

Grid: (M/bm, N/bn, K/bk) with the K dimension 'arbitrary' (sequential),
f32 accumulator in VMEM scratch, blocks aligned to (128, 128) MXU tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant_block(wq, scale, *, bits: int, group: int, bk: int, bn: int):
    """int8 (or nibble-packed int4) block (bk[, /2], bn) -> f32 (bk, bn)."""
    if bits == 4:
        # packed: rows interleave (even, odd) nibbles
        lo = (wq & 0x0F).astype(jnp.int8)
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = ((wq >> 4) & 0x0F).astype(jnp.int8)
        hi = jnp.where(hi >= 8, hi - 16, hi)
        w = jnp.stack([lo, hi], axis=1).reshape(bk, bn)
    else:
        w = wq
    wf = w.astype(jnp.float32)
    if group:
        wf = wf.reshape(bk // group, group, bn) * scale
        wf = wf.reshape(bk, bn)
    else:
        wf = wf * scale            # (1, bn) per-channel broadcast
    return wf


def _qmm_kernel(x_ref, wq_ref, scale_ref, o_ref, acc_ref, *,
                bits: int, group: int, bk: int, bn: int, n_k: int,
                out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wf = _dequant_block(wq_ref[...], scale_ref[...],
                        bits=bits, group=group, bk=bk, bn=bn)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), wf,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def quant_matmul_pallas(x: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                        *, bits: int = 8, group: int = 0,
                        bm: int = 128, bn: int = 128, bk: int = 128,
                        out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """x: (M, K) float; wq: (K, N) int8 or (K//2, N) packed int4;
    scale: (1, N) per-channel f32 or (K//group, 1, N) per-group f32."""
    M, K = x.shape
    N = wq.shape[-1]
    K_logical = wq.shape[0] * (2 if bits == 4 else 1)
    assert K == K_logical, (K, K_logical)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    if group:
        assert bk % group == 0, (bk, group)
    out_dtype = out_dtype or x.dtype
    n_k = K // bk

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    if bits == 4:
        w_spec = pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j))
    else:
        w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    if group:
        s_spec = pl.BlockSpec((bk // group, 1, bn), lambda i, j, k: (k, 0, j))
    else:
        s_spec = pl.BlockSpec((1, bn), lambda i, j, k: (0, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))

    kernel = functools.partial(_qmm_kernel, bits=bits, group=group,
                               bk=bk, bn=bn, n_k=n_k, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[x_spec, w_spec, s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"quant_matmul_w{bits}",
    )(x, wq, scale.astype(jnp.float32))
