"""Pallas TPU kernel: flash attention forward (online softmax).

VMEM-tiled: Q block (bq, D) stays resident; K/V blocks (bk, D) stream in
along the 'arbitrary' grid dim with running (m, l, acc) scratch carried
across iterations.  Supports causal masking, sliding windows (gemma3
local layers) and GQA (query heads grouped onto kv heads by index map).

The assigned decode/long-context shapes run the *distributed* pure-JAX
attention (seq-sharded KV, GSPMD softmax) — this kernel is the TPU
hot-path for prefill, validated on CPU with interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, n_k: int, seq_q: int, seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    q_idx = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (seq_k - seq_q)                             # align ends
    k_idx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= q_idx >= k_idx
    if window:
        mask &= (q_idx - k_idx) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0, 0].astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == n_k - 1)
    def _done():
        # rows with no valid key (fully masked) have l == 0; emit zeros
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, window: int = 0,
                           scale: float | None = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D); returns (B, Sq, H, D).

    Layout inside the kernel is (B*H, S, D); GQA maps query head h to
    kv head h // (H // KV) in the K/V index maps.
    """
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    rep = H // KV
    sc = scale if scale is not None else 1.0 / (D ** 0.5)

    qx = q.transpose(0, 2, 1, 3)                      # (B, H, Sq, D)
    kx = k.transpose(0, 2, 1, 3)                      # (B, KV, Sk, D)
    vx = v.transpose(0, 2, 1, 3)
    n_k = Sk // bk

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // rep, j, 0))
    v_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // rep, j, 0))
    o_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))

    kernel = functools.partial(
        _flash_kernel, scale=sc, causal=causal, window=window,
        bq=bq, bk=bk, n_k=n_k, seq_q=Sq, seq_k=Sk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, Sq // bq, n_k),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),         # running max
            pltpu.VMEM((bq, 1), jnp.float32),         # running denom
            pltpu.VMEM((bq, D), jnp.float32),         # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qx, kx, vx)
    return out.transpose(0, 2, 1, 3)
