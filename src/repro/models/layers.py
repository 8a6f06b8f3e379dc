"""Shared layer implementations (pure functions over param dicts).

Every layer's parameter names/shapes come from ``core.blocks`` — the same
declarations the analytical profiler counts — so the profile and the HLO
always agree.  All functions take ``impl`` hints so the dry-run lowers
pure-jnp (GSPMD-partitionable) code while TPU runs hit the Pallas kernels.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.model_config import ModelSpec
from repro.quant.qlinear import dequant_param, qdot
from repro.models.scan_util import scan as _scan

Params = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# Norms / activations / RoPE
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    # scale stored as (1 + s) like rmsnorm, so zero-init is identity
    return (out * (1.0 + scale.astype(jnp.float32))
            + bias.astype(jnp.float32)).astype(x.dtype)


def norm(spec: ModelSpec, p: Params, name: str, x: jnp.ndarray) -> jnp.ndarray:
    if spec.norm == "layernorm":
        return layernorm(x, p[name], p[name + "_b"])
    return rmsnorm(x, p[name], spec.norm_eps)


def qk_norm(spec: ModelSpec, p: Params, q: jnp.ndarray, k: jnp.ndarray,
            prefix: str = ""):
    """Per-head RMSNorm of q (..., H, D) and k (..., KV, D) before RoPE,
    where the spec asks for it (``qk_norm``)."""
    if prefix + "q_norm" not in p:
        return q, k
    return (rmsnorm(q, p[prefix + "q_norm"], spec.norm_eps),
            rmsnorm(k, p[prefix + "k_norm"], spec.norm_eps))


def activation(spec: ModelSpec, x: jnp.ndarray) -> jnp.ndarray:
    if spec.act in ("silu", "swiglu"):
        return jax.nn.silu(x)
    if spec.act == "gelu":
        return jax.nn.gelu(x)
    if spec.act == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(spec.act)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq        # (..., S, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., None, :]                                      # (..., S, 1, half)
    cos = cos[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _compute_dtype(q: jnp.ndarray):
    """Matmul operand dtype: keep bf16/f16 operands as-is (f32 ACCUMULATION
    via preferred_element_type) — avoids materializing f32 copies of the KV
    cache, the dominant HBM-traffic term found in the decode hillclimb
    (EXPERIMENTS.md §Perf). f32 inputs keep full precision."""
    return q.dtype if q.dtype in (jnp.bfloat16, jnp.float16) else jnp.float32


def _grouped_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q (B,Sq,H,D), k (B,Sk,KV,D) -> f32 logits (B,KV,G,Sq,Sk) without
    materializing repeated KV (G = H // KV)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    ct = _compute_dtype(q)
    qg = q.reshape(B, Sq, KV, H // KV, D).astype(ct)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(ct),
                      preferred_element_type=jnp.float32)


def _grouped_out(p: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """p (B,KV,G,Sq,Sk) f32 probs, v (B,Sk,KV,D) -> f32 (B,Sq,H,D).
    P is cast down to the V operand dtype for the matmul (TPU flash
    convention); accumulation stays f32."""
    B, KV, G, Sq, Sk = p.shape
    ct = _compute_dtype(v)
    out = jnp.einsum("bkgst,btkd->bskgd", p.astype(ct), v.astype(ct),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, KV * G, out.shape[-1])


def _mask(Sq: int, Sk: int, causal: bool, window: int, q_offset) -> jnp.ndarray:
    q_idx = jnp.arange(Sq)[:, None] + q_offset
    k_idx = jnp.arange(Sk)[None, :]
    m = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        m &= q_idx >= k_idx
    if window:
        m &= (q_idx - k_idx) < window
    return m


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, causal: bool,
         window: int = 0, softcap: float = 0.0) -> jnp.ndarray:
    """Full-materialization grouped-query attention (smoke / short-seq)."""
    D = q.shape[-1]
    s = _grouped_scores(q, k) / math.sqrt(D)            # f32 logits
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    m = _mask(q.shape[1], k.shape[1], causal, window, k.shape[1] - q.shape[1])
    s = jnp.where(m[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_out(p, v).astype(q.dtype)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, window: int = 0,
                      chunk: int = 1024) -> jnp.ndarray:
    """Online-softmax attention scanning KV chunks: O(S·chunk) memory.

    Pure jnp (GSPMD-partitionable) — the long-prefill path the dry-run
    lowers; mathematically identical to the Pallas flash kernel.
    """
    import os as _os
    chunk = int(_os.environ.get("REPRO_ATTN_CHUNK", chunk))
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    chunk = min(chunk, Sk)
    if Sk % chunk:
        chunk = math.gcd(Sk, chunk) or Sk
    n = Sk // chunk
    G = H // KV
    ct = _compute_dtype(q)
    qf = (q.astype(ct) / math.sqrt(D)).reshape(B, Sq, KV, G, D)
    kc = k.astype(ct).reshape(B, n, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    vc = v.astype(ct).reshape(B, n, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    q_idx = jnp.arange(Sq)[:, None] + (Sk - Sq)

    def step(carry, xs):
        m_run, l_run, acc = carry
        kb, vb, start = xs
        s = jnp.einsum("bskgd,btkd->bkgst", qf, kb,
                       preferred_element_type=jnp.float32)        # (B,KV,G,Sq,c)
        k_idx = start + jnp.arange(chunk)[None, :]
        msk = jnp.ones((Sq, chunk), dtype=bool)
        if causal:
            msk &= q_idx >= k_idx
        if window:
            msk &= (q_idx - k_idx) < window
        s = jnp.where(msk[None, None, None], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(ct), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KV, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, D), jnp.float32)
    starts = jnp.arange(n) * chunk
    (m_f, l_f, acc), _ = _scan(step, (m0, l0, a0), (kc, vc, starts))
    l_f = jnp.where(l_f == 0.0, 1.0, l_f)
    out = (acc / l_f[..., None]).transpose(0, 3, 1, 2, 4)          # (B,Sq,KV,G,D)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     pos, *, window: int = 0,
                     ring: bool = False) -> jnp.ndarray:
    """Single-token attention against a cache.

    q: (B, 1, H, D); caches (B, S, KV, D).  ``pos`` is the absolute index
    of the current token.  For ring-buffer (sliding-window) caches, slot
    j holds absolute position  pos - ((pos - j) mod S).
    """
    B, _, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    s = _grouped_scores(q, k_cache) / math.sqrt(D)                 # (B,KV,G,1,S)
    idx = jnp.arange(S)
    if ring:
        abs_pos = pos - jnp.mod(pos - idx, S)
        valid = abs_pos >= 0
        if window:
            valid &= (pos - abs_pos) < window
    else:
        valid = idx <= pos
        if window:
            valid &= (pos - idx) < window
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_out(p, v_cache).astype(q.dtype)


def attention_block(spec: ModelSpec, p: Params, x: jnp.ndarray,
                    positions: jnp.ndarray, *, kind: str = "attn",
                    impl: str = "auto", prefix: str = "") -> jnp.ndarray:
    """Projections + RoPE + attention (+output proj). No residual/norm."""
    B, S, _ = x.shape
    H, KV, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    q = qdot(x, p[prefix + "wq"]).reshape(B, S, H, D)
    k = qdot(x, p[prefix + "wk"]).reshape(B, S, KV, D)
    v = qdot(x, p[prefix + "wv"]).reshape(B, S, KV, D)
    q, k = qk_norm(spec, p, q, k, prefix)
    causal = kind != "enc_attn"
    if causal:
        q = rope(q, positions, spec.rope_theta)
        k = rope(k, positions, spec.rope_theta)
    window = spec.sliding_window if kind == "attn_local" else 0
    if impl == "pallas":
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=causal, window=window)
    elif impl == "chunked" or (impl == "auto" and S > 2048):
        o = chunked_attention(q, k, v, causal=causal, window=window)
    else:
        o = sdpa(q, k, v, causal=causal, window=window,
                 softcap=spec.attn_logit_softcap)
    return qdot(o.reshape(B, S, H * D), p[prefix + "wo"])


def cross_attention_block(spec: ModelSpec, p: Params, x: jnp.ndarray,
                          enc_out: jnp.ndarray) -> jnp.ndarray:
    B, S, _ = x.shape
    H, KV, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    q = qdot(x, p["cross_wq"]).reshape(B, S, H, D)
    k = qdot(enc_out, p["cross_wk"]).reshape(B, enc_out.shape[1], KV, D)
    v = qdot(enc_out, p["cross_wv"]).reshape(B, enc_out.shape[1], KV, D)
    o = sdpa(q, k, v, causal=False)
    return qdot(o.reshape(B, S, H * D), p["cross_wo"])


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp_block(spec: ModelSpec, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = qdot(x, p["mlp_wi"])
    if spec.act in ("silu", "swiglu"):
        gate, up = jnp.split(h, 2, axis=-1)
        h = activation(spec, gate) * up
    else:
        h = activation(spec, h)
    return qdot(h, p["mlp_wo"])


def _gated_ff(spec: ModelSpec, wi, wo, x: jnp.ndarray) -> jnp.ndarray:
    h = qdot(x, wi)
    gate, up = jnp.split(h, 2, axis=-1)
    return qdot(activation(spec, gate) * up, wo)


def moe_block(spec: ModelSpec, p: Params, x: jnp.ndarray,
              group_size: int = 512) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GShard-style capacity-based token-choice MoE (dense dispatch einsum),
    the training path; serving runs the dropless ``routed_experts``.

    Routing is ``route``'s; the held experts (``spec.moe.held_range``)
    are the dispatch targets and assignments to other experts are
    dropped.  Returns (output, aux_loss).  Expert weights carry a
    leading padded expert dim sharded on the 'model' axis; the dispatch
    einsum becomes the EP all-to-all under GSPMD.
    """
    m = spec.moe
    B, S, d = x.shape
    E, k = m.num_experts, m.top_k
    lo, hi = m.held_range
    Ep = hi - lo
    T = B * S
    g = min(group_size, T)
    G = T // g
    xg = x.reshape(G, g, d)

    probs, top_e, top_p = route(spec, p, xg)                      # (G,g,E|k)

    # Switch aux loss: E * sum_e f_e * P_e
    fe = jnp.mean(jax.nn.one_hot(top_e[..., 0], E), axis=(0, 1))
    pe = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(fe * pe)

    cap = max(1, int(g * k * m.capacity_factor / E))
    # ids outside the held range one-hot to zero rows: never dispatched
    onehot = jax.nn.one_hot(top_e - lo, Ep, dtype=jnp.float32)    # (G,g,k,Ep)
    # global position-in-expert over the flattened (token, k) sequence so
    # different k-lanes of different tokens never collide on a slot
    flat = onehot.reshape(G, g * k, Ep)
    pos1 = jnp.cumsum(flat, axis=1) * flat                        # 1-based, 0=inactive
    pos1 = pos1.reshape(G, g, k, Ep).sum(axis=2)                  # (G,g,Ep): ≤1 active k
    kept = (pos1 >= 1.0) & (pos1 <= cap)
    gates = jnp.einsum("gtke,gtk->gte", onehot, top_p) * kept     # (G,g,Ep)
    pos0 = jnp.clip(pos1 - 1.0, 0, cap - 1).astype(jnp.int32)
    pos_oh = jax.nn.one_hot(pos0, cap, dtype=jnp.float32)         # (G,g,Ep,cap)
    dispatch = pos_oh * kept[..., None]
    combine = pos_oh * gates[..., None]

    xin = jnp.einsum("gtec,gtd->egcd", dispatch,
                     xg.astype(jnp.float32)).astype(x.dtype)      # (Ep,G,cap,d)
    wi = dequant_param(p["experts_wi"])
    wo = dequant_param(p["experts_wo"])
    h = jnp.einsum("egcd,edf->egcf", xin, wi.astype(x.dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    h = activation(spec, gate) * up
    xout = jnp.einsum("egcf,efd->egcd", h, wo.astype(x.dtype))
    out = jnp.einsum("egcd,gtec->gtd", xout.astype(jnp.float32), combine)
    out = out.reshape(B, S, d).astype(x.dtype)

    if m.num_shared_experts:
        out = out + _gated_ff(spec, p["shared_wi"], p["shared_wo"], x)
    return out, aux.astype(jnp.float32)


def route(spec: ModelSpec, p: Params, x: jnp.ndarray):
    """Router of a routed-expert layer, in float32 over every published
    expert: scores (T, E) (softmax or sigmoid of ``x @ router_w``), and
    the top-k expert ids (T, k) and weights (T, k).  Selection ranks
    ``scores + expert_bias`` where the spec has a selection bias; the
    weights are the unbiased scores, divided by their sum (+1e-6 for
    sigmoid scores, as the published sigmoid routers do) under
    ``norm_topk``, times ``scale``."""
    m = spec.moe
    logits = jnp.dot(x.astype(jnp.float32), p["router_w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if m.score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if m.expert_bias:
        _, top_e = jax.lax.top_k(scores + p["expert_bias"].astype(jnp.float32),
                                 m.top_k)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    else:
        top_w, top_e = jax.lax.top_k(scores, m.top_k)
    if m.norm_topk:
        eps = 1e-6 if m.score == "sigmoid" else 0.0
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + eps)
    return scores, top_e, top_w * m.scale


def routed_experts(spec: ModelSpec, p: Params, x: jnp.ndarray,
                   valid: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless routed-expert layer over the experts this chip holds.

    Routes every row over all published experts (``route``), keeps the
    (row, expert) assignments that fall in the held range
    ``spec.moe.held_range``, groups them by held expert and runs the
    gated FFN as two grouped matmuls (``jax.lax.ragged_dot``: on a TPU
    the ``ragged-dot`` ops of a device trace) over the held experts'
    weights, then combines each row's weighted expert outputs.  No row
    is ever dropped, so a row's output never depends on the other rows
    of its batch.  Assignments to experts held elsewhere contribute
    nothing here; rows where ``valid`` (T,) is False (bucket padding)
    are routed nowhere.  Shared experts run densely on every row.

    x: (..., d).  Returns (output like x, rows per held expert (H,)
    int32)."""
    m = spec.moe
    lo, hi = m.held_range
    H, k = hi - lo, m.top_k
    shape, d = x.shape, x.shape[-1]
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    _, top_e, top_w = route(spec, p, xf)
    local = top_e - lo
    held = (local >= 0) & (local < H)
    if valid is not None:
        held &= valid.reshape(T, 1)
    # sort the T*k assignments by held expert; the rest go last, in a
    # group of their own that no weight reads
    key = jnp.where(held, local, H).reshape(-1)
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=H + 1).astype(jnp.int32)
    rows = xf[order // k]
    wi = dequant_param(p["experts_wi"]).astype(x.dtype)
    wo = dequant_param(p["experts_wo"]).astype(x.dtype)
    h = jax.lax.ragged_dot(rows, wi, counts[:H],
                           preferred_element_type=jnp.float32)
    gate, up = jnp.split(h, 2, axis=-1)
    h = (activation(spec, gate) * up).astype(x.dtype)
    y = jax.lax.ragged_dot(h, wo, counts[:H],
                           preferred_element_type=jnp.float32)
    # rows past the held groups are never written by the grouped
    # matmul: select them away rather than multiply (no NaN leaks)
    y = jnp.where((key[order] < H)[:, None], y, 0.0)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    y = y[inv].reshape(T, k, d)
    out = jnp.sum(y * top_w[..., None], axis=1)
    out = out.reshape(shape).astype(x.dtype)
    if m.num_shared_experts:
        out = out + _gated_ff(spec, p["shared_wi"], p["shared_wo"], x)
    return out, counts[:H]


def ffn(spec: ModelSpec, p: Params, x: jnp.ndarray,
        valid: Optional[jnp.ndarray] = None
        ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """A layer's serving FFN: the dropless routed-expert layer where the
    layer has a router, else the dense MLP.  Returns (output, rows per
    held expert or None)."""
    if "router_w" in p:
        return routed_experts(spec, p, x, valid)
    return mlp_block(spec, p, x), None


# ---------------------------------------------------------------------------
# Gated short convolution
# ---------------------------------------------------------------------------

def _conv_in(p: Params, x: jnp.ndarray):
    """[B, C, h] = split3(x W_in); returns (B * h, C)."""
    bch = qdot(x, p["conv_in"])
    b, c, h = jnp.split(bch, 3, axis=-1)
    return b * h, c


def short_conv(spec: ModelSpec, p: Params, x: jnp.ndarray,
               state: Optional[jnp.ndarray] = None,
               true_len=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gated short convolution over a sequence x (B, S, d):
    ``y = C * conv(B * h)``, ``out = y W_out``, with ``conv`` causal and
    depthwise over ``conv_width`` taps (``conv_w[j]`` weights the input
    ``conv_width - 1 - j`` positions back).

    ``state`` (B, conv_width - 1, d) holds the ``B * h`` rows before the
    sequence (zeros at a sequence's start).  Returns (out, new state):
    the last ``conv_width - 1`` rows of ``B * h`` before position
    ``true_len`` (default S), so a bucket-padded prompt leaves the state
    at its true end."""
    K = spec.conv_width
    Bn, S, d = x.shape
    bx, c = _conv_in(p, x)
    if state is None:
        state = jnp.zeros((Bn, K - 1, d), jnp.float32)
    full = jnp.concatenate([state.astype(bx.dtype), bx], axis=1)  # (B, S+K-1, d)
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(full[:, j:j + S].astype(jnp.float32) * w[j]
               for j in range(K))
    y = (c.astype(jnp.float32) * conv).astype(x.dtype)
    end = S if true_len is None else true_len
    new_state = jax.lax.dynamic_slice_in_dim(full, end, K - 1, axis=1)
    return qdot(y, p["conv_out"]), new_state.astype(jnp.float32)

