"""Plain float32 reference of a dense pre-norm decoder with grouped-query
attention and rotary positions (Qwen2, Granite), from the published
equations; it imports nothing of the program under test.

    h = embedding_multiplier * E[x]
    per layer:  a = RMSNorm(h);  q, k, v = a Wq, a Wk, a Wv;  rotate q, k
                h += residual_multiplier * softmax(attention_multiplier
                                           * q k^T, causal) v Wo
                m = RMSNorm(h)
                h += residual_multiplier * (silu(m Wg) * (m Wu)) Wd
    logits = RMSNorm(h) Wh / logits_scaling      (Wh = E^T when tied)

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + s).  Weights are
regenerated from the seed layer by layer (``chipbench.weights``), and
every matmul runs at "highest" precision.

Quantized KV (``kv_quant="int4"``): a served model stores every key and
value it has written in the cache, symmetric per (token, KV head) with
scale max|x|/7.  Rows at or past ``prompt_len`` (decode) read only
stored keys and values; prompt rows read the stored ones below
``matched`` (a prefix-cache hit) and their own unquantized ones after.

``prec="fp8"`` is the control: every matmul operand rounded to float8
e4m3 with a per-row or per-column scale.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench.weights import canonical

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256          # logit rows per head block
Q_BLOCK = 512            # query rows per attention block


@dataclasses.dataclass(frozen=True)
class Dims:
    L: int
    d: int
    H: int
    KV: int
    D: int
    F: int
    V: int
    eps: float
    theta: float
    m_emb: float
    m_att: float
    m_res: float
    m_logit: float
    tied: bool
    #: (weight name, extra right shift) pairs: ``weight_shift``
    shifts: tuple = ()

    def shift(self, name: str) -> int:
        return dict(self.shifts).get(name, 0)


def dims(cfg: dict) -> Dims:
    H = cfg["num_attention_heads"]
    D = cfg.get("head_dim") or cfg["hidden_size"] // H
    return Dims(L=cfg["num_hidden_layers"], d=cfg["hidden_size"], H=H,
                KV=cfg["num_key_value_heads"], D=D,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                # where the program's epsilon departs from the published
                # one, the reference follows the program
                eps=float(cfg.get("program", {}).get("rms_norm_eps",
                                                     cfg["rms_norm_eps"])),
                theta=float(cfg["rope_theta"]),
                m_emb=float(cfg.get("embedding_multiplier", 1.0)),
                m_att=float(cfg.get("attention_multiplier", D ** -0.5)),
                m_res=float(cfg.get("residual_multiplier", 1.0)),
                m_logit=float(cfg.get("logits_scaling", 1.0)),
                tied=bool(cfg.get("tie_word_embeddings", False)),
                shifts=tuple(sorted(cfg.get("weight_shift", {}).items())))


def _fp8(x, axis):
    """Round to float8 e4m3 (3 mantissa bits, normals down to 2**-6)
    after scaling max|x| along ``axis`` to 448."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    y = x / s
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.abs(y))), -6.0)
    step = jnp.exp2(e - 3.0)
    return jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) * s


def _mm(x, w, prec):
    if prec == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + s)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _store(x, kv_quant):
    """What the cache holds of ``x`` (T, KV, D)."""
    if kv_quant == "none":
        return x
    top = {"int4": 7.0, "int8": 127.0}[kv_quant]
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / top
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _w(dm: Dims, key, name, layer, shape):
    return canonical(key, name, layer, shape,
                     shift=dm.shift(name)).astype(jnp.float32)


def _attention(dm: Dims, q, k, v, prompt_len, matched, kv_quant, prec):
    T = q.shape[0]
    G = dm.H // dm.KV
    ks, vs = _store(k, kv_quant), _store(v, kv_quant)
    key_pos = jnp.arange(T)
    if prec == "fp8":
        k, v, ks, vs = _fp8(k, -1), _fp8(v, 0), _fp8(ks, -1), _fp8(vs, 0)

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK)
        if prec == "fp8":
            qb = _fp8(qb, -1)
        qb = qb.reshape(Q_BLOCK, dm.KV, G, dm.D)
        stored = ((rows[:, None] >= prompt_len)
                  | (key_pos[None, :] < matched))             # (t, s)
        s = jnp.einsum("tkgd,skd->kgts", qb, k, precision=HIGHEST)
        if kv_quant != "none":
            sq = jnp.einsum("tkgd,skd->kgts", qb, ks, precision=HIGHEST)
            s = jnp.where(stored, sq, s)
        s = jnp.where(key_pos[None, :] <= rows[:, None], s * dm.m_att,
                      -jnp.inf)
        p = jax.nn.softmax(s, -1)
        if prec == "fp8":
            p = _fp8(p, -1)
        if kv_quant == "none":
            o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)
        else:
            o = (jnp.einsum("kgts,skd->tkgd", jnp.where(stored, 0.0, p), v,
                            precision=HIGHEST)
                 + jnp.einsum("kgts,skd->tkgd", jnp.where(stored, p, 0.0),
                              vs, precision=HIGHEST))
        return o.reshape(Q_BLOCK, dm.H * dm.D)

    starts = jnp.arange(0, T, Q_BLOCK)
    return jax.lax.map(block, starts).reshape(T, dm.H * dm.D)


def _hidden(dm: Dims, kv_quant, prec, key, tokens, prompt_len, matched):
    """Final-normed hidden states (T, d) of one token sequence."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    E = _w(dm, key, "embed", 0, (dm.V, dm.d))
    h = dm.m_emb * E[tokens]
    del E
    qd, kvd = dm.H * dm.D, dm.KV * dm.D

    def layer(h, l):
        a = _rmsnorm(h, _w(dm, key, "attn_norm", l, (dm.d,)), dm.eps)
        q = _mm(a, _w(dm, key, "q", l, (dm.d, qd)), prec).reshape(
            T, dm.H, dm.D)
        k = _mm(a, _w(dm, key, "k", l, (dm.d, kvd)), prec).reshape(
            T, dm.KV, dm.D)
        v = _mm(a, _w(dm, key, "v", l, (dm.d, kvd)), prec).reshape(
            T, dm.KV, dm.D)
        q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
        o = _attention(dm, q, k, v, prompt_len, matched, kv_quant, prec)
        h = h + dm.m_res * _mm(o, _w(dm, key, "o", l, (qd, dm.d)), prec)
        m = _rmsnorm(h, _w(dm, key, "mlp_norm", l, (dm.d,)), dm.eps)
        g = _mm(m, _w(dm, key, "gate", l, (dm.d, dm.F)), prec)
        u = _mm(m, _w(dm, key, "up", l, (dm.d, dm.F)), prec)
        h = h + dm.m_res * _mm(jax.nn.silu(g) * u,
                               _w(dm, key, "down", l, (dm.F, dm.d)), prec)
        return h, None

    h, _ = jax.lax.scan(layer, h, jnp.arange(dm.L))
    return _rmsnorm(h, _w(dm, key, "final_norm", 0, (dm.d,)), dm.eps)


@functools.partial(jax.jit, static_argnames=("dm", "kv_quant", "prec"))
def row_stats(key, tokens, prompt_len, matched, rows, picks, *, dm: Dims,
              kv_quant: str = "none", prec: str = "f32"):
    """Logit statistics of the sequence ``tokens`` (T,) at positions
    ``rows`` (R,), R a multiple of ``ROW_BLOCK`` and T of ``Q_BLOCK``.

    Returns ``best`` (R,) the largest logit, ``maxabs`` (R,) the largest
    |logit|, ``picked`` (n, R) the logits of the token ids ``picks``
    (n, R), and ``top`` (R,) the argmax."""
    h = _hidden(dm, kv_quant, prec, key, tokens, prompt_len, matched)
    if dm.tied:
        Wh = _w(dm, key, "embed", 0, (dm.V, dm.d)).T
    else:
        Wh = _w(dm, key, "head", 0, (dm.d, dm.V))

    def block(i):
        r = jax.lax.dynamic_slice_in_dim(rows, i * ROW_BLOCK, ROW_BLOCK)
        pk = jax.lax.dynamic_slice_in_dim(picks, i * ROW_BLOCK, ROW_BLOCK, 1)
        logits = _mm(h[r], Wh, prec) / dm.m_logit                  # (b, V)
        picked = jnp.take_along_axis(logits, pk.T, axis=1).T
        return (jnp.max(logits, -1), jnp.max(jnp.abs(logits), -1), picked,
                jnp.argmax(logits, -1).astype(jnp.int32))

    best, maxabs, picked, top = jax.lax.map(
        block, jnp.arange(rows.shape[0] // ROW_BLOCK))
    return (best.reshape(-1), maxabs.reshape(-1),
            jnp.moveaxis(picked, 1, 0).reshape(picks.shape[0], -1),
            top.reshape(-1))
