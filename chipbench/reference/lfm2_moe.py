"""Plain float32 reference of LFM2-MoE (``Lfm2MoeForCausalLM``), from the
published equations; it imports nothing of the program under test.

    h = E[x]
    per layer l:  a = RMSNorm(h)                               (operator_norm)
        conv layer:  [B, C, u] = split3(a W_in)
                     y[t] = C[t] * sum_j w[j] * (B * u)[t + j - (K - 1)]
                     h += y W_out                      (causal, depthwise)
        attention:   q, k, v = a Wq, a Wk, a Wv;  q, k = RMSNorm per head
                     rotate q, k;  h += softmax(q k^T / sqrt(D), causal) v Wo
        m = RMSNorm(h)                                         (ffn_norm)
        l < num_dense_layers:  h += (silu(m Wg) * (m Wu)) Wd
        else:  s = sigmoid(m Wr);  top-k of s + expert_bias picks experts;
               g = s[top] / (sum s[top] + 1e-6) * routed_scaling_factor
               h += sum over picked experts e held here of
                    g_e * (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = RMSNorm(h) E^T                                  (tied head)

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + s).  The conv runs over the
whole sequence, never from a carried state.  The routed layer computes
every held expert on every row and weights it by its gate (zero where
the router did not pick it), so no row is ever dropped; experts held on
other chips add nothing, as in the program.  Weights are regenerated
from the seed layer by layer (``chipbench.weights``) under the names the
adapter (``adapters/lfm2_moe.py``) uses, and every matmul runs at
"highest" precision.

``prec="fp8"`` is the control: every matmul operand rounded to float8
e4m3 with a per-row or per-column scale (``dense_gqa._fp8``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench.reference.dense_gqa import _attention, _mm, _rmsnorm, _rope
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
    F: int               # dense FFN width
    Fe: int              # expert width
    E: int               # published experts (router outputs)
    held: tuple          # (first, end) of the experts held here
    top_k: int
    V: int
    eps: float
    theta: float
    K: int               # conv kernel taps
    attn: tuple          # per layer: 1 attention, 0 conv
    dense: int           # leading dense-FFN layers
    norm_topk: bool
    scale: float
    tied: bool = True
    #: (weight name, extra right shift) pairs: ``weight_shift``
    shifts: tuple = ()

    @property
    def m_att(self) -> float:
        return self.D ** -0.5

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]

    def shift(self, name: str) -> int:
        return dict(self.shifts).get(name, 0)


def dims(cfg: dict) -> Dims:
    """The published shapes of ``cfg``; ``num_experts`` counts the experts
    held here (the first ones), the router keeps the published count."""
    H = cfg["num_attention_heads"]
    published = cfg.get("published", {})
    held = cfg["num_experts"]
    return Dims(L=cfg["num_hidden_layers"], d=cfg["hidden_size"], H=H,
                KV=cfg["num_key_value_heads"],
                D=cfg.get("head_dim") or cfg["hidden_size"] // H,
                F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
                E=published.get("num_experts", held), held=(0, held),
                top_k=cfg["num_experts_per_tok"], V=cfg["vocab_size"],
                eps=float(cfg["norm_eps"]), theta=float(cfg["rope_theta"]),
                K=cfg["conv_L_cache"],
                attn=tuple(int(t == "full_attention")
                           for t in cfg["layer_types"]),
                dense=cfg["num_dense_layers"],
                norm_topk=bool(cfg["norm_topk_prob"]),
                scale=float(cfg["routed_scaling_factor"]),
                tied=bool(cfg.get("tie_embedding", True)),
                shifts=tuple(sorted(cfg.get("weight_shift", {}).items())))


def _w(dm: Dims, key, name, layer, shape):
    return canonical(key, name, layer, shape,
                     shift=dm.shift(name)).astype(jnp.float32)


def _conv(dm: Dims, key, l, a, prec):
    T = a.shape[0]
    bcu = _mm(a, _w(dm, key, "conv_in", l, (dm.d, 3 * dm.d)), prec)
    b, c, u = jnp.split(bcu, 3, axis=-1)
    bu = jnp.concatenate([jnp.zeros((dm.K - 1, dm.d)), b * u])
    w = _w(dm, key, "conv", l, (dm.K, dm.d))
    y = c * sum(bu[j:j + T] * w[j] for j in range(dm.K))
    return _mm(y, _w(dm, key, "o", l, (dm.d, dm.d)), prec)


def _attn(dm: Dims, key, l, a, pos, prompt_len, prec):
    T = a.shape[0]
    qd, kvd = dm.H * dm.D, dm.KV * dm.D
    q = _mm(a, _w(dm, key, "q", l, (dm.d, qd)), prec).reshape(T, dm.H, dm.D)
    k = _mm(a, _w(dm, key, "k", l, (dm.d, kvd)), prec).reshape(
        T, dm.KV, dm.D)
    v = _mm(a, _w(dm, key, "v", l, (dm.d, kvd)), prec).reshape(
        T, dm.KV, dm.D)
    q = _rmsnorm(q, _w(dm, key, "q_norm", l, (dm.D,)), dm.eps)
    k = _rmsnorm(k, _w(dm, key, "k_norm", l, (dm.D,)), dm.eps)
    q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
    o = _attention(dm, q, k, v, prompt_len, 0, "none", prec)
    return _mm(o, _w(dm, key, "o", l, (dm.H * dm.D, dm.d)), prec)


def _dense(dm: Dims, key, l, m, prec):
    g = _mm(m, _w(dm, key, "gate", l, (dm.d, dm.F)), prec)
    u = _mm(m, _w(dm, key, "up", l, (dm.d, dm.F)), prec)
    return _mm(jax.nn.silu(g) * u, _w(dm, key, "down", l, (dm.F, dm.d)),
               prec)


def gates(dm: Dims, key, l, m, prec):
    """Router of layer ``l``: the gate of every published expert for
    every row (T, E), zero where the row did not pick the expert."""
    s = jax.nn.sigmoid(_mm(m, _w(dm, key, "router", l, (dm.d, dm.E)), prec))
    bias = _w(dm, key, "expert_bias", l, (dm.E,))
    _, top = jax.lax.top_k(s + bias, dm.top_k)
    g = jnp.take_along_axis(s, top, axis=-1)
    if dm.norm_topk:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, top].set(g * dm.scale)


def _experts(dm: Dims, key, l, m, prec):
    g = gates(dm, key, l, m, prec)[:, dm.held[0]:dm.held[1]]   # (T, held)
    Wg = _w(dm, key, "gate", l, (dm.n_held, dm.d, dm.Fe))
    Wu = _w(dm, key, "up", l, (dm.n_held, dm.d, dm.Fe))
    Wd = _w(dm, key, "down", l, (dm.n_held, dm.Fe, dm.d))

    def one(acc, e):
        h = jax.nn.silu(_mm(m, Wg[e], prec)) * _mm(m, Wu[e], prec)
        return acc + g[:, e, None] * _mm(h, Wd[e], prec), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(dm.n_held))
    return out


def _hidden(dm: Dims, prec, key, tokens, prompt_len):
    """Final-normed hidden states (T, d) of one token sequence."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    h = _w(dm, key, "embed", 0, (dm.V, dm.d))[tokens]
    is_attn = jnp.asarray(dm.attn, jnp.int32)

    def layer(h, l):
        a = _rmsnorm(h, _w(dm, key, "op_norm", l, (dm.d,)), dm.eps)
        h = h + jax.lax.cond(
            is_attn[l] == 1,
            lambda: _attn(dm, key, l, a, pos, prompt_len, prec),
            lambda: _conv(dm, key, l, a, prec))
        m = _rmsnorm(h, _w(dm, key, "ffn_norm", l, (dm.d,)), dm.eps)
        h = h + jax.lax.cond(l < dm.dense,
                             lambda: _dense(dm, key, l, m, prec),
                             lambda: _experts(dm, key, l, m, prec))
        return h, None

    h, _ = jax.lax.scan(layer, h, jnp.arange(dm.L))
    return _rmsnorm(h, _w(dm, key, "final_norm", 0, (dm.d,)), dm.eps)


def logits_all(key, tokens, *, dm: Dims, prec: str = "f32"):
    """Logits (T, V) at every position: for the CPU tests at small size."""
    h = _hidden(dm, prec, key, tokens, tokens.shape[0])
    return _mm(h, _w(dm, key, "embed", 0, (dm.V, dm.d)).T, prec)


@functools.partial(jax.jit, static_argnames=("dm", "kv_quant", "prec"))
def row_stats(key, tokens, prompt_len, matched, rows, picks, *, dm: Dims,
              kv_quant: str = "none", prec: str = "f32"):
    """Logit statistics of the sequence ``tokens`` (T,) at positions
    ``rows`` (R,), R a multiple of ``ROW_BLOCK`` and T of ``Q_BLOCK``.
    ``matched`` is unused (the configuration's engine caches no
    prefixes) and the pages are float32 (``kv_quant`` "none").

    Returns ``best`` (R,) the largest logit, ``maxabs`` (R,) the largest
    |logit|, ``picked`` (n, R) the logits of the token ids ``picks``
    (n, R), and ``top`` (R,) the argmax."""
    if kv_quant != "none":
        raise ValueError(f"lfm2_moe: float32 pages only, not {kv_quant}")
    del matched
    h = _hidden(dm, prec, key, tokens, prompt_len)
    Wh = _w(dm, key, "embed", 0, (dm.V, dm.d)).T

    def block(i):
        r = jax.lax.dynamic_slice_in_dim(rows, i * ROW_BLOCK, ROW_BLOCK)
        pk = jax.lax.dynamic_slice_in_dim(picks, i * ROW_BLOCK, ROW_BLOCK, 1)
        logits = _mm(h[r], Wh, prec)                               # (b, V)
        picked = jnp.take_along_axis(logits, pk.T, axis=1).T
        return (jnp.max(logits, -1), jnp.max(jnp.abs(logits), -1), picked,
                jnp.argmax(logits, -1).astype(jnp.int32))

    best, maxabs, picked, top = jax.lax.map(
        block, jnp.arange(rows.shape[0] // ROW_BLOCK))
    return (best.reshape(-1), maxabs.reshape(-1),
            jnp.moveaxis(picked, 1, 0).reshape(picks.shape[0], -1),
            top.reshape(-1))
