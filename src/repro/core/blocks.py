"""Declarative per-block parameter shapes, FLOP counts and state shapes.

This is the single source of truth shared by:
  * ``core/analytical.py`` — the EdgeProfiler analytical model (params,
    FLOPs/token, memory) is computed from these declarations, and
  * ``models/`` — JAX model init materializes exactly these shapes.

so the analytical prediction and the lowered HLO always describe the same
network.  A unit test asserts ``analytical params == sum(model leaves)``.

Conventions: all linear layers are bias-free (biases are <0.1 % of params
for every assigned arch; noted in DESIGN.md), weights are stored
``(in_dim, out_dim)``, MoE expert weights carry a leading expert dim.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

from repro.core.model_config import ModelSpec

Shape = Tuple[int, ...]


def _prod(s: Shape) -> int:
    out = 1
    for x in s:
        out *= x
    return out


# ---------------------------------------------------------------------------
# Parameter shape plans
# ---------------------------------------------------------------------------

def attention_param_shapes(spec: ModelSpec, cross: bool = False) -> Dict[str, Shape]:
    d, q, kv = spec.d_model, spec.q_dim, spec.kv_dim
    pre = "cross_" if cross else ""
    return {
        f"{pre}wq": (d, q),
        f"{pre}wk": (d, kv),
        f"{pre}wv": (d, kv),
        f"{pre}wo": (q, d),
    }


def mlp_param_shapes(spec: ModelSpec, d_ff: int = 0) -> Dict[str, Shape]:
    d = spec.d_model
    ff = d_ff or spec.d_ff
    if ff == 0:
        return {}
    if spec.act in ("silu", "swiglu"):          # gated
        return {"mlp_wi": (d, 2 * ff), "mlp_wo": (ff, d)}
    return {"mlp_wi": (d, ff), "mlp_wo": (ff, d)}


def moe_param_shapes(spec: ModelSpec) -> Dict[str, Shape]:
    m = spec.moe
    assert m is not None
    d = spec.d_model
    ep = m.num_held                  # the experts this chip holds
    out = {
        "router_w": (d, m.num_experts),
        "experts_wi": (ep, d, 2 * m.expert_ff),
        "experts_wo": (ep, m.expert_ff, d),
    }
    if m.expert_bias:
        out["expert_bias"] = (m.num_experts,)
    if m.num_shared_experts:
        sff = m.shared_ff or m.num_shared_experts * m.expert_ff
        out["shared_wi"] = (d, 2 * sff)
        out["shared_wo"] = (sff, d)
    return out


def conv_param_shapes(spec: ModelSpec) -> Dict[str, Shape]:
    """Gated short convolution: in-projection to [B, C, h], a depthwise
    causal kernel of ``conv_width`` taps, out-projection (no biases)."""
    d = spec.d_model
    return {"conv_in": (d, 3 * d), "conv_w": (spec.conv_width, d),
            "conv_out": (d, d)}


def ffn_param_shapes(spec: ModelSpec, layer_idx: int) -> Dict[str, Shape]:
    if spec.moe_layer(layer_idx):
        return moe_param_shapes(spec)
    return mlp_param_shapes(spec)


def ssm_param_shapes(spec: ModelSpec) -> Dict[str, Shape]:
    s = spec.ssm
    assert s is not None
    d = spec.d_model
    d_inner = s.expand * d
    nh = s.num_heads or d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.state_dim        # x, B, C share the conv
    return {
        "ssm_in_proj": (d, 2 * d_inner + 2 * s.state_dim + nh),
        "ssm_conv_w": (s.conv_width, conv_dim),
        "ssm_A_log": (nh,),
        "ssm_D": (nh,),
        "ssm_dt_bias": (nh,),
        "ssm_gate_norm": (d_inner,),
        "ssm_out_proj": (d_inner, d),
    }


def mlstm_param_shapes(spec: ModelSpec) -> Dict[str, Shape]:
    x = spec.xlstm
    assert x is not None
    d = spec.d_model
    inner = int(x.proj_factor * d)
    qk = int(x.qk_dim_factor * inner)
    nh = spec.num_heads
    return {
        "ml_up": (d, 2 * inner),
        "ml_q": (inner, qk),
        "ml_k": (inner, qk),
        "ml_v": (inner, inner),
        "ml_igate": (inner, nh),
        "ml_fgate": (inner, nh),
        "ml_onorm": (inner,),
        "ml_down": (inner, d),
    }


def slstm_param_shapes(spec: ModelSpec) -> Dict[str, Shape]:
    d = spec.d_model
    return {
        "sl_wx": (d, 4 * d),     # i, f, z, o input projections (fused)
        "sl_wr": (d, 4 * d),     # recurrent projections (fused)
        "sl_bias": (4 * d,),
    }


def norm_shapes(spec: ModelSpec, names: Tuple[str, ...]) -> Dict[str, Shape]:
    out: Dict[str, Shape] = {}
    for n in names:
        out[n] = (spec.d_model,)
        if spec.norm == "layernorm":
            out[n + "_b"] = (spec.d_model,)
    return out


def layer_param_shapes(spec: ModelSpec, kind: str, layer_idx: int = 0) -> Dict[str, Shape]:
    """All parameter shapes for one layer of the given kind."""
    out: Dict[str, Shape] = {}
    if kind in ("attn", "attn_local", "attn_global"):
        out.update(norm_shapes(spec, ("norm1", "norm2")))
        out.update(attention_param_shapes(spec))
        if spec.qk_norm:
            out["q_norm"] = (spec.head_dim,)
            out["k_norm"] = (spec.head_dim,)
        if spec.cross_attention:
            out.update(norm_shapes(spec, ("norm_cross",)))
            out.update(attention_param_shapes(spec, cross=True))
        out.update(ffn_param_shapes(spec, layer_idx))
    elif kind == "conv":
        out.update(norm_shapes(spec, ("norm1", "norm2")))
        out.update(conv_param_shapes(spec))
        out.update(ffn_param_shapes(spec, layer_idx))
    elif kind == "enc_attn":                      # encoder layer: non-causal attn + mlp
        out.update(norm_shapes(spec, ("norm1", "norm2")))
        out.update(attention_param_shapes(spec))
        out.update(mlp_param_shapes(spec))
    elif kind == "ssm":
        out.update(norm_shapes(spec, ("norm1",)))
        out.update(ssm_param_shapes(spec))
    elif kind == "mlstm":
        out.update(norm_shapes(spec, ("norm1",)))
        out.update(mlstm_param_shapes(spec))
    elif kind == "slstm":
        out.update(norm_shapes(spec, ("norm1",)))
        out.update(slstm_param_shapes(spec))
    else:
        raise ValueError(f"unknown layer kind {kind}")
    return out


def shared_block_param_shapes(spec: ModelSpec) -> Dict[str, Shape]:
    """zamba2: ONE shared transformer block reused every ``attn_every`` layers."""
    out: Dict[str, Shape] = {}
    out.update(norm_shapes(spec, ("norm1", "norm2")))
    out.update(attention_param_shapes(spec))
    out.update(mlp_param_shapes(spec))
    return out


def global_param_shapes(spec: ModelSpec) -> Dict[str, Shape]:
    """Embedding, head, final norm, frontend projections."""
    d, vp = spec.d_model, spec.padded_vocab
    out: Dict[str, Shape] = {"embed": (vp, d)}
    out.update(norm_shapes(spec, ("final_norm",)))
    if not spec.tie_embeddings:
        out["head"] = (d, vp)
    if spec.vision_tokens:
        out["vision_proj"] = (spec.vision_embed_dim, d)
        out["vision_norm"] = (spec.vision_embed_dim,)
    if spec.encoder_layers:
        # encoder final norm; encoder input is the precomputed-frontend stub
        out["enc_final_norm"] = (d,)
        if spec.norm == "layernorm":
            out["enc_final_norm_b"] = (d,)
    return out


def param_count(spec: ModelSpec, padded: bool = True) -> int:
    """Exact parameter count (matches model init leaf-for-leaf).

    padded=False removes vocab/expert padding to report the *logical* model
    size (what the paper's eq. 7 describes).
    """
    total = 0
    for i, kind in enumerate(spec.layer_kinds()):
        for name, shape in layer_param_shapes(spec, kind, i).items():
            n = _prod(shape)
            if (not padded and spec.moe is not None
                    and spec.moe.held is None and name.startswith("experts_")):
                n = n * spec.moe.num_experts // spec.moe.padded_experts
            total += n
    if spec.ssm is not None and spec.attn_every:
        total += sum(_prod(s) for s in shared_block_param_shapes(spec).values())
    if spec.encoder_layers:
        for _ in range(spec.encoder_layers):
            total += sum(_prod(s) for s in layer_param_shapes(spec, "enc_attn").values())
    for name, shape in global_param_shapes(spec).items():
        n = _prod(shape)
        if not padded and name in ("embed", "head"):
            n = n * spec.vocab_size // spec.padded_vocab
        total += n
    return total


def active_param_count(spec: ModelSpec) -> int:
    """Params touched per token (MoE: top_k + shared experts only)."""
    if spec.moe is None:
        return param_count(spec, padded=False)
    m = spec.moe
    total = param_count(spec, padded=False)
    n_moe_layers = sum(1 for i in range(spec.num_layers) if spec.moe_layer(i))
    per_expert = _prod((spec.d_model, 2 * m.expert_ff)) + _prod((m.expert_ff, spec.d_model))
    # experts the parameter count holds less the top_k a token uses
    # (with a held share, the expected top_k * held / num_experts)
    held = m.num_held if m.held is not None else m.num_experts
    total -= n_moe_layers * (held - m.top_k * held_share(spec)) * per_expert
    return total


def held_share(spec: ModelSpec) -> float:
    """Share of a token's routed experts this chip computes, under
    uniform routing: held / num_experts with a held range, else 1."""
    m = spec.moe
    if m is None or m.held is None:
        return 1.0
    return m.num_held / m.num_experts


# ---------------------------------------------------------------------------
# FLOPs per layer (forward, per token, at context length S_ctx)
# ---------------------------------------------------------------------------

def _ctx(spec: ModelSpec, kind: str, s_ctx: int) -> int:
    if kind == "attn_local" and spec.sliding_window:
        return min(s_ctx, spec.sliding_window)
    return s_ctx


def attention_flops_per_token(spec: ModelSpec, s_ctx: int, cross_len: int = 0) -> float:
    """QKVO projections + scores + AV, per query token with context s_ctx."""
    d, q, kv = spec.d_model, spec.q_dim, spec.kv_dim
    f = 2 * d * q + 2 * 2 * d * kv + 2 * q * d          # q,k,v,o projections
    f += 2 * s_ctx * q + 2 * s_ctx * q                   # QK^T and AV
    f += 7 * spec.num_heads * s_ctx                      # softmax (exp,max,sum,div)
    if cross_len:
        f += 2 * d * q + 2 * q * d + 4 * cross_len * q + 7 * spec.num_heads * cross_len
    return f


def mlp_flops_per_token(spec: ModelSpec, d_ff: int = 0) -> float:
    d = spec.d_model
    ff = d_ff or spec.d_ff
    if ff == 0:
        return 0.0
    if spec.act in ("silu", "swiglu"):
        return 2 * d * 2 * ff + 2 * ff * d + 4 * ff      # gate/up, down, act*mul
    return 2 * d * ff + 2 * ff * d + 4 * ff


def moe_flops_per_token(spec: ModelSpec, dispatch: bool = False,
                        tokens_per_step: int = 1) -> float:
    """useful (top_k) flops; dispatch=True adds dense-dispatch overhead the
    capacity-based HLO actually executes (padded experts x capacity)."""
    m = spec.moe
    assert m is not None
    d = spec.d_model
    f = 2 * d * m.num_experts                            # router
    per_ff = lambda ff: 2 * d * 2 * ff + 2 * ff * d + 4 * ff
    if dispatch:
        # each padded expert processes capacity = top_k * cf * T / E tokens
        ratio = m.padded_experts / m.num_experts * m.capacity_factor
        f += m.top_k * ratio * per_ff(m.expert_ff)
        # dispatch/combine one-hot einsums: 2 * E * cap * d each
        f += 2 * 2 * m.top_k * m.capacity_factor * d
    else:
        f += m.top_k * held_share(spec) * per_ff(m.expert_ff)
    if m.num_shared_experts:
        sff = m.shared_ff or m.num_shared_experts * m.expert_ff
        f += per_ff(sff)
    return f


def conv_flops_per_token(spec: ModelSpec) -> float:
    """Gated short convolution: in/out projections, the B*h and C*y
    gates, and the depthwise kernel."""
    d = spec.d_model
    return 2 * d * 3 * d + 2 * d * d + 2 * d + 2 * spec.conv_width * d


def ffn_flops_per_token(spec: ModelSpec, layer_idx: int,
                        dispatch: bool = False) -> float:
    if spec.moe_layer(layer_idx):
        return moe_flops_per_token(spec, dispatch=dispatch)
    return mlp_flops_per_token(spec)


def ssm_flops_per_token(spec: ModelSpec) -> float:
    s = spec.ssm
    assert s is not None
    d = spec.d_model
    d_inner = s.expand * d
    nh = s.num_heads or d_inner // s.head_dim
    f = 2 * d * (2 * d_inner + 2 * s.state_dim + nh)     # in_proj
    f += 2 * s.conv_width * (d_inner + 2 * s.state_dim)  # depthwise conv
    # chunked selective scan: state update + output, plus intra-chunk term
    f += 2 * d_inner * s.state_dim * 2                   # h = a*h + B x ; y = C h
    f += 2 * d_inner * s.chunk                           # intra-chunk quadratic
    f += 2 * d_inner * d                                 # out_proj
    f += 10 * d_inner                                    # gates/norm epsilon terms
    return f


def mlstm_flops_per_token(spec: ModelSpec, s_ctx: int) -> float:
    x = spec.xlstm
    assert x is not None
    d = spec.d_model
    inner = int(x.proj_factor * d)
    qk = int(x.qk_dim_factor * inner)
    chunk = min(s_ctx, 256)
    f = 2 * d * 2 * inner                                # up
    f += 2 * inner * qk * 2 + 2 * inner * inner          # q,k,v
    f += 2 * inner * spec.num_heads * 2                  # gates
    f += 2 * chunk * (2 * qk + inner)                    # intra-chunk parallel part
    f += 2 * (qk // spec.num_heads) * inner              # state read/update (recurrent part)
    f += 2 * inner * d                                   # down
    return f


def slstm_flops_per_token(spec: ModelSpec) -> float:
    d = spec.d_model
    return 2 * d * 4 * d + 2 * d * 4 * d + 20 * d        # input + recurrent + gates


def layer_flops_per_token(spec: ModelSpec, kind: str, s_ctx: int,
                          layer_idx: int = 0, dispatch: bool = False) -> float:
    norm_f = 5 * spec.d_model
    if kind in ("attn", "attn_local", "attn_global"):
        f = attention_flops_per_token(
            spec, _ctx(spec, kind, s_ctx),
            cross_len=spec.encoder_seq if spec.cross_attention else 0)
        return f + ffn_flops_per_token(spec, layer_idx, dispatch) + 2 * norm_f
    if kind == "conv":
        return (conv_flops_per_token(spec)
                + ffn_flops_per_token(spec, layer_idx, dispatch) + 2 * norm_f)
    if kind == "enc_attn":
        return (attention_flops_per_token(spec, s_ctx)
                + mlp_flops_per_token(spec) + 2 * norm_f)
    if kind == "ssm":
        return ssm_flops_per_token(spec) + norm_f
    if kind == "mlstm":
        return mlstm_flops_per_token(spec, s_ctx) + norm_f
    if kind == "slstm":
        return slstm_flops_per_token(spec) + norm_f
    raise ValueError(kind)


def forward_flops_per_token(spec: ModelSpec, s_ctx: int, dispatch: bool = False) -> float:
    """Decoder-stack forward FLOPs per token at context length s_ctx.

    The paper's eq. 8 is the MHA special case of this function
    (see tests/test_analytical.py::test_eq8_special_case).
    """
    f = 0.0
    for i, kind in enumerate(spec.layer_kinds()):
        f += layer_flops_per_token(spec, kind, s_ctx, i, dispatch)
        if spec.ssm is not None and spec.attn_every and (i + 1) % spec.attn_every == 0:
            f += (attention_flops_per_token(spec, s_ctx)
                  + mlp_flops_per_token(spec) + 10 * spec.d_model)
    f += 2 * spec.d_model * spec.padded_vocab            # LM head
    f += 5 * spec.d_model                                # final norm
    return f


def encoder_flops(spec: ModelSpec) -> float:
    """Whisper-style encoder cost per sequence (fixed encoder_seq)."""
    if not spec.encoder_layers:
        return 0.0
    per_tok = (attention_flops_per_token(spec, spec.encoder_seq)
               + mlp_flops_per_token(spec) + 10 * spec.d_model)
    return per_tok * spec.encoder_seq * spec.encoder_layers


# ---------------------------------------------------------------------------
# Recurrent / cache state shapes per layer kind (for memory + serve engine)
# ---------------------------------------------------------------------------

def layer_state_shapes(spec: ModelSpec, kind: str, batch: int, max_seq: int) -> Dict[str, Shape]:
    if kind in ("attn", "attn_global", "enc_attn"):
        return {"k": (batch, max_seq, spec.num_kv_heads, spec.head_dim),
                "v": (batch, max_seq, spec.num_kv_heads, spec.head_dim)}
    if kind == "attn_local":
        w = min(max_seq, spec.sliding_window or max_seq)
        return {"k": (batch, w, spec.num_kv_heads, spec.head_dim),
                "v": (batch, w, spec.num_kv_heads, spec.head_dim)}
    if kind == "conv":
        return {"conv_state": (batch, spec.conv_width - 1, spec.d_model)}
    if kind == "ssm":
        s = spec.ssm
        d_inner = s.expand * spec.d_model
        nh = s.num_heads or d_inner // s.head_dim
        return {"ssm_state": (batch, nh, s.head_dim, s.state_dim),
                "conv_state": (batch, s.conv_width - 1, d_inner + 2 * s.state_dim)}
    if kind == "mlstm":
        x = spec.xlstm
        inner = int(x.proj_factor * spec.d_model)
        qk = int(x.qk_dim_factor * inner)
        nh = spec.num_heads
        return {"C": (batch, nh, qk // nh, inner // nh),
                "n": (batch, nh, qk // nh),
                "m": (batch, nh)}
    if kind == "slstm":
        d = spec.d_model
        return {"c": (batch, d), "h": (batch, d), "n_": (batch, d), "m_": (batch, d)}
    raise ValueError(kind)


def cache_bytes(spec: ModelSpec, batch: int, max_seq: int, bytes_per: float = 2.0) -> float:
    total = 0
    for kind in spec.layer_kinds():
        for shape in layer_state_shapes(spec, kind, batch, max_seq).values():
            total += _prod(shape)
    if spec.ssm is not None and spec.attn_every:
        n_shared = sum(1 for i in range(spec.num_layers) if (i + 1) % spec.attn_every == 0)
        total += n_shared * 2 * _prod((batch, max_seq, spec.num_kv_heads, spec.head_dim))
    if spec.cross_attention:
        total += spec.num_layers * 2 * _prod(
            (batch, spec.encoder_seq, spec.num_kv_heads, spec.head_dim))
    return total * bytes_per
