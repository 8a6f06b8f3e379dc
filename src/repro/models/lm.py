"""Generic LM assembly: every assigned architecture is an instance of this
module, driven entirely by ``ModelSpec`` + ``core.blocks`` declarations.

Layers are grouped into runs of identical kind and executed with
``lax.scan`` over stacked parameters (one compiled body per kind), which
keeps XLA compile time flat in depth — essential for the 512-device
dry-run of 48-layer models.

Entry points:
    init(rng, spec, dtype)                 -> params
    forward(params, spec, batch, ...)      -> (logits, aux)   train/teacher-forced
    prefill(params, spec, batch, ...)      -> (logits, cache) inference prefill
    decode_step(params, spec, cache, t)    -> (logits, cache) one token
    init_cache(spec, batch, max_seq, ...)  -> cache pytree
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import blocks
from repro.core.model_config import ModelSpec
from repro.kernels import page_layout
from repro.models import layers as L
from repro.models import recurrent as R
from repro.models.scan_util import scan as _scan
from repro.quant.qlinear import qdot, maybe_fake_quant

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Layer grouping
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Group:
    kind: str          # attn | attn_local | attn_global | conv | ssm | ssm_shared | mlstm | slstm
    base: int          # first layer index
    n: int             # number of layers


def group_plan(spec: ModelSpec) -> List[Group]:
    """Runs of consecutive layers with one parameter layout: the same
    kind and the same FFN (dense or routed experts)."""
    kinds = list(spec.layer_kinds())
    if spec.ssm is not None and spec.attn_every:
        kinds = ["ssm_shared" if (i + 1) % spec.attn_every == 0 else k
                 for i, k in enumerate(kinds)]
    groups: List[Group] = []
    i = 0
    while i < len(kinds):
        j = i
        while (j < len(kinds) and kinds[j] == kinds[i]
               and spec.moe_layer(j) == spec.moe_layer(i)):
            j += 1
        groups.append(Group(kinds[i], i, j - i))
        i = j
    return groups


def _base_kind(kind: str) -> str:
    return "ssm" if kind == "ssm_shared" else kind


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_param(key, name: str, shape, dtype, n_layers: int):
    if len(shape) == 0 or name.endswith("_b") or "bias" in name:
        return jnp.zeros(shape, dtype)
    if "norm" in name or name in ("ssm_gate_norm", "ml_onorm"):
        return jnp.zeros(shape, dtype)          # rmsnorm stored as (1 + scale)
    if name == "ssm_A_log":
        return jnp.log(jnp.linspace(1.0, 16.0, shape[0])).astype(dtype)
    if name == "ssm_D":
        return jnp.ones(shape, dtype)
    if name == "ssm_dt_bias":
        return jnp.zeros(shape, dtype)
    std = 0.02
    if name.endswith(("wo", "out_proj", "ml_down")):
        std = 0.02 / math.sqrt(max(1, 2 * n_layers))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init(rng, spec: ModelSpec, dtype=jnp.float32) -> Params:
    n_total = spec.num_layers + spec.encoder_layers
    params: Params = {"global": {}, "groups": []}
    keys = jax.random.split(rng, 4096)
    ki = iter(range(4096))

    for name, shape in blocks.global_param_shapes(spec).items():
        std_key = keys[next(ki)]
        params["global"][name] = _init_param(std_key, name, shape, dtype, n_total)

    for g in group_plan(spec):
        gp: Dict[str, jnp.ndarray] = {}
        shapes = blocks.layer_param_shapes(spec, _base_kind(g.kind), g.base)
        for name, shape in shapes.items():
            stacked = jnp.stack([
                _init_param(keys[next(ki)], name, shape, dtype, n_total)
                for _ in range(g.n)])
            gp[name] = stacked
        params["groups"].append(gp)

    if spec.ssm is not None and spec.attn_every:
        sb = {}
        for name, shape in blocks.shared_block_param_shapes(spec).items():
            sb[name] = _init_param(keys[next(ki)], name, shape, dtype, n_total)
        params["shared_block"] = sb

    if spec.encoder_layers:
        ep = {}
        shapes = blocks.layer_param_shapes(spec, "enc_attn")
        for name, shape in shapes.items():
            ep[name] = jnp.stack([
                _init_param(keys[next(ki)], name, shape, dtype, n_total)
                for _ in range(spec.encoder_layers)])
        params["encoder"] = ep

    return params


def param_count_actual(params) -> int:
    leaves = jax.tree_util.tree_leaves(params)
    return sum(x.size for x in leaves)


# ---------------------------------------------------------------------------
# Blocks with residual/norm wiring
# ---------------------------------------------------------------------------

def _layer_forward(spec: ModelSpec, kind: str, p: Params, x, positions,
                   enc_out, shared_p, impl: str, qat_cfg):
    """Full residual layer. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if qat_cfg is not None:
        p = {k: maybe_fake_quant(v, qat_cfg) if k.startswith(
            ("wq", "wk", "wv", "wo", "mlp", "experts", "shared", "cross"))
            else v for k, v in p.items()}
    base = _base_kind(kind)
    if base in ("attn", "attn_local", "attn_global", "enc_attn", "conv"):
        if base == "conv":
            h, _ = L.short_conv(spec, p, L.norm(spec, p, "norm1", x))
            x = x + h
        else:
            x = _attn_residual(spec, base, p, x, L.norm(spec, p, "norm1", x),
                               positions, enc_out, impl)
        y = L.norm(spec, p, "norm2", x)
        if "router_w" in p:
            h, aux = L.moe_block(spec, p, y)
        else:
            h = L.mlp_block(spec, p, y)
        x = x + h
    elif base == "ssm":
        x = x + R.mamba2_forward(spec, p, L.norm(spec, p, "norm1", x))
        if kind == "ssm_shared":
            x = _shared_block_forward(spec, shared_p, x, positions, impl)
    elif base == "mlstm":
        x = x + R.mlstm_forward(spec, p, L.norm(spec, p, "norm1", x))
    elif base == "slstm":
        x = x + R.slstm_forward(spec, p, L.norm(spec, p, "norm1", x))
    else:
        raise ValueError(kind)
    return x, aux


def _attn_residual(spec: ModelSpec, base: str, p: Params, x, xn, positions,
                   enc_out, impl: str):
    """x plus the attention of its normed ``xn`` (and cross-attention
    where the spec has it)."""
    x = x + L.attention_block(spec, p, xn, positions, kind=base, impl=impl)
    if spec.cross_attention and base != "enc_attn":
        x = x + L.cross_attention_block(
            spec, p, L.norm(spec, p, "norm_cross", x), enc_out)
    return x


def _ffn_residual(spec: ModelSpec, p: Params, y, valid=None,
                  expert_rows: Optional[list] = None):
    """y plus the serving FFN of its normed self (``layers.ffn``: the
    dropless routed-expert layer or the dense MLP).  A routed layer's
    rows per held expert are appended to ``expert_rows`` when given."""
    h, rows = L.ffn(spec, p, L.norm(spec, p, "norm2", y), valid)
    if rows is not None and expert_rows is not None:
        expert_rows.append(rows)
    return y + h


def _shared_block_forward(spec: ModelSpec, sp: Params, x, positions, impl):
    h = L.attention_block(spec, sp, L.norm(spec, sp, "norm1", x), positions,
                          kind="attn", impl=impl)
    x = x + h
    x = x + L.mlp_block(spec, sp, L.norm(spec, sp, "norm2", x))
    return x


# ---------------------------------------------------------------------------
# Forward (train / teacher-forced eval)
# ---------------------------------------------------------------------------

def _embed_inputs(params, spec: ModelSpec, batch) -> jnp.ndarray:
    tokens = batch["tokens"]
    x = jnp.take(params["global"]["embed"], tokens, axis=0)
    if spec.name.startswith("gemma"):
        x = x * math.sqrt(spec.d_model)
    if spec.vision_tokens:
        pe = batch["patch_embeds"]
        pe = L.rmsnorm(pe, params["global"]["vision_norm"])
        pe = qdot(pe, params["global"]["vision_proj"]).astype(x.dtype)
        nv = pe.shape[1]
        x = jnp.concatenate([pe, x[:, nv:]], axis=1)
    return x


def _encoder_forward(params, spec: ModelSpec, frames, impl, remat) -> jnp.ndarray:
    x = frames
    ep = params["encoder"]
    positions = jnp.arange(x.shape[1])[None]

    def body(carry, pslice):
        y, _ = _layer_forward(spec, "enc_attn", pslice, carry, positions,
                              None, None, impl, None)
        return y, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = _scan(body, x, ep)
    g = params["global"]
    if spec.norm == "layernorm":
        x = L.layernorm(x, g["enc_final_norm"], g["enc_final_norm_b"])
    else:
        x = L.rmsnorm(x, g["enc_final_norm"])
    return x


def _lm_head(params, spec: ModelSpec, x) -> jnp.ndarray:
    g = params["global"]
    x = L.norm(spec, g, "final_norm", x)
    if spec.tie_embeddings:
        emb = g["embed"]
        from repro.quant.qlinear import dequant_param
        return jnp.dot(x, dequant_param(emb).astype(x.dtype).T)
    return qdot(x, g["head"])


def forward(params, spec: ModelSpec, batch, *, impl: str = "auto",
            remat: bool = True, qat_cfg=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Teacher-forced forward over the full sequence -> (logits, aux_loss)."""
    x = _embed_inputs(params, spec, batch)
    B, S = x.shape[:2]
    positions = jnp.arange(S)[None]
    enc_out = None
    if spec.encoder_layers:
        enc_out = _encoder_forward(params, spec, batch["frames"], impl, remat)
    shared_p = params.get("shared_block")
    aux_total = jnp.zeros((), jnp.float32)

    for g, gp in zip(group_plan(spec), params["groups"]):
        def body(carry, pslice, _kind=g.kind):
            y, aux = _layer_forward(spec, _kind, pslice, carry, positions,
                                    enc_out, shared_p, impl, qat_cfg)
            return y, aux

        if remat:
            body = jax.checkpoint(body, policy=None)
        x, auxes = _scan(body, x, gp)
        aux_total = aux_total + jnp.sum(auxes)

    logits = _lm_head(params, spec, x)
    return logits, aux_total


# ---------------------------------------------------------------------------
# KV / recurrent cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Paged KV layout for the continuous-batching serve path.

    ``num_pages`` physical pages of ``page_size`` tokens each, shared by
    up to ``batch`` slots via per-slot block tables of
    ``pages_per_slot`` entries.  Page 0 is reserved as the null page
    that inactive slots' block tables point at.
    """
    num_pages: int
    page_size: int = 16
    pages_per_slot: int = 0          # 0 -> derive from max_seq

    def slots_pages(self, max_seq: int) -> int:
        return self.pages_per_slot or -(-max_seq // self.page_size)


def _norm_cache_dtype(dtype) -> str:
    """Canonical paged-cache dtype name for a string or jnp dtype."""
    if isinstance(dtype, str):
        if dtype not in ("fp32", "int8", "int4"):
            raise ValueError(f"cache dtype {dtype!r} (want fp32|int8|int4)")
        return dtype
    return "int8" if dtype == jnp.int8 else "fp32"


def paged_page_size(cache, head_dim: int) -> int:
    """Token capacity of one page of a cache storing heads of
    ``head_dim``.  Conv layers' entries hold no pages."""
    entry = next(e for g in cache["groups"] for e in g if "k_pages" in e)
    return _entry_page(entry, head_dim)


def lane_dense_layers(cache, head_dim: int) -> int:
    """Attention layers of a paged cache whose pools are stored
    lane-dense (``kernels.page_layout``)."""
    return sum(page_layout.is_lane_dense(e["k_pages"].shape, head_dim)
               for g in cache["groups"] for e in g if "k_pages" in e)


def _entry_page(entry, head_dim: int) -> int:
    """Token capacity of one page of a layer's pools — from the scale
    pool for quantized caches (the int4 value pool's token dim is
    nibble-packed to half; scale pools are LANE-MAJOR (P, KV, page),
    token dim last), else from the pool in either layout
    (``kernels.page_layout``)."""
    if "k_scale" in entry:
        return entry["k_scale"].shape[-1]
    return page_layout.pool_dims(entry["k_pages"].shape, head_dim)[0]


def _paged_quant(entry) -> str:
    """Quantization of one layer's page pools: none | int8 | int4 —
    int4 iff the value pool's token dim is half the scale pool's
    (lane-major scales keep the token dim LAST)."""
    if "k_scale" not in entry:
        return "none"
    return ("int4" if entry["k_pages"].shape[1] != entry["k_scale"].shape[-1]
            else "int8")


def init_paged_cache(spec: ModelSpec, batch: int, max_seq: int,
                     layout: PagedLayout, dtype=jnp.float32) -> Params:
    """Paged serve cache: per-layer page pools + per-slot block tables.

    Supported for attention stacks (attn / attn_local / attn_global)
    and for gated short-convolution layers ("conv"): those hold no
    pages (an empty entry in ``groups``) but a per-slot state,
    ``cache["conv"]``, one (batch, conv_width - 1, d) float32 array per
    conv layer in layer order — the last ``conv_width - 1`` conv inputs
    of each slot, written by the slot's own prefill and rolled by
    decode.  Specs with routed experts also carry ``expert_rows``
    (MoE layers, held experts) int32: the serve backend writes each
    call's rows per held expert there.  Other recurrent state
    (ssm/xlstm) and cross-attention have no paged representation yet.
    ``dtype`` is a jnp dtype or one of
    "fp32" | "int8" | "int4": quantized caches store int8 pools with
    per-token-per-head f32 scales (``k_scale``/``v_scale``); "int4"
    nibble-packs two adjacent tokens per byte along the pool's token
    dim ((P, page//2, KV, D), ``quant.quantize.pack_int4(axis=1)``
    layout) so a page moves ~8x fewer bytes than fp32.  Scale pools are
    LANE-MAJOR (P, KV, page) — token dim last, so one page's scales sit
    in a single (8, 128) f32 tile on real TPU instead of tile-padding a
    (page, KV, 1) block per token.  Float pools whose head_dim is under
    128 lanes are stored lane-dense, (P, KV, page·D/128, 128), where
    ``kernels.page_layout.lane_dense_rows`` allows it.  ``pos`` is a
    PER-SLOT length vector, not a scalar.
    """
    for kind in spec.layer_kinds():
        if _base_kind(kind) not in ("attn", "attn_local", "attn_global",
                                    "conv"):
            raise NotImplementedError(
                f"paged cache: unsupported layer kind {kind!r}")
    if spec.cross_attention or spec.encoder_layers:
        raise NotImplementedError("paged cache: cross-attention/encoder")
    cdt = _norm_cache_dtype(dtype)
    if cdt == "int4" and layout.page_size % 2:
        raise ValueError(f"int4 pages need an even page_size, "
                         f"got {layout.page_size}")
    pps = layout.slots_pages(max_seq)
    cache: Params = {
        "pos": jnp.zeros((batch,), jnp.int32),
        "block_tables": jnp.zeros((batch, pps), jnp.int32),
        "groups": [],
    }
    KV, D = spec.num_kv_heads, spec.head_dim
    tok = layout.page_size // 2 if cdt == "int4" else layout.page_size
    if cdt == "fp32":       # any float dtype passes through (bf16 pools ok)
        pool_dtype = jnp.float32 if isinstance(dtype, str) else dtype
    else:
        pool_dtype = jnp.int8
    pool = page_layout.pool_shape(layout.num_pages, tok, KV, D, pool_dtype)
    for g in group_plan(spec):
        if g.kind == "conv":
            cache["groups"].append([{} for _ in range(g.n)])
            continue
        layers = []
        for _ in range(g.n):
            entry: Dict[str, jnp.ndarray] = {
                "k_pages": jnp.zeros(pool, pool_dtype),
                "v_pages": jnp.zeros(pool, pool_dtype),
            }
            if cdt != "fp32":
                sshape = (layout.num_pages, KV, layout.page_size)
                entry["k_scale"] = jnp.zeros(sshape, jnp.float32)
                entry["v_scale"] = jnp.zeros(sshape, jnp.float32)
            layers.append(entry)
        cache["groups"].append(layers)
    if spec.num_conv_layers():
        cache["conv"] = [
            jnp.zeros((batch, spec.conv_width - 1, spec.d_model), jnp.float32)
            for _ in range(spec.num_conv_layers())]
    if spec.moe is not None:
        n_moe = sum(spec.moe_layer(i) for i in range(spec.num_layers))
        cache["expert_rows"] = jnp.zeros((n_moe, spec.moe.num_held),
                                         jnp.int32)
    return cache


def init_cache(spec: ModelSpec, batch: int, max_seq: int,
               dtype=jnp.float32, *,
               paged: Optional[PagedLayout] = None) -> Params:
    """Cache layout: one dict of state arrays PER LAYER (list per group).

    Per-layer buffers (instead of a stacked (n_layers, ...) array) keep
    decode updates strictly per-buffer: a stacked cache forces every
    layer's dynamic_update_slice to produce the full stacked array, which
    both defeats donation-aliasing analysis and inflates the HLO memory
    term ~n_layers-fold (§Perf iteration 3).

    With ``paged`` set, returns the block-table paged layout instead
    (see ``init_paged_cache``).
    """
    if paged is not None:
        return init_paged_cache(spec, batch, max_seq, paged, dtype)
    cache: Params = {"pos": jnp.zeros((), jnp.int32), "groups": []}
    for g in group_plan(spec):
        base = _base_kind(g.kind)
        shapes = blocks.layer_state_shapes(spec, "ssm" if base == "ssm" else base,
                                           batch, max_seq)
        layers = []
        for _ in range(g.n):
            entry: Dict[str, jnp.ndarray] = {}
            for name, shape in shapes.items():
                dt = (jnp.float32 if base in ("ssm", "mlstm", "slstm", "conv")
                      else dtype)
                fill = -jnp.inf if name in ("m", "m_") else 0.0
                entry[name] = jnp.full(shape, fill, dt)
            if g.kind == "ssm_shared":
                kv_shape = (batch, max_seq, spec.num_kv_heads, spec.head_dim)
                entry["shared_k"] = jnp.zeros(kv_shape, dtype)
                entry["shared_v"] = jnp.zeros(kv_shape, dtype)
            if spec.cross_attention and base.startswith("attn"):
                ck = (batch, spec.encoder_seq, spec.num_kv_heads, spec.head_dim)
                entry["cross_k"] = jnp.zeros(ck, dtype)
                entry["cross_v"] = jnp.zeros(ck, dtype)
            layers.append(entry)
        cache["groups"].append(layers)
    return cache


def _attn_prefill_kv(spec, p, xn, positions):
    B, S = xn.shape[:2]
    KV, D = spec.num_kv_heads, spec.head_dim
    k = qdot(xn, p["wk"]).reshape(B, S, KV, D)
    v = qdot(xn, p["wv"]).reshape(B, S, KV, D)
    if "k_norm" in p:
        k = L.rmsnorm(k, p["k_norm"], spec.norm_eps)
    k = L.rope(k, positions, spec.rope_theta)
    return k, v


# ---------------------------------------------------------------------------
# Prefill: forward + cache construction
# ---------------------------------------------------------------------------

def prefill(params, spec: ModelSpec, batch, *, max_seq: Optional[int] = None,
            impl: str = "auto", cache_dtype=None, true_len=None,
            expert_rows: Optional[list] = None) -> Tuple[jnp.ndarray, Params]:
    """Run the prompt, return (last-position logits, filled cache).

    ``true_len`` (traced scalar) supports bucket-padded prompts: tokens
    at positions >= true_len are padding — causal masking keeps them
    from influencing earlier positions, the returned logits come from
    position ``true_len - 1``, and ``cache["pos"]`` is set to
    ``true_len`` so decode overwrites the padding k/v.  One XLA compile
    per bucket length instead of one per prompt length.  Conv layers
    leave their state at ``true_len``, and routed-expert layers route
    padding rows nowhere; their rows per held expert are appended to
    ``expert_rows`` when given.
    """
    x = _embed_inputs(params, spec, batch)
    B, S = x.shape[:2]
    max_seq = max_seq or S
    dtype = cache_dtype or x.dtype
    positions = jnp.arange(S)[None]
    valid = (None if true_len is None else
             jnp.broadcast_to(positions < true_len, (B, S)).reshape(-1))
    enc_out = None
    if spec.encoder_layers:
        enc_out = _encoder_forward(params, spec, batch["frames"], impl, False)
    shared_p = params.get("shared_block")
    cache = init_cache(spec, B, max_seq, dtype)
    cache["pos"] = (jnp.array(S, jnp.int32) if true_len is None
                    else jnp.asarray(true_len, jnp.int32))

    for gi, (g, gp) in enumerate(zip(group_plan(spec), params["groups"])):
        base = _base_kind(g.kind)

        def body(carry, pslice, _kind=g.kind, _base=base):
            y0 = carry
            xn = L.norm(spec, pslice, "norm1", y0)
            out: Dict[str, jnp.ndarray] = {}
            rows: list = []
            if _base.startswith("attn"):
                k, v = _attn_prefill_kv(spec, pslice, xn, positions)
                y = _attn_residual(spec, _base, pslice, y0, xn, positions,
                                   enc_out, impl)
                y = _ffn_residual(spec, pslice, y, valid, rows)
                if _base == "attn_local" and spec.sliding_window and \
                        max_seq == spec.sliding_window:
                    # ring layout: slot j holds the unique p ≡ j (mod W)
                    # within the final window [S-W, S)
                    W = max_seq
                    if S >= W:
                        sel = (S - W) + jnp.mod(jnp.arange(W) - (S - W), W)
                        out["k"], out["v"] = k[:, sel], v[:, sel]
                    else:
                        pad = W - S
                        out["k"] = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        out["v"] = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                else:
                    pad = max_seq - S
                    out["k"] = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    out["v"] = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                if spec.cross_attention:
                    KV, D = spec.num_kv_heads, spec.head_dim
                    Se = enc_out.shape[1]
                    out["cross_k"] = qdot(enc_out, pslice["cross_wk"]).reshape(
                        B, Se, KV, D)
                    out["cross_v"] = qdot(enc_out, pslice["cross_wv"]).reshape(
                        B, Se, KV, D)
            elif _base == "conv":
                h, out["conv_state"] = L.short_conv(spec, pslice, xn,
                                                    true_len=true_len)
                y = _ffn_residual(spec, pslice, y0 + h, valid, rows)
            elif _base == "ssm":
                y, st = _mamba_prefill(spec, pslice, y0)
                out.update(st)
                if _kind == "ssm_shared":
                    xn2 = L.norm(spec, shared_p, "norm1", y)
                    k, v = _attn_prefill_kv(spec, shared_p, xn2, positions)
                    pad = max_seq - S
                    out["shared_k"] = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    out["shared_v"] = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    y = _shared_block_forward(spec, shared_p, y, positions, impl)
            elif _base == "mlstm":
                y, st = _mlstm_prefill(spec, pslice, y0)
                out.update(st)
            else:                                   # slstm
                y, st = _slstm_prefill(spec, pslice, y0)
                out.update(st)
            return y, (out, rows)

        x, (outs, rows) = _scan(body, x, gp)
        if expert_rows is not None:
            expert_rows.extend(r[li] for r in rows for li in range(g.n))
        for li in range(len(cache["groups"][gi])):
            entry = cache["groups"][gi][li]
            for k_, v_ in outs.items():
                if k_ in entry:
                    entry[k_] = v_[li].astype(entry[k_].dtype)
                else:
                    entry[k_] = v_[li]

    if true_len is None:
        x_last = x[:, -1:]
    else:
        x_last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(true_len, jnp.int32) - 1, 1, axis=1)
    logits = _lm_head(params, spec, x_last)
    return logits, cache


def _mamba_prefill(spec, p, x0):
    xn = L.norm(spec, p, "norm1", x0)
    y, st = R.mamba2_forward(spec, p, xn, return_state=True)
    return x0 + y, st


def _mlstm_prefill(spec, p, x0):
    xn = L.norm(spec, p, "norm1", x0)
    y, st = R.mlstm_forward(spec, p, xn, return_state=True)
    return x0 + y, st


def _slstm_prefill(spec, p, x0):
    xn = L.norm(spec, p, "norm1", x0)
    y, st = R.slstm_forward(spec, p, xn, return_state=True)
    return x0 + y, st


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _attn_decode(spec, p, x, pos, kv, *, kind, prefix="") -> Tuple[jnp.ndarray, Dict]:
    B = x.shape[0]
    H, KV, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    S = kv["k"].shape[1]
    q = qdot(x, p[prefix + "wq"]).reshape(B, 1, H, D)
    k = qdot(x, p[prefix + "wk"]).reshape(B, 1, KV, D)
    v = qdot(x, p[prefix + "wv"]).reshape(B, 1, KV, D)
    q, k = L.qk_norm(spec, p, q, k, prefix)
    posb = jnp.full((B, 1), pos, jnp.int32)
    q = L.rope(q, posb, spec.rope_theta)
    k = L.rope(k, posb, spec.rope_theta)
    ring = kind == "attn_local" and spec.sliding_window and S == spec.sliding_window
    slot = jnp.mod(pos, S) if ring else pos
    k_cache = jax.lax.dynamic_update_slice(kv["k"], k.astype(kv["k"].dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(kv["v"], v.astype(kv["v"].dtype),
                                           (0, slot, 0, 0))
    window = spec.sliding_window if kind == "attn_local" else 0
    o = L.decode_attention(q, k_cache, v_cache, pos, window=window, ring=bool(ring))
    out = qdot(o.reshape(B, 1, H * D), p[prefix + "wo"])
    return out, {"k": k_cache, "v": v_cache}


def _scatter_kv_rows(kv: Dict, name: str, rows: jnp.ndarray,
                     tgt_page: jnp.ndarray, tgt_off: jnp.ndarray) -> Dict:
    """Scatter float KV ``rows`` (N, KV, D) into one pool at token
    positions (``tgt_page``, ``tgt_off``) (N,), quantizing per the
    pool's layout.  Returns the updated pool entries ({name}_pages and,
    when quantized, {name}_scale — lane-major (P, KV, page), so a
    token's scales land at [page, :, off]).  Float pools take the rows
    in either layout (``kernels.page_layout.write_tokens``).

    int4 pools nibble-pack two adjacent tokens per byte, so a token
    write is a read-modify-write of its byte that must preserve the
    neighbour's nibble.  Writes run in two parity passes (even offsets,
    then odd) so the bytes touched within a pass are distinct — the
    only duplicate targets are rows routed to the null page (padding /
    inactive slots), whose content is never read.
    """
    from repro.quant.quantize import quantize_kv_int4, quantize_kv_int8
    pool = kv[name + "_pages"]
    quant = _paged_quant(kv)
    if quant == "none":
        return {name + "_pages": page_layout.write_tokens(
            pool, rows, tgt_page, tgt_off)}
    if quant == "int8":
        qrow, srow = quantize_kv_int8(rows)
        return {name + "_pages": pool.at[tgt_page, tgt_off].set(qrow),
                name + "_scale": kv[name + "_scale"].at[
                    tgt_page, :, tgt_off].set(srow[..., 0])}
    qrow, srow = quantize_kv_int4(rows)
    nib = qrow & jnp.int8(0x0F)
    byte = tgt_off // 2
    expand = (slice(None),) + (None,) * (rows.ndim - 1)
    for parity in (0, 1):
        m = (tgt_off % 2) == parity
        tp = jnp.where(m, tgt_page, 0)          # park non-parity rows on null
        cur = pool[tp, byte]
        upd = ((cur & jnp.int8(-16)) | nib if parity == 0
               else (cur & jnp.int8(0x0F)) | (nib << 4))
        pool = pool.at[tp, byte].set(jnp.where(m[expand], upd, cur))
    return {name + "_pages": pool,
            name + "_scale": kv[name + "_scale"].at[
                tgt_page, :, tgt_off].set(srow[..., 0])}


def _attn_decode_paged(spec, p, x, pos, kv, block_tables, *,
                       kind, ring=False, mesh=None,
                       shard_params=False) -> Tuple[jnp.ndarray, Dict]:
    """Paged-cache decode attention for one layer.

    ``pos`` is the per-slot context length vector (B,) — the new token's
    absolute position.  Writes the new k/v row into each slot's current
    page (pages are uniquely owned, so the batched scatter never
    collides; int4 pools read-modify-write the shared byte), then
    attends over the slot's block table via the paged attention op —
    quantized pools hand the kernel int8/packed-int4 pages plus scale
    pages, dequantized in-kernel.

    With ``mesh`` (a Mesh whose "model" axis divides the KV heads) the
    attention runs TENSOR-PARALLEL: the pools stay sharded over the
    KV-head dim and the paged attention op executes per shard under
    ``shard_map`` (heads are embarrassingly parallel — no collective
    inside the op).  ``shard_params=False`` (replicated weights, the
    odd-KV fallback contract) all-gathers the attention output so the
    wo projection runs replicated, keeping logits bitwise-identical to
    a single device.  ``shard_params=True`` means the weights live
    column/row-parallel (``ShardingRules.param_pspec``): q/k/v arrive
    head-sharded straight from column-parallel wq/wk/wv (shard_map's
    in_specs make that a no-op reshard), the attention output STAYS
    head-sharded, and row-parallel wo reduces with the megatron block's
    single psum — no replicated-weight gathers anywhere on the path.
    """
    from repro.kernels import ops as kops
    B = x.shape[0]
    H, KV, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    page = _entry_page(kv, D)
    q = qdot(x, p["wq"]).reshape(B, 1, H, D)
    k = qdot(x, p["wk"]).reshape(B, 1, KV, D)
    v = qdot(x, p["wv"]).reshape(B, 1, KV, D)
    q, k = L.qk_norm(spec, p, q, k)
    posb = pos[:, None]
    q = L.rope(q, posb, spec.rope_theta)
    k = L.rope(k, posb, spec.rope_theta)

    pidx = pos // page
    if ring:
        pidx = pidx % block_tables.shape[1]
    slot_page = block_tables[jnp.arange(B), pidx]
    off = pos % page
    new_kv = dict(kv)
    for name, row in (("k", k[:, 0]), ("v", v[:, 0])):
        new_kv.update(_scatter_kv_rows(kv, name, row, slot_page, off))

    window = spec.sliding_window if kind == "attn_local" else 0
    if mesh is not None:
        o = kops.paged_attention_sharded(
            mesh, q[:, 0], new_kv["k_pages"], new_kv["v_pages"],
            block_tables, pos + 1, window=window, ring=ring,
            k_scale=new_kv.get("k_scale"), v_scale=new_kv.get("v_scale"),
            gather_output=not shard_params)
    else:
        o = kops.paged_attention(
            q[:, 0], new_kv["k_pages"], new_kv["v_pages"], block_tables,
            pos + 1, window=window, ring=ring,
            k_scale=new_kv.get("k_scale"), v_scale=new_kv.get("v_scale"))
    out = qdot(o.reshape(B, 1, H * D), p["wo"])
    return out, new_kv


def _attn_decode_window_paged(spec, p, x, pos, lens, kv, block_tables, *,
                              kind, ring=False, mesh=None,
                              shard_params=False) -> Tuple[jnp.ndarray, Dict]:
    """Paged attention for a K-token DECODE WINDOW (speculative verify).

    ``x`` is (B, K, d): the last committed token plus K-1 drafted
    tokens per slot; ``pos`` (B,) the context length BEFORE the window
    (token j lands at absolute position ``pos + j``); ``lens`` (B,) how
    many window positions are real for each slot — rows past ``lens``
    are padding whose K/V scatter routes to the null page and whose
    logits the caller ignores (slots whose draft missed run a shorter
    window inside the same fixed-shape step).  All K rows scatter
    before the attention, so the multi-query paged op reads the window
    causally from the SAME pages sequential decode would (bitwise-equal
    values: per-token quantization, per-position rope), which is what
    makes draft verification exact.  ``mesh``/``shard_params`` run the
    attention tensor-parallel per KV-head shard exactly as the
    single-query path (head-sharded output into row-parallel wo when
    the weights are sharded, replicated gather otherwise).

    ``ring=True`` treats each block-table row as a RING of
    ``block_tables.shape[1]`` entries (absolute page q lives at entry
    ``q % R``) — the O(window) layout the windowed serve engine
    installs; the write target and the attention op both follow the
    ring mapping.
    """
    from repro.kernels import ops as kops
    B, K = x.shape[:2]
    H, KV, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    page = _entry_page(kv, D)
    q = qdot(x, p["wq"]).reshape(B, K, H, D)
    k = qdot(x, p["wk"]).reshape(B, K, KV, D)
    v = qdot(x, p["wv"]).reshape(B, K, KV, D)
    q, k = L.qk_norm(spec, p, q, k)
    posb = pos[:, None] + jnp.arange(K)[None]            # (B, K) absolute
    q = L.rope(q, posb, spec.rope_theta)
    k = L.rope(k, posb, spec.rope_theta)

    valid = jnp.arange(K)[None] < lens[:, None]          # (B, K)
    if ring:
        page_idx = (posb // page) % block_tables.shape[1]
    else:
        page_idx = jnp.minimum(posb // page, block_tables.shape[1] - 1)
    tgt_page = jnp.where(
        valid, block_tables[jnp.arange(B)[:, None], page_idx], 0)
    tgt_off = posb % page
    new_kv = dict(kv)
    for name, rows in (("k", k), ("v", v)):
        new_kv.update(_scatter_kv_rows(
            kv, name, rows.reshape(B * K, KV, D),
            tgt_page.reshape(-1), tgt_off.reshape(-1)))

    window = spec.sliding_window if kind == "attn_local" else 0
    if mesh is not None:
        o = kops.paged_attention_sharded(
            mesh, q, new_kv["k_pages"], new_kv["v_pages"],
            block_tables, pos + K, window=window, ring=ring,
            k_scale=new_kv.get("k_scale"), v_scale=new_kv.get("v_scale"),
            gather_output=not shard_params)
    else:
        o = kops.paged_attention(
            q, new_kv["k_pages"], new_kv["v_pages"], block_tables,
            pos + K, window=window, ring=ring,
            k_scale=new_kv.get("k_scale"), v_scale=new_kv.get("v_scale"))
    out = qdot(o.reshape(B, K, H * D), p["wo"])
    return out, new_kv


def _suffix_attn_paged(spec, p, xn, positions, kv, pref_pages, prefix_len,
                       tgt_page, tgt_off, *, kind, ring=False, mesh=None):
    """Attention for a prompt SUFFIX against cached prefix pages.

    The prefix-cache admission path: the first ``prefix_len`` context
    tokens already live in the page pool (shared read-only from the
    prefix store), so only the suffix runs projections.  Gathers the
    prefix K/V rows (dequantizing int8 pages, unpacking int4 nibbles;
    scale pools are lane-major (P, KV, page)), attends causally over
    [prefix ; suffix], and scatters the suffix K/V into the slot's own
    pages.  Padding needs no mask here: padded KEYS sit causally after
    every true query, and padded rows are routed to the null page by
    ``tgt_page`` (computed from ``true_len`` in ``prefill_paged``),
    whose content is never read.

    ``ring=True`` means ``pref_pages`` is a slot's RING block-table row
    (entry j holds the absolute page ``last - ((last - j) mod R)`` of
    the already-written context, ``last = (prefix_len - 1) // page``):
    the gathered rows get per-entry absolute key positions instead of
    ``arange``, never-written entries (negative position) are masked,
    and queries only ever need keys within ``spec.sliding_window`` —
    which the ring holds by construction.

    With ``mesh`` the pools are sharded over the KV-head dim; the
    gathered prefix rows are constrained back to replicated before the
    dense suffix attention (suffix prefill is a one-off per admission,
    so the all-gather is cheap next to the decode-loop savings).  The
    q/k/v/wo projections around it are partitioned by GSPMD from the
    committed weight shardings when the backend shards its params.
    """
    from repro.quant.quantize import unpack_int4
    B, S = xn.shape[:2]
    H, KV, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    quant = _paged_quant(kv)
    page = _entry_page(kv, D)
    npr = pref_pages.shape[0] * page
    q = qdot(xn, p["wq"]).reshape(B, S, H, D)
    k = qdot(xn, p["wk"]).reshape(B, S, KV, D)
    v = qdot(xn, p["wv"]).reshape(B, S, KV, D)
    q, k = L.qk_norm(spec, p, q, k)
    q = L.rope(q, positions, spec.rope_theta)
    k = L.rope(k, positions, spec.rope_theta)

    kp = page_layout.pages_from_pool(kv["k_pages"][pref_pages], D)
    vp = page_layout.pages_from_pool(kv["v_pages"][pref_pages], D)
    if quant == "int4":
        kp = unpack_int4(kp, axis=1)
        vp = unpack_int4(vp, axis=1)
    kp = kp.astype(jnp.float32)
    vp = vp.astype(jnp.float32)
    if quant != "none":
        kp = kp * jnp.moveaxis(kv["k_scale"][pref_pages], -1, -2)[..., None]
        vp = vp * jnp.moveaxis(kv["v_scale"][pref_pages], -1, -2)[..., None]
    if mesh is not None:
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        kp = jax.lax.with_sharding_constraint(kp, rep)
        vp = jax.lax.with_sharding_constraint(vp, rep)
    kp = kp.reshape(1, npr, KV, D)
    vp = vp.reshape(1, npr, KV, D)
    k_all = jnp.concatenate([kp.astype(k.dtype), k], axis=1)
    v_all = jnp.concatenate([vp.astype(v.dtype), v], axis=1)

    s = L._grouped_scores(q, k_all) / math.sqrt(D)       # (B,KV,G,S,T)
    if spec.attn_logit_softcap:
        s = jnp.tanh(s / spec.attn_logit_softcap) * spec.attn_logit_softcap
    i_abs = positions[0][:, None]                        # (S, 1)
    if ring:
        n_ent = pref_pages.shape[0]
        last = jnp.maximum(prefix_len - 1, 0) // page
        j = jnp.arange(n_ent)
        ap = last - jnp.mod(last - j, n_ent)             # abs page per entry
        pref_abs = (ap[:, None] * page
                    + jnp.arange(page)[None]).reshape(npr)
    else:
        pref_abs = jnp.arange(npr)
    k_abs = jnp.concatenate([pref_abs, positions[0]])
    is_suffix = jnp.concatenate([jnp.zeros((npr,), bool),
                                 jnp.ones((S,), bool)])
    valid = (k_abs[None, :] >= 0) & (k_abs[None, :] <= i_abs) & \
            ((k_abs[None, :] < prefix_len) | is_suffix[None, :])
    window = spec.sliding_window if kind == "attn_local" else 0
    if window:
        valid &= (i_abs - k_abs[None, :]) < window
    s = jnp.where(valid[None, None, None], s, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    o = L._grouped_out(prob, v_all).astype(q.dtype)
    out = qdot(o.reshape(B, S, H * D), p["wo"])

    new_kv = dict(kv)
    for name, rows in (("k", k[0]), ("v", v[0])):        # rows: (S, KV, D)
        new_kv.update(_scatter_kv_rows(kv, name, rows, tgt_page, tgt_off))
    return out, new_kv


def prefill_paged(params, spec: ModelSpec, tokens, cache, slot, bt_row,
                  prefix_len, true_len, *, n_prefix_pages: int,
                  ring=False, mesh=None,
                  expert_rows: Optional[list] = None
                  ) -> Tuple[jnp.ndarray, Params]:
    """Prefill a prompt SUFFIX directly into a paged cache slot whose
    first ``prefix_len`` tokens are already cached (prefix-cache hit).

    ``tokens`` is the (1, S) bucket-padded suffix; ``true_len`` (traced)
    its real length; ``prefix_len`` (traced) the cached context length;
    ``n_prefix_pages`` (static) how many block-table entries to gather
    for the prefix — rows past ``prefix_len`` are masked, so a
    power-of-two bucket keeps compile variants low.  Returns the logits
    of the last true suffix token and the updated cache with
    ``pos[slot] = prefix_len + true_len`` and the slot's block table set
    to ``bt_row``.  The FLOPs this skips relative to a full prefill are
    what ``core.analytical.mixed_iteration_flops(cached_prefix_tokens=)``
    accounts for.

    ``ring=True``: ``bt_row`` is a RING of ``bt_row.shape[0]`` entries.
    Suffix rows land at entry ``abs_page % R``; rows whose absolute page
    falls below the post-chunk horizon (``last_pg - R + 1``) route to
    the null page — only the last R pages of an over-long chunk are
    retained, which is exactly what the sliding window can ever read.
    The prefix gather follows the ring position mapping (see
    ``_suffix_attn_paged``), so chunked prefill and swap rejoins compose
    with windowed slots unchanged.

    Conv layers start from the slot's state in ``cache["conv"]`` when
    ``prefix_len`` > 0 (the slot's earlier chunks wrote it) and from
    zeros otherwise, and leave it at the suffix's true end.  A prefix
    that came from anywhere else (a prefix-cache hit, a swap-in) has no
    state here: the engine turns those paths off for such specs.
    Routed-expert layers route padding rows nowhere and append their
    rows per held expert to ``expert_rows`` when given.
    """
    page = paged_page_size(cache, spec.head_dim)
    S = tokens.shape[1]
    positions = prefix_len + jnp.arange(S)[None]         # (1, S) absolute
    pref_pages = bt_row[:n_prefix_pages]
    abs_pos = prefix_len + jnp.arange(S)
    apg = abs_pos // page
    if ring:
        R = bt_row.shape[0]
        last_pg = (prefix_len + true_len - 1) // page
        keep = (jnp.arange(S) < true_len) & (apg > last_pg - R)
        tgt_page = jnp.where(keep, bt_row[apg % R], 0)
    else:
        page_idx = jnp.minimum(apg, bt_row.shape[0] - 1)
        tgt_page = jnp.where(jnp.arange(S) < true_len, bt_row[page_idx], 0)
    tgt_off = abs_pos % page

    valid = jnp.arange(S) < true_len
    conv = list(cache.get("conv", ()))
    ci = 0
    x = jnp.take(params["global"]["embed"], tokens, axis=0)
    if spec.name.startswith("gemma"):
        x = x * math.sqrt(spec.d_model)
    new_groups = []
    for g, gp, cg in zip(group_plan(spec), params["groups"], cache["groups"]):
        base = _base_kind(g.kind)
        new_layers = []
        for li, cslice in enumerate(cg):
            pslice = jax.tree_util.tree_map(lambda v: v[li], gp)
            xn = L.norm(spec, pslice, "norm1", x)
            if base == "conv":
                start = jnp.where(prefix_len > 0, conv[ci][slot], 0.0)
                h, st = L.short_conv(spec, pslice, xn, start[None],
                                     true_len=true_len)
                conv[ci] = conv[ci].at[slot].set(st[0])
                ci += 1
                kv_new = cslice
            else:
                h, kv_new = _suffix_attn_paged(
                    spec, pslice, xn, positions, cslice, pref_pages,
                    prefix_len, tgt_page, tgt_off, kind=base, ring=ring,
                    mesh=mesh)
            x = _ffn_residual(spec, pslice, x + h, valid, expert_rows)
            new_layers.append(kv_new)
        new_groups.append(new_layers)

    x_last = jax.lax.dynamic_slice_in_dim(
        x, jnp.asarray(true_len, jnp.int32) - 1, 1, axis=1)
    logits = _lm_head(params, spec, x_last)
    new_cache = {
        **cache,
        "pos": cache["pos"].at[slot].set(
            jnp.asarray(prefix_len + true_len, jnp.int32)),
        "block_tables": cache["block_tables"].at[slot].set(bt_row),
        "groups": new_groups,
    }
    if conv:
        new_cache["conv"] = conv
    return logits, new_cache


def decode_step_paged(params, spec: ModelSpec, cache, tokens, *,
                      ring=False, mesh=None, shard_params=False,
                      valid=None, expert_rows: Optional[list] = None
                      ) -> Tuple[jnp.ndarray, Params]:
    """One decode step over a PAGED cache (per-slot positions).

    Same layer unroll as ``decode_step`` but attention reads/writes go
    through block tables, so slots at wildly different context lengths
    batch into one step without padding every slot to the longest —
    the continuous-batching scheduler's inner loop.  ``mesh`` enables
    the tensor-parallel attention path (pools sharded over KV heads,
    paged attention per shard via ``shard_map``); ``shard_params``
    declares that the weights themselves are column/row-parallel so the
    attention output stays head-sharded into row-parallel wo (GSPMD
    partitions the MLP / embed / lm-head matmuls from the committed
    param shardings on its own).  Conv layers roll every slot's state in
    ``cache["conv"]`` by the new token; routed-expert layers route
    slots where ``valid`` (B,) is False (idle) nowhere, and append
    their rows per held expert to ``expert_rows`` when given.
    """
    pos = cache["pos"]
    bt = cache["block_tables"]
    conv = list(cache.get("conv", ()))
    ci = 0
    x = jnp.take(params["global"]["embed"], tokens, axis=0)
    if spec.name.startswith("gemma"):
        x = x * math.sqrt(spec.d_model)
    new_groups = []
    for g, gp, cg in zip(group_plan(spec), params["groups"], cache["groups"]):
        base = _base_kind(g.kind)
        new_layers = []
        for li, cslice in enumerate(cg):
            pslice = jax.tree_util.tree_map(lambda v: v[li], gp)
            xn = L.norm(spec, pslice, "norm1", x)
            if base == "conv":
                h, conv[ci] = L.short_conv(spec, pslice, xn, conv[ci])
                ci += 1
                kv_new = cslice
            else:
                h, kv_new = _attn_decode_paged(spec, pslice, xn, pos, cslice,
                                               bt, kind=base, ring=ring,
                                               mesh=mesh,
                                               shard_params=shard_params)
            x = _ffn_residual(spec, pslice, x + h, valid, expert_rows)
            new_layers.append(kv_new)
        new_groups.append(new_layers)
    logits = _lm_head(params, spec, x)
    new_cache = {**cache, "pos": pos + 1, "groups": new_groups}
    if conv:
        new_cache["conv"] = conv
    return logits, new_cache


def decode_window_paged(params, spec: ModelSpec, cache, tokens, lens, *,
                        ring=False, mesh=None, shard_params=False,
                        expert_rows: Optional[list] = None
                        ) -> Tuple[jnp.ndarray, Params]:
    """K-token decode window over a paged cache (speculative verify).

    ``tokens`` is (B, K): the last committed token followed by K-1
    drafted tokens per slot; ``lens`` (B,) how many of the K are real
    (draft misses run shorter windows inside the same compiled shape).
    Returns logits for ALL K positions (B, K, vocab) — position j's
    logits are exactly what sequential ``decode_step_paged`` would
    produce after committing tokens[:, :j+1] — and the cache with every
    real window row scattered into the pool but ``pos`` UNCHANGED: the
    caller decides how many drafts were accepted and advances ``pos``
    by that many (the rollback that keeps rejected-draft KV outside the
    valid context; those rows are overwritten before they can ever be
    read).  K=1 with ``lens=1`` degenerates to ``decode_step_paged``
    minus the pos advance — the serve backend keeps K=1 on the original
    path so the non-speculative program is byte-identical.  Conv layers
    are refused: rejected drafts would leave their rows in the state,
    which keeps no history to roll back.
    """
    if spec.num_conv_layers():
        raise ValueError(f"{spec.name}: a verify window cannot roll back "
                         "per-slot conv state")
    pos = cache["pos"]
    valid = (jnp.arange(tokens.shape[1])[None] < lens[:, None]).reshape(-1)
    bt = cache["block_tables"]
    x = jnp.take(params["global"]["embed"], tokens, axis=0)
    if spec.name.startswith("gemma"):
        x = x * math.sqrt(spec.d_model)
    new_groups = []
    for g, gp, cg in zip(group_plan(spec), params["groups"], cache["groups"]):
        base = _base_kind(g.kind)
        new_layers = []
        for li, cslice in enumerate(cg):
            pslice = jax.tree_util.tree_map(lambda v: v[li], gp)
            xn = L.norm(spec, pslice, "norm1", x)
            h, kv_new = _attn_decode_window_paged(
                spec, pslice, xn, pos, lens, cslice, bt, kind=base,
                ring=ring, mesh=mesh, shard_params=shard_params)
            x = _ffn_residual(spec, pslice, x + h, valid, expert_rows)
            new_layers.append(kv_new)
        new_groups.append(new_layers)
    logits = _lm_head(params, spec, x)
    new_cache = {**cache, "groups": new_groups}
    return logits, new_cache


def decode_step(params, spec: ModelSpec, cache, tokens, *,
                ring=False, mesh=None, shard_params=False,
                valid=None, expert_rows: Optional[list] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """One decoding step for the whole batch. tokens: (B, 1) int32.

    Decode unrolls a python loop over layers with PER-LAYER cache buffers:
    stacked caches force each layer's update op to produce the whole
    stacked array (defeating donation aliasing and inflating the HLO
    memory term ~n_layers-fold — §Perf iterations 2-3).  Decode layer
    bodies are small, so the unrolled compile stays cheap.

    A paged cache (built with ``init_cache(..., paged=...)``) dispatches
    to ``decode_step_paged``.  Routed-expert layers route rows where
    ``valid`` (B,) is False nowhere, and append their rows per held
    expert to ``expert_rows`` when given.
    """
    if "block_tables" in cache:
        return decode_step_paged(params, spec, cache, tokens, ring=ring,
                                 mesh=mesh, shard_params=shard_params,
                                 valid=valid, expert_rows=expert_rows)
    if ring:
        raise ValueError("ring layout requires a paged cache")
    pos = cache["pos"]
    x = jnp.take(params["global"]["embed"], tokens, axis=0)
    if spec.name.startswith("gemma"):
        x = x * math.sqrt(spec.d_model)
    shared_p = params.get("shared_block")
    new_groups = []

    for g, gp, cg in zip(group_plan(spec), params["groups"], cache["groups"]):
        base = _base_kind(g.kind)

        def body(y0, pslice, cslice, _kind=g.kind, _base=base):
            xn = L.norm(spec, pslice, "norm1", y0)
            new_c = dict(cslice)
            if _base.startswith("attn"):
                h, kv_new = _attn_decode(spec, pslice, xn, pos, cslice, kind=_base)
                y = y0 + h
                new_c.update(kv_new)
                if spec.cross_attention:
                    xc = L.norm(spec, pslice, "norm_cross", y)
                    B, H, KV, D = y.shape[0], spec.num_heads, spec.num_kv_heads, spec.head_dim
                    qc = qdot(xc, pslice["cross_wq"]).reshape(B, 1, H, D)
                    oc = L.decode_attention(qc, cslice["cross_k"],
                                            cslice["cross_v"],
                                            cslice["cross_k"].shape[1] - 1)
                    y = y + qdot(oc.reshape(B, 1, H * D), pslice["cross_wo"])
                y = _ffn_residual(spec, pslice, y, valid, expert_rows)
            elif _base == "conv":
                h, new_c["conv_state"] = L.short_conv(
                    spec, pslice, xn, cslice["conv_state"])
                y = _ffn_residual(spec, pslice, y0 + h, valid, expert_rows)
            elif _base == "ssm":
                h, st = R.mamba2_decode_step(
                    spec, pslice, xn,
                    {"ssm_state": cslice["ssm_state"],
                     "conv_state": cslice["conv_state"]})
                y = y0 + h
                new_c.update(st)
                if _kind == "ssm_shared":
                    xn2 = L.norm(spec, shared_p, "norm1", y)
                    h2, kv_new = _attn_decode(
                        spec, shared_p, xn2, pos,
                        {"k": cslice["shared_k"], "v": cslice["shared_v"]},
                        kind="attn")
                    y = y + h2
                    new_c["shared_k"] = kv_new["k"]
                    new_c["shared_v"] = kv_new["v"]
                    y = y + L.mlp_block(spec, shared_p,
                                        L.norm(spec, shared_p, "norm2", y))
            elif _base == "mlstm":
                h, st = R.mlstm_decode_step(
                    spec, pslice, xn,
                    {"C": cslice["C"], "n": cslice["n"], "m": cslice["m"]})
                y = y0 + h
                new_c.update(st)
            else:
                h, st = R.slstm_decode_step(
                    spec, pslice, xn,
                    {"c": cslice["c"], "h": cslice["h"],
                     "n_": cslice["n_"], "m_": cslice["m_"]})
                y = y0 + h
                new_c.update(st)
            return y, {k: new_c[k].astype(cslice[k].dtype) for k in cslice}

        new_layers = []
        for li, cslice in enumerate(cg):
            pslice = jax.tree_util.tree_map(lambda v: v[li], gp)
            x, nc = body(x, pslice, cslice)
            new_layers.append(nc)
        new_groups.append(new_layers)

    logits = _lm_head(params, spec, x)
    new_cache = {**cache, "pos": pos + 1, "groups": new_groups}
    return logits, new_cache
