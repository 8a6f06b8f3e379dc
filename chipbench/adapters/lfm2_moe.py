"""The program's side of an LFM2-MoE configuration: its ``ModelSpec``
(the registered ``lfm2-8b-a1b`` arch, holding the configuration's share
of the experts) and its parameters, built on the device from the seeded
weights (``chipbench.weights``) under the names the reference
(``reference/lfm2_moe.py``) regenerates.

The program stores each SwiGLU's gate and up projections side by side
(``mlp_wi``, ``experts_wi``) and RMSNorm gains as offsets, as the
reference draws them; the head is the tied embedding.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench.reference.lfm2_moe import Dims, dims
from chipbench.weights import canonical


def program_spec(cfg: dict):
    """The program's ``ModelSpec`` for ``cfg``: its registered arch with
    the configuration's sizes and layer pattern, the held share of the
    experts and an unpadded vocabulary.  What the sizes do not set —
    the routing, QK-norm, the tied head and the RMSNorm epsilon — is
    the arch's own, checked against the configuration."""
    from repro.configs import ARCHS
    dm = dims(cfg)
    arch = ARCHS[cfg["program"]["arch"]]
    spec = arch.with_(
        num_layers=dm.L, d_model=dm.d, num_heads=dm.H, num_kv_heads=dm.KV,
        head_dim=dm.D, d_ff=dm.F, vocab_size=dm.V, vocab_pad_multiple=1,
        rope_theta=dm.theta, conv_width=dm.K, dense_layers=dm.dense,
        layer_types=tuple("attn" if a else "conv" for a in dm.attn),
        moe=dataclasses.replace(arch.moe, num_experts=dm.E, top_k=dm.top_k,
                                expert_ff=dm.Fe, held=dm.held))
    m = spec.moe
    got = (m.score, m.expert_bias, m.norm_topk, m.scale,
           m.num_shared_experts, spec.qk_norm, spec.tie_embeddings,
           spec.act, spec.norm)
    want = ("sigmoid", True, dm.norm_topk, dm.scale, 0, True, dm.tied,
            "silu", "rmsnorm")
    if got != want:
        raise ValueError(f"{cfg['name']}: program arch "
                         f"{cfg['program']['arch']} routes or normalises "
                         f"otherwise: {got}, configuration {want}")
    if spec.norm_eps != dm.eps:
        raise ValueError(f"{cfg['name']}: the program's RMSNorm epsilon is "
                         f"{spec.norm_eps}, the configuration says {dm.eps}")
    return spec


def _layer(key, l, *, dm: Dims, attn: bool, moe: bool, dtype):
    """Layer ``l``'s parameters in the program's layout."""
    def w(name, shape):
        return canonical(key, name, l, shape,
                         shift=dm.shift(name)).astype(dtype)

    qd, kvd = dm.H * dm.D, dm.KV * dm.D
    p = {"norm1": w("op_norm", (dm.d,)), "norm2": w("ffn_norm", (dm.d,))}
    if attn:
        p.update(wq=w("q", (dm.d, qd)), wk=w("k", (dm.d, kvd)),
                 wv=w("v", (dm.d, kvd)), wo=w("o", (qd, dm.d)),
                 q_norm=w("q_norm", (dm.D,)), k_norm=w("k_norm", (dm.D,)))
    else:
        p.update(conv_in=w("conv_in", (dm.d, 3 * dm.d)),
                 conv_w=w("conv", (dm.K, dm.d)), conv_out=w("o", (dm.d, dm.d)))
    if moe:
        n = dm.n_held
        p.update(router_w=w("router", (dm.d, dm.E)),
                 expert_bias=w("expert_bias", (dm.E,)),
                 experts_wi=jnp.concatenate(
                     [w("gate", (n, dm.d, dm.Fe)), w("up", (n, dm.d, dm.Fe))],
                     -1),
                 experts_wo=w("down", (n, dm.Fe, dm.d)))
    else:
        p.update(mlp_wi=jnp.concatenate([w("gate", (dm.d, dm.F)),
                                         w("up", (dm.d, dm.F))], -1),
                 mlp_wo=w("down", (dm.F, dm.d)))
    return p


@functools.partial(jax.jit,
                   static_argnames=("dm", "attn", "moe", "n", "dtype"))
def _group(key, base, *, dm: Dims, attn: bool, moe: bool, n: int, dtype):
    return jax.lax.map(functools.partial(_layer, key, dm=dm, attn=attn,
                                         moe=moe, dtype=dtype),
                       base + jnp.arange(n))


@functools.partial(jax.jit, static_argnames=("dm", "dtype"))
def _global(key, *, dm: Dims, dtype):
    return {"embed": canonical(key, "embed", 0, (dm.V, dm.d),
                               shift=dm.shift("embed")).astype(dtype),
            "final_norm": canonical(key, "final_norm", 0,
                                    (dm.d,)).astype(dtype)}


def program_params(cfg: dict, spec, key):
    """Parameters in the program's pytree layout (checked against
    ``lm.init``'s shapes), in the configuration's weight dtype: one
    jitted build per group of like layers."""
    from repro.models import lm
    dtype = jnp.dtype(cfg["program"]["weight_dtype"])
    dm = dims(cfg)
    groups = [_group(key, g.base, dm=dm, attn=g.kind == "attn",
                     moe=spec.moe_layer(g.base), n=g.n, dtype=dtype)
              for g in lm.group_plan(spec)]
    params = {"global": _global(key, dm=dm, dtype=dtype), "groups": groups}
    want = jax.eval_shape(functools.partial(lm.init, spec=spec, dtype=dtype),
                          jax.random.PRNGKey(0))
    got_s = jax.tree_util.tree_structure(params)
    want_s = jax.tree_util.tree_structure(want)
    if got_s != want_s or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(want))):
        raise ValueError(f"{cfg['name']}: built parameters do not match "
                         "the program's layout")
    return params
