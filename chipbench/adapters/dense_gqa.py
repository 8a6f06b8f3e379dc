"""The program's side of a dense GQA configuration: its ``ModelSpec``
and its parameters, built on the device in one jitted call from the
seeded weights (``chipbench.weights``).

The program's layer has no scalar multipliers and no tied head, so the
published multipliers are folded into the weights handed to it: wq by
attention_multiplier * sqrt(head_dim) (the program scales scores by
1/sqrt(head_dim)), wo and the down projection by residual_multiplier,
the embedding by embedding_multiplier, and the head (the transposed
embedding when tied) by 1/logits_scaling.  With all multipliers 1 the
values pass through unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.dense_gqa import Dims, dims
from chipbench.weights import canonical

#: The program's RMSNorm epsilon, fixed in its code.
PROGRAM_EPS = 1e-6


def program_spec(cfg: dict):
    """The program's ``ModelSpec`` for ``cfg``: its registered arch with
    the configuration's sizes, an unpadded vocabulary and an untied head
    (the adapter builds the head)."""
    from repro.configs import ARCHS
    dm = dims(cfg)
    spec = ARCHS[cfg["program"]["arch"]].with_(
        num_layers=dm.L, d_model=dm.d, num_heads=dm.H, num_kv_heads=dm.KV,
        head_dim=dm.D, d_ff=dm.F, vocab_size=dm.V, vocab_pad_multiple=1,
        rope_theta=dm.theta, tie_embeddings=False)
    if (spec.family, spec.act, spec.norm, spec.moe, spec.sliding_window) != (
            "dense", "silu", "rmsnorm", None, 0):
        raise ValueError(f"{cfg['name']}: program arch "
                         f"{cfg['program']['arch']} is not a dense "
                         "SwiGLU RMSNorm stack with full attention")
    if dm.eps != PROGRAM_EPS:
        raise ValueError(f"{cfg['name']}: the program's RMSNorm epsilon is "
                         f"{PROGRAM_EPS}, the configuration says {dm.eps}")
    return spec


@functools.partial(jax.jit, static_argnames=("dm", "dtype"))
def _build(key, *, dm: Dims, dtype):
    f32 = jnp.float32
    qd, kvd = dm.H * dm.D, dm.KV * dm.D

    def w(name, layer, shape, mult=1.0):
        x = canonical(key, name, layer, shape,
                      shift=dm.shift(name)).astype(f32)
        return (x * mult).astype(dtype)

    def layer(l):
        return {
            "norm1": w("attn_norm", l, (dm.d,)),
            "norm2": w("mlp_norm", l, (dm.d,)),
            "wq": w("q", l, (dm.d, qd), dm.m_att * dm.D ** 0.5),
            "wk": w("k", l, (dm.d, kvd)),
            "wv": w("v", l, (dm.d, kvd)),
            "wo": w("o", l, (qd, dm.d), dm.m_res),
            "mlp_wi": jnp.concatenate([w("gate", l, (dm.d, dm.F)),
                                       w("up", l, (dm.d, dm.F))], -1),
            "mlp_wo": w("down", l, (dm.F, dm.d), dm.m_res),
        }

    groups = [jax.lax.map(layer, jnp.arange(dm.L))]
    if dm.tied:
        head = w("embed", 0, (dm.V, dm.d), 1.0 / dm.m_logit).T
    else:
        head = w("head", 0, (dm.d, dm.V), 1.0 / dm.m_logit)
    glob = {"embed": w("embed", 0, (dm.V, dm.d), dm.m_emb),
            "final_norm": w("final_norm", 0, (dm.d,)),
            "head": head}
    return {"global": glob, "groups": groups}


def program_params(cfg: dict, spec, key):
    """Parameters in the program's pytree layout (checked against
    ``lm.init``'s shapes), in the configuration's weight dtype."""
    from repro.models import lm
    dtype = jnp.dtype(cfg["program"]["weight_dtype"])
    params = _build(key, dm=dims(cfg), dtype=dtype)
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
