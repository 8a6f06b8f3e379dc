"""Operations and bytes of the served work, from the configuration's
published shapes (``reference.dense_gqa.Dims``) and the host spans of a
window.  Model FLOPs count what the tokens need: every matmul of a
processed token, attention over its context, and the head for each
token that is sampled; padding and idle slots count nothing."""
from __future__ import annotations

import math

from chipbench.reference.dense_gqa import Dims

#: Bytes per cached value and whether per-(token, head) f32 scales ride
#: along, by the program's page dtype.
KV_VALUE_BYTES = {"fp32": (4.0, False), "int8": (1.0, True),
                  "int4": (0.5, True)}


def layer_matmul_params(dm: Dims) -> int:
    qd, kvd = dm.H * dm.D, dm.KV * dm.D
    return dm.d * qd + 2 * dm.d * kvd + qd * dm.d + 3 * dm.d * dm.F


def prefill_flops(dm: Dims, tokens: int, prefix: int) -> float:
    """A prefill of ``tokens`` new tokens after ``prefix`` cached ones,
    sampling one token."""
    matmul = 2.0 * tokens * dm.L * layer_matmul_params(dm)
    # token i (0-based, absolute prefix + i) attends prefix + i + 1 keys
    keys = tokens * prefix + tokens * (tokens + 1) / 2
    attn = 4.0 * keys * dm.H * dm.D * dm.L
    return matmul + attn + 2.0 * dm.d * dm.V


def decode_flops(dm: Dims, contexts) -> float:
    """One decode step for live slots whose contexts (new token
    included) are ``contexts``."""
    n = len(contexts)
    per_token = 2.0 * (dm.L * layer_matmul_params(dm) + dm.d * dm.V)
    return n * per_token + 4.0 * float(sum(contexts)) * dm.H * dm.D * dm.L


def span_flops(dm: Dims, span) -> float:
    if span.name == "decode":
        return decode_flops(dm, span.contexts)
    return prefill_flops(dm, span.tokens, span.prefix)


def paged_attention_bytes(dm: Dims, contexts, page: int, cache_dtype: str,
                          act_bytes: int = 2) -> float:
    """HBM bytes the paged-attention kernel needs in one decode step,
    over all layers: the live K and V pages (and their scale pages) of
    every live slot, plus its query and output rows."""
    per_value, scaled = KV_VALUE_BYTES[cache_dtype]
    total = 0.0
    for c in contexts:
        tokens = math.ceil(c / page) * page
        total += 2 * tokens * dm.KV * dm.D * per_value
        if scaled:
            total += 2 * tokens * dm.KV * 4
        total += 2 * dm.H * dm.D * act_bytes
    return total * dm.L
