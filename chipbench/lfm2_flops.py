"""Operations and bytes of LFM2-MoE's served work, from the
configuration's published shapes (``reference.lfm2_moe.Dims``), the host
spans of a window and the program's routed-expert counters.

Model FLOPs count what the tokens need: every matmul of a processed
token outside the routed experts (conv and attention projections, the
dense FFNs, the router), attention over its context, the head for each
token that is sampled, and the expert matmuls of each row the held
experts computed (``moe_rows``); padding and idle slots count nothing.
"""
from __future__ import annotations

from chipbench.reference.lfm2_moe import Dims

#: Bytes of a served weight and of an activation row element (bfloat16).
WEIGHT_BYTES = ACT_BYTES = 2


def _n_attn(dm: Dims) -> int:
    return sum(dm.attn)


def _n_moe(dm: Dims) -> int:
    return dm.L - dm.dense


def token_matmul_flops(dm: Dims) -> float:
    """Matmul FLOPs of one token through every layer, experts aside."""
    qd, kvd = dm.H * dm.D, dm.KV * dm.D
    attn = 2.0 * dm.d * (qd + 2 * kvd) + 2.0 * qd * dm.d
    conv = 2.0 * dm.d * 3 * dm.d + 2.0 * dm.d * dm.d
    n_attn = _n_attn(dm)
    return (n_attn * attn + (dm.L - n_attn) * conv
            + dm.dense * 6.0 * dm.d * dm.F + _n_moe(dm) * 2.0 * dm.d * dm.E)


def expert_row_flops(dm: Dims) -> float:
    """FLOPs of one routed row through one expert (gate, up, down)."""
    return 6.0 * dm.d * dm.Fe


def prefill_flops(dm: Dims, tokens: int, prefix: int) -> float:
    """A prefill of ``tokens`` new tokens after ``prefix`` cached ones,
    sampling one token; experts aside."""
    keys = tokens * prefix + tokens * (tokens + 1) / 2
    return (tokens * token_matmul_flops(dm)
            + 4.0 * keys * dm.H * dm.D * _n_attn(dm) + 2.0 * dm.d * dm.V)


def decode_flops(dm: Dims, contexts) -> float:
    """One decode step for live slots whose contexts (new token
    included) are ``contexts``; experts aside."""
    n = len(contexts)
    return (n * (token_matmul_flops(dm) + 2.0 * dm.d * dm.V)
            + 4.0 * float(sum(contexts)) * dm.H * dm.D * _n_attn(dm))


def span_flops(dm: Dims, span) -> float:
    if span.name == "decode":
        return decode_flops(dm, span.contexts)
    return prefill_flops(dm, span.tokens, span.prefix)


def expert_matmul_bytes(dm: Dims, experts_hit: float, rows: float) -> float:
    """HBM bytes the two grouped matmuls of the routed layers need: the
    weights of each held expert a call hit (gate and up, then down),
    each routed row in and out of both (bfloat16 in, float32 out)."""
    weights = experts_hit * 3.0 * dm.d * dm.Fe * WEIGHT_BYTES
    first = rows * (dm.d * ACT_BYTES + 2 * dm.Fe * 4)
    second = rows * (dm.Fe * ACT_BYTES + dm.d * 4)
    return weights + first + second
