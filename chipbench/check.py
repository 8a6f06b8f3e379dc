"""Decide ``correct``: the tokens the window served, held against the
plain reference.

After the window, a sample of the requests the window finished is
drawn from the seed: the one with the most served tokens, then others
until the sample holds ``MIN_TOKENS`` served tokens and ``MIN_REQUESTS``
requests (at most ``MAX_REQUESTS``).  The reference runs once over each
prompt with its served tokens (teacher forcing) and reads, at every
position that emitted a token, how far that token's logit lies below
the reference's best.  ``greedy_gap`` is the widest such gap, relative
to the largest |logit| of the request's rows; 0 means every served
token is the reference's argmax.  Served decoding is greedy, so a sound
program only breaks near-ties.

Beside it, ``argmax_miss_pct`` is the share of served tokens that are
not the reference's argmax.  A cell's limits file names the numbers
that decide ``correct`` for it.

The control (``control=True``) puts the reference in the program's
place at the next precision down, float8 e4m3 matmuls (the
configurations serve bfloat16), and reads the same numbers for the
token that it ranks first at each of those positions.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

MIN_TOKENS = 1024
MIN_REQUESTS = 4
MAX_REQUESTS = 16

#: The program's page dtype names, as the reference's stored-KV names.
KV_QUANT = {"fp32": "none", "int8": "int8", "int4": "int4"}


def sample(window, seed: int) -> List[int]:
    ok = [u for u, c in window.completions.items()
          if c.status == "ok" and len(c.tokens)]
    if not ok:
        return []
    ok.sort(key=lambda u: (-len(window.completions[u].tokens), u))
    rng = np.random.default_rng(seed)
    rest = list(rng.permutation(ok[1:]))
    out = [ok[0]]
    served = len(window.completions[ok[0]].tokens)
    while rest and len(out) < MAX_REQUESTS and (
            served < MIN_TOKENS or len(out) < MIN_REQUESTS):
        u = int(rest.pop())
        out.append(u)
        served += len(window.completions[u].tokens)
    return out


def _pad(a, n):
    out = np.zeros(n, np.int32)
    out[:len(a)] = a
    return out


def readings(cell, key, window, uids, *, control: bool = False) -> Dict:
    """The numbers ``correct`` compares, for the served tokens of
    ``uids`` and, with ``control``, for the control's first-ranked
    tokens at the same positions: ``greedy_gap``, the widest gap of any
    request, and ``argmax_miss_pct``, the share of tokens that are not
    the reference's argmax.  Returns {"program": {...}, "control":
    {...} or None, "tokens": tokens compared}."""
    from chipbench.cell import family
    ref = family("reference", cell.family)
    dm = ref.dims(cell.config)
    prog = cell.config["program"]
    kvq = KV_QUANT[prog["cache_dtype"]]
    # one padded shape per cell, so the reference compiles once
    T = -(-prog["max_seq"] // ref.Q_BLOCK) * ref.Q_BLOCK
    R = -(-cell.mix["output"]["max"] // ref.ROW_BLOCK) * ref.ROW_BLOCK
    sides = 2 if control else 1
    gap, miss, total = np.zeros(sides), np.zeros(sides), 0
    for uid in uids:
        req, c = window.requests[uid], window.completions[uid]
        toks = np.asarray(c.tokens, np.int32)
        n, P = len(toks), len(req.prompt)
        total += n
        if np.any(toks < 0) or np.any(toks >= dm.V):
            gap[0], miss[0] = np.inf, miss[0] + n
            continue
        seq = _pad(np.concatenate([req.prompt, toks[:-1]]), T)
        rows = _pad(np.arange(P - 1, P - 1 + n), R)
        args = (key, seq, np.int32(P), np.int32(window.matched.get(uid, 0)),
                rows)
        picks = [_pad(toks, R)]
        if control:
            *_, top = ref.row_stats(*args, np.zeros((1, R), np.int32),
                                    dm=dm, kv_quant=kvq, prec="fp8")
            picks.append(np.asarray(top))
        best, maxabs, picked, _ = ref.row_stats(
            *args, np.stack(picks), dm=dm, kv_quant=kvq, prec="f32")
        best, picked = np.asarray(best)[:n], np.asarray(picked)[:, :n]
        short = best[None] - picked
        gap = np.maximum(gap, short.max(axis=1)
                         / float(np.max(np.asarray(maxabs)[:n])))
        miss += (short > 0).sum(axis=1)
    out = [{"greedy_gap": float(gap[i]),
            "argmax_miss_pct": 100.0 * float(miss[i]) / max(total, 1)}
           for i in range(sides)]
    return {"program": out[0], "control": out[1] if control else None,
            "tokens": total}


def judge(limits: Dict[str, float], numbers: Dict[str, float],
          n_requests: int):
    """``correct`` and each compared number beside its limit: every
    number the cell's limits name must lie at or under its limit, and at
    least one finished request must have been checked."""
    checks = {name: {"value": numbers[name], "limit": lim}
              for name, lim in limits.items()}
    checks["requests_checked"] = {"value": n_requests, "limit": 1}
    ok = n_requests >= 1 and all(numbers[n] <= lim
                                 for n, lim in limits.items())
    return ok, checks
