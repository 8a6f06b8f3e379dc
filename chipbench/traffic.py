"""The one traffic generator: it reads a mix's parameters
(``traffic/<mix>.json``) and makes requests from the seed; the mix's
``loop`` names its arrival process, ``arrivals/<loop>.py``, found by
name, which decides when each request is sent (``open``: Poisson
arrivals at ``rate``; ``closed``: ``clients`` callers; each file says
its parameters).  This module holds what the processes share.

Every seed gets the same multiset of prompt lengths, output lengths,
inter-arrival gaps and shared-prefix choices, drawn as evenly spaced
quantiles of the mix's distributions (in an open loop, one set for the
pre-roll and one for the window); the seed shuffles their order and
draws the token ids.  So two seeds give the same amount of work and
differ only in which request comes when.

Mix parameters besides the process's own:

* ``preroll_s`` (optional): seconds of traffic served before the
  window opens; its requests are not the window's.
* ``prompt``: a list of segments, each a length distribution, whose
  lengths add up to a request's own (unshared) prompt tokens.
* ``shared_prefix`` (optional): ``count`` system prompts of ``tokens``
  tokens each, one of them before every prompt, picked with Zipf
  popularity of exponent ``zipf``.
* ``output``: the distribution of generated tokens per request.

A length distribution is ``{"lognormal": {"median", "sigma"}}`` or
``{"uniform": {}}``, with integer ``min`` and ``max`` that clip it.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

#: Requests per closed-loop client made ahead; the stream cycles through
#: them again (with fresh token ids) if a run uses more.
CLOSED_POOL_PER_CLIENT = 8


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    arrival_s: float = 0.0      # scheduled arrival (open loop)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles (at (i + 1/2)/n) of a clipped length
    distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    if "lognormal" in dist:
        p = dist["lognormal"]
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = p["median"] * np.exp(p["sigma"] * z)
    elif "uniform" in dist:
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        x = np.floor(x)
    else:
        raise ValueError(f"unknown length distribution {dist}")
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int, seconds: float) -> np.ndarray:
    """``n`` inter-arrival gaps: the quantiles of an exponential at
    ``rate``, scaled so that the n arrivals fill ``seconds``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate
    return g * (seconds * n / (n + 0.5)) / g.sum()


def zipf_choices(count: int, s: float, n: int) -> np.ndarray:
    """``n`` picks among ``count`` items with Zipf(``s``) popularity, as
    evenly spaced quantiles of the discrete distribution."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w / w.sum())
    u = (np.arange(n) + 0.5) / n
    return np.searchsorted(cdf, u).astype(np.int64)


class Traffic:
    """Requests of one mix for one seed."""

    def __init__(self, mix: dict, vocab: int, seed: int, seconds: float):
        self.mix, self.vocab, self.seconds = mix, vocab, seconds
        self.rng = np.random.default_rng(seed)
        pre = mix.get("shared_prefix")
        self.prefixes = ([self.rng.integers(0, vocab, size=pre["tokens"])
                          for _ in range(pre["count"])] if pre else [])
        self._uid = 0

    def arrivals(self):
        """The mix's arrival process (``arrivals/<loop>.py``) over these
        requests."""
        from chipbench.cell import arrivals
        return arrivals(self.mix["loop"]).Arrivals(self)

    def sizes(self, n: int) -> Dict[str, np.ndarray]:
        m, rng = self.mix, self.rng
        out = {f"seg{i}": rng.permutation(quantiles(seg, n))
               for i, seg in enumerate(m["prompt"])}
        out["new"] = rng.permutation(quantiles(m["output"], n))
        if self.prefixes:
            pre = m["shared_prefix"]
            out["prefix"] = rng.permutation(
                zipf_choices(pre["count"], pre["zipf"], n))
        return out

    def request(self, sizes, i: int, **kw) -> Request:
        parts = []
        if self.prefixes:
            parts.append(self.prefixes[int(sizes["prefix"][i])])
        for j in range(len(self.mix["prompt"])):
            parts.append(self.rng.integers(0, self.vocab,
                                           size=int(sizes[f"seg{j}"][i])))
        uid, self._uid = self._uid, self._uid + 1
        return Request(uid, np.concatenate(parts).astype(np.int32),
                       int(sizes["new"][i]), **kw)

    def open_arrivals(self) -> List[Request]:
        """Every request scheduled in [-preroll_s, seconds), in arrival
        order (the window opens at 0).  The pre-roll and the window each
        take their own quantiles of every size and of the gaps, so every
        seed's window holds the same requests and gaps in another order."""
        rate, pre = self.mix["rate"], float(self.mix.get("preroll_s", 0.0))
        out = []
        for start, span, least in ((-pre, pre, 0), (0.0, self.seconds, 1)):
            n = max(least, int(round(rate * span)))
            if n == 0:
                continue
            sizes = self.sizes(n)
            t = start + np.cumsum(self.rng.permutation(
                exp_gaps(rate, n, span)))
            out += [self.request(sizes, i, arrival_s=float(t[i]))
                    for i in range(n)]
        return out

    def closed_streams(self) -> Iterator[Request]:
        """An endless stream of closed-loop requests: first the callers'
        ``clients`` mid-flight requests, then fresh ones.  A first
        request has already generated part of its output, which is
        appended to its prompt, and its budget is the rest.

        The mid-flight requests take ``clients`` quantiles of each size;
        the one with the j-th shortest output has the share
        ((j * k) mod clients + 1/2) / clients of it left to generate
        (k about 0.618 clients and coprime to it, so the share does not
        follow the length), the rest is already in its prompt.  So every
        seed opens the window on the same budgets and the same total
        context."""
        c = self.mix["clients"]
        sizes = self.sizes(c)
        k = _coprime_step(c)
        rank = np.empty(c, np.int64)
        rank[np.argsort(sizes["new"], kind="stable")] = np.arange(c)
        for i in range(c):
            req = self.request(sizes, i)
            left = ((rank[i] * k) % c + 0.5) / c
            new = req.max_new_tokens
            req.max_new_tokens = max(1, int(math.ceil(left * new)))
            done = self.rng.integers(0, self.vocab,
                                     size=new - req.max_new_tokens)
            req.prompt = np.concatenate([req.prompt, done]).astype(np.int32)
            yield req
        while True:
            n = c * CLOSED_POOL_PER_CLIENT
            sizes = self.sizes(n)
            for i in range(n):
                yield self.request(sizes, i)


def _coprime_step(n: int) -> int:
    """The smallest k >= 0.618 n that shares no factor with n."""
    k = max(1, int(round(0.618 * n)))
    while math.gcd(k, n) != 1:
        k += 1
    return k


def request_bounds(mix: dict) -> Dict[str, int]:
    """Smallest and largest prompt, unshared suffix and output the mix
    can produce, and the longest prompt an admission can see
    (``admit_max``, as the arrival process says: a closed loop's
    mid-flight requests carry part of their output): the shapes a run
    has to warm up."""
    from chipbench.cell import arrivals
    pre = mix.get("shared_prefix")
    prefix = pre["tokens"] if pre else 0
    lo = sum(s["min"] for s in mix["prompt"])
    hi = sum(s["max"] for s in mix["prompt"])
    out = mix["output"]["max"]
    return {"prompt_min": prefix + lo, "prompt_max": prefix + hi,
            "admit_max": arrivals(mix["loop"]).admit_max(mix, prefix + hi),
            "suffix_min": lo, "suffix_max": hi, "prefix": prefix,
            "output_max": out}
