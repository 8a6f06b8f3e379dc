"""The traffic generator: the same seed gives the same requests; every
seed gets the same multiset of sizes, within the mix's clips; open-loop
arrivals fill the pre-roll and the window; closed-loop first requests
carry cut budgets."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic as T

MIXES = sorted((Path(__file__).resolve().parents[1] / "traffic")
               .glob("*.json"))


def _load(path):
    with open(path) as f:
        return json.load(f)


def _requests(mix, seed, seconds=20.0, n_closed=100):
    """The mix's scheduled requests through its arrival process, and in
    a closed loop those its completions send, up to ``n_closed``."""
    src = T.Traffic(mix, 1000, seed, seconds).arrivals()
    reqs = src.schedule()
    while src.closed and len(reqs) < n_closed:
        reqs += src.after(None)
    return reqs


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    mix = _load(path)
    a, b = _requests(mix, 2**33 + 7), _requests(mix, 2**33 + 7)
    assert [(r.max_new_tokens, r.arrival_s) for r in a] == \
        [(r.max_new_tokens, r.arrival_s) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_sizes_within_clips_and_alike_across_seeds(path):
    mix = _load(path)
    b = T.request_bounds(mix)
    lo, hi = b["prompt_min"], b["prompt_max"]
    skip = mix.get("clients", 0)          # closed: mid-flight first ones
    sizes = []
    for seed in (1, 2):
        reqs = _requests(mix, seed)
        assert all(lo <= len(r.prompt) <= hi for r in reqs[skip:])
        assert all(lo <= len(r.prompt) <= b["admit_max"] for r in reqs)
        assert all(len(r.prompt) + r.max_new_tokens
                   <= hi + mix["output"]["max"] for r in reqs)
        assert all(1 <= r.max_new_tokens <= mix["output"]["max"]
                   for r in reqs)
        sizes.append(((sorted(r.max_new_tokens for r in reqs[:skip]),
                       sum(len(r.prompt) for r in reqs[:skip])),
                      sorted(r.max_new_tokens for r in reqs[skip:])))
        assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
                   for r in reqs)
    if mix["loop"] == "open":
        assert sizes[0][1] == sizes[1][1]
    else:
        # every seed opens on the same budgets and the same total context
        assert sizes[0][0] == sizes[1][0]


def test_open_arrivals_fill_preroll_and_window():
    mix = {"loop": "open", "rate": 2.0, "preroll_s": 10,
           "prompt": [{"uniform": {}, "min": 4, "max": 8}],
           "output": {"uniform": {}, "min": 1, "max": 3}}
    reqs = T.Traffic(mix, 50, 3, 30.0).open_arrivals()
    t = np.array([r.arrival_s for r in reqs])
    assert len(reqs) == 80
    assert np.all(np.diff(t) > 0) and t[0] >= -10 and t[-1] < 30
    assert np.sum(t < 0) == 20
    # every seed's window holds the same sizes and the same gaps
    windows = []
    for seed in (3, 2**35 + 1):
        w = [r for r in T.Traffic(mix, 50, seed, 30.0).open_arrivals()
             if r.arrival_s >= 0]
        gaps = np.diff([0.0] + [r.arrival_s for r in w])
        windows.append((sorted(len(r.prompt) for r in w),
                        sorted(r.max_new_tokens for r in w),
                        np.round(np.sort(gaps), 9).tolist()))
    assert windows[0] == windows[1] and len(windows[0][0]) == 60


def test_closed_first_requests_are_caught_mid_flight():
    mix = {"loop": "closed", "clients": 8,
           "prompt": [{"uniform": {}, "min": 4, "max": 8}],
           "output": {"uniform": {}, "min": 100, "max": 100}}
    stream = T.Traffic(mix, 50, 5, 10.0).closed_streams()
    reqs = [next(stream) for _ in range(20)]
    first = sorted(r.max_new_tokens for r in reqs[:8])
    assert first == [7, 19, 32, 44, 57, 69, 82, 94]
    # what a first request has already generated is in its prompt
    assert sorted(len(r.prompt) + r.max_new_tokens for r in reqs[:8]) \
        == sorted(100 + T.quantiles(mix["prompt"][0], 8))
    assert all(r.max_new_tokens == 100 and len(r.prompt) <= 8
               for r in reqs[8:])
    assert T.request_bounds(mix)["admit_max"] == 8 + 99


def test_coprime_step():
    assert [T._coprime_step(n) for n in (1, 8, 32, 10)] == [1, 5, 21, 7]


def test_quantiles_follow_the_distribution():
    q = T.quantiles({"lognormal": {"median": 100, "sigma": 0.5},
                     "min": 1, "max": 10**6}, 1001)
    assert q[500] == 100 and q[0] < 100 < q[-1]
    z = T.zipf_choices(4, 1.0, 2500)
    counts = np.bincount(z)
    assert counts[0] == 1200 and list(counts) == sorted(counts)[::-1]
