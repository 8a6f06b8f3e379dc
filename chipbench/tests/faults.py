"""Faults planted under the timed path, for ``test_correct.py``: run as

    python -m chipbench.tests.faults <fault> <config> <mix> <seed>

it breaks the program's backend, then drives a whole benchmark run of
a small cell on whatever device JAX has, skipping the harness's look
for a chip.  The faults are the ones a one-chip serving cell can have
(there is no exchange between chips to leave out):

* ``state_unchanged``: the decode step returns the cache it was given;
* ``half_batch``: the decode step computes only every other slot and
  the others take whatever it emitted for them;
* ``token_altered``: one emitted token is changed where it is produced.
"""
import functools
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
#: Limits of the small configurations.  At the tests' seed a sound run
#: reads 0.0023 and 0.9% (tiny-gqa) or 0.021 and 0.5% (the int4 stack);
#: every fault reads at least 0.033 or 5.3% (tiny-gqa) and 0.41 and 19%
#: (the int4 stack).
LIMITS = {"tiny-gqa": {"greedy_gap": 0.02, "argmax_miss_pct": 4.0},
          "tiny-granite-int4": {"greedy_gap": 0.08, "argmax_miss_pct": 8.0}}


def tiny_cell(config: str, mix: str):
    from chipbench.cell import Cell, load_json, load_manifest
    m = load_manifest()
    name = "r1q15-chat-open"
    return Cell(name=name, chips=1,
                config=load_json(HERE / "data" / f"{config}.json"),
                mix=load_json(HERE / "data" / f"{mix}.json"),
                limits=dict(LIMITS[config]),
                end_to_end=[x for x in m["end_to_end"]
                            if name in x.get("workloads", [name])],
                per_layer=[])


def state_unchanged():
    import jax
    from repro.serve import backend as B
    orig = B._decode_fn

    @functools.partial(jax.jit, static_argnames=("spec", "mesh",
                                                 "shard_params", "ring"))
    def stuck(params, cache, tokens, active, *, spec, mesh=None,
              shard_params=False, ring=False):
        nxt, ok, _ = orig(params, cache, tokens, active, spec=spec,
                          mesh=mesh, shard_params=shard_params, ring=ring)
        return nxt, ok, cache

    B._decode_fn = stuck


def half_batch():
    from repro.serve import backend as B
    orig = B.SingleDeviceBackend.decode

    def half(self, tokens, active, lens=None):
        part = np.asarray(active).copy()
        part[1::2] = 0
        out, _, ok = orig(self, tokens, part, lens)
        return out, np.asarray(active, np.int32), ok

    B.SingleDeviceBackend.decode = half


def token_altered():
    from repro.serve import backend as B
    orig = B.SingleDeviceBackend.decode

    def altered(self, tokens, active, lens=None):
        out, n, ok = orig(self, tokens, active, lens)
        out = np.array(out)
        live = np.flatnonzero(np.asarray(active))[:1]
        out[live, 0] = (out[live, 0] + 1) % self.spec.vocab_size
        return out, n, ok

    B.SingleDeviceBackend.decode = altered


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  token_altered)}


def main(argv):
    fault, config, mix, seed = argv
    if fault != "none":
        FAULTS[fault]()
    from chipbench import run
    return run.main(["--workload", "r1q15-chat-open", "--seed", seed,
                     "--seconds", "3", "--trace", "0"],
                    cell=tiny_cell(config, mix), require_tpu=False)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
