"""``correct`` at a small size on the CPU: the sound program passes, each
fault a one-chip serving cell can have makes it false, and the control
(the reference at float8 in the program's place) fails the limits.  The
seeded weights are the same bits in the program's parameters and in
the reference."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, drive, run
from chipbench import traffic as traffic_mod
from chipbench.adapters.dense_gqa import program_params, program_spec
from chipbench.cell import ROOT
from chipbench.tests.faults import FAULTS, LIMITS, tiny_cell
from chipbench.weights import base_key, canonical

SEED = 2**32 + 11


def _run(fault, config="tiny-gqa", mix="tiny-open"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-m", "chipbench.tests.faults",
                        fault, config, mix, str(SEED)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    assert p.stderr.strip().splitlines()[-1].startswith(
        "check requests_checked=")
    return out


@pytest.mark.parametrize("fault", ["none"] + sorted(FAULTS))
def test_faults_make_correct_false(fault):
    out = _run(fault)
    assert out["correct"] is (fault == "none"), out["checks"]
    assert out["checks"]["requests_checked"]["value"] >= 4


@pytest.mark.parametrize("fault", ["none"] + sorted(FAULTS))
def test_int4_multipliers_tied_head_faults(fault):
    out = _run(fault, "tiny-granite-int4", "tiny-closed")
    assert out["correct"] is (fault == "none"), out["checks"]


def _window(config, mix, seed, seconds=3.0):
    from repro.serve.backend import make_backend
    from repro.serve.scheduler import ContinuousBatchingEngine
    cell = tiny_cell(config, mix)
    spec = program_spec(cell.config)
    key = base_key(seed)
    params = program_params(cell.config, spec, key)
    sched = run.scheduler_config(cell, params, jax.devices()[0])
    inner = make_backend(params, spec, sched, devices=1)
    backend = drive.TimedBackend(inner, sched.max_slots)
    engine = ContinuousBatchingEngine(params, spec, sched, backend=backend)
    w = drive.run_window(engine, backend, traffic_mod.Traffic(
        cell.mix, spec.vocab_size, seed, seconds), seconds,
        drive.CompileLog())
    return cell, key, w


def test_control_fails_the_limits():
    cell, key, w = _window("tiny-gqa", "tiny-open", SEED)
    uids = check.sample(w, SEED)
    found = check.readings(cell, key, w, uids, control=True)
    ok, _ = check.judge(LIMITS["tiny-gqa"], found["program"], len(uids))
    assert ok, found
    ctrl_ok, _ = check.judge(LIMITS["tiny-gqa"], found["control"],
                             len(uids))
    assert not ctrl_ok, found


@pytest.mark.parametrize("config", ["tiny-gqa", "tiny-granite-int4"])
def test_program_weights_are_the_seeded_bits(config):
    cell = tiny_cell(config, "tiny-open")
    spec = program_spec(cell.config)
    key = base_key(SEED)
    p = program_params(cell.config, spec, key)
    g = p["groups"][0]
    c = cell.config
    d, F = c["hidden_size"], c["intermediate_size"]
    kvd = c["num_key_value_heads"] * d // c["num_attention_heads"]
    for layer in (0, 1):
        assert jnp.array_equal(g["wk"][layer],
                               canonical(key, "k", layer, (d, kvd)))
        assert jnp.array_equal(g["mlp_wi"][layer][:, F:],
                               canonical(key, "up", layer, (d, F)))
        assert jnp.array_equal(g["norm2"][layer],
                               canonical(key, "mlp_norm", layer, (d,)))
    emb = canonical(key, "embed", 0, (c["vocab_size"], d),
                    shift=c.get("weight_shift", {}).get("embed", 0)).astype(
        jnp.float32)
    if c.get("tie_word_embeddings"):
        want = (emb.T / c["logits_scaling"]).astype(jnp.bfloat16)
    else:
        want = canonical(key, "head", 0, (d, c["vocab_size"]))
    assert jnp.array_equal(p["global"]["head"], want)
    assert p["global"]["embed"].dtype == jnp.bfloat16


def test_weights_are_exact_and_wide_seeds_work():
    key = base_key(2**40 + 3)
    a = canonical(key, "q", 3, (8, 16), jnp.float32)
    b = jax.jit(lambda k: canonical(k, "q", 3, (8, 16), jnp.float32))(key)
    assert jnp.array_equal(a, b)
    ints = np.asarray(a) * 2**20
    assert np.array_equal(ints, np.round(ints))
    assert not jnp.array_equal(a, canonical(base_key(2**40 + 4), "q", 3,
                                            (8, 16), jnp.float32))
