"""``chip_smoke.py`` on the CPU: it must refuse to report success
without a TPU, and its phases must pass every check but the compiled
kernel's presence when driven at a small size with the Pallas kernel in
interpret mode.  Also the helpers the entry points share: the
compile-cache placement rule and the device-sized KV budget, and the
stream parity measures the smoke run checks with."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core.analytical import MemoryBreakdown, kv_budget
from repro.kernels import ops
from repro.launch import compile_cache
from repro.serve.paged_cache import (ACTIVATION_HEADROOM_BYTES,
                                     device_kv_budget)
from repro.serve.parity import greedy_gap, token_match_fraction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ok_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                out.append(line)
        except (ValueError, AttributeError):
            pass
    return out


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode != 0
    assert not _ok_lines(p.stdout)
    assert "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("cache_dtype", ["fp32", "int4"])
def test_one_chip_phase_small_on_cpu(monkeypatch, cache_dtype):
    """The whole phase at a small TinyLlama-shaped size (GQA, shared
    prefix, three cold buckets) with the paged kernel forced into the
    engine: completions, prefix hits and both numeric checks pass; the
    one failure is the missing compiled kernel, which interpret mode
    cannot provide."""
    smoke = _load_smoke()
    monkeypatch.setattr(ops, "_resolve_paged_impl",
                        lambda impl: "ref" if impl == "ref" else "pallas")
    spec = smoke.ARCHS[smoke.ARCH].scaled_down(
        layers=2, width=64, vocab=256).with_(num_kv_heads=2)
    params = smoke.init_params(spec)
    reqs = smoke.workload(spec.vocab_size, n=6, prefix=32, short=(17, 32),
                          long=(33, 64), new=(4, 8))
    failures = smoke.one_chip_phase(
        params, spec, cache_dtype, smoke.CompileLog(), kv_budget_bytes=4e6,
        reqs=reqs, slots=3, max_seq=128, prefix=32)
    assert failures == [
        f"1chip {cache_dtype}: no Pallas kernel in the decode step"]


def test_four_chip_phase_small_on_cpu_devices():
    """tp=4 and dp=4 on four virtual CPU devices: tokens within band of
    one device at both matmul precisions, quarter-size weight shards,
    one replica per device, no router step faults; again only the
    compiled kernel is missing."""
    code = textwrap.dedent(f"""
        import importlib.util, jax
        from repro.kernels import ops
        ops._resolve_paged_impl = (
            lambda impl: "ref" if impl == "ref" else "pallas")
        s = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        smoke = importlib.util.module_from_spec(s)
        s.loader.exec_module(smoke)
        spec = smoke.ARCHS[smoke.ARCH].scaled_down(
            layers=2, width=64, vocab=32000).with_(num_kv_heads=4)
        failures = smoke.four_chip_phase(
            smoke.init_params(spec), spec, smoke.CompileLog(),
            kv_budget_bytes=4e7, slots=8, max_seq=512,
            reqs_fn=lambda: smoke.workload(
                spec.vocab_size, n=8, prefix=0, long=(129, 256),
                new=(4, 8)))
        print("FAILURES", failures)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr
    assert "FAILURES ['tp=4: no Pallas kernel in the decode step']" \
        in p.stdout, p.stdout


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_wins(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_defaults_into_checkout(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_device_kv_budget_from_reported_limit():
    params = {"w": np.zeros((1024, 256), np.float32)}
    got = device_kv_budget(params, _Device({"bytes_limit": 16 << 30}))
    assert got == kv_budget(16 << 30, MemoryBreakdown(
        weights=1 << 20, activations=ACTIVATION_HEADROOM_BYTES))
    # the CPU backend reports no memory, so callers keep their default
    assert device_kv_budget(params, _Device(None)) is None
    assert device_kv_budget(params, jax.devices("cpu")[0]) is None


def test_parity_measures():
    assert token_match_fraction([1, 2, 3, 4], [1, 2, 3, 4]) == 1.0
    assert token_match_fraction([1, 2, 9, 4], [1, 2, 3, 4]) == 0.5
    assert token_match_fraction([1, 2], [1, 2, 3, 4]) == 0.5
    logits = np.array([[4.0, 3.9, -1.0], [0.0, 2.0, 1.0]])
    assert greedy_gap([0, 1], logits) == 0.0
    # a near-tie broken the other way costs its score shortfall
    assert greedy_gap([1, 1], logits) == pytest.approx(0.1 / 4.0)
    assert greedy_gap([2, 1], logits) == pytest.approx(5.0 / 4.0)
    assert greedy_gap([], logits) == 0.0
