"""CLI smoke tests for ``launch/serve.py`` — the launcher had zero test
coverage, so flag/plumbing rot (a renamed SchedulerConfig field, a
backend-factory signature change) only surfaced when a human ran it.
Each test drives the real argparse entry point in a subprocess on a
tiny --local config and asserts on the printed report."""
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _serve(*argv, extra_env=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    if extra_env:
        env.update(extra_env)
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *argv],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


TINY = ("--local", "--layers", "2", "--width", "64", "--vocab", "256",
        "--batch", "2", "--prompt-len", "16", "--steps", "8")


def test_static_engine_cli():
    out = _serve(*TINY)
    assert "[serve] generated" in out


def test_paged_engine_cli_int4():
    out = _serve(*TINY, "--engine", "paged", "--cache-dtype", "int4")
    assert "paged engine (int4 pages" in out
    assert "usable pages" in out
    assert re.search(r"host per step: engine \d+\.\d+ ms, page table "
                     r"\d+\.\d+ ms; decode dispatch \d+\.\d+ ms per call",
                     out), out


def test_paged_engine_cli_spec_decode():
    out = _serve(*TINY, "--engine", "paged", "--spec-k", "4",
                 "--steps", "16")
    assert "spec_k=4" in out
    assert "spec decode:" in out and "drafts" in out


def test_paged_engine_cli_windowed_int4():
    """gemma3 reduced to its attn_local layers + --sliding-window:
    the paged engine must auto-switch to ring block tables (O(window)
    KV per slot) with int4 pages, and the run must actually wrap."""
    out = _serve("--arch", "gemma3-4b", *TINY, "--engine", "paged",
                 "--cache-dtype", "int4", "--sliding-window", "16",
                 "--steps", "32")
    assert "paged engine (int4 pages" in out
    assert "sliding window 16: ring tables 2 pages/slot" in out
    assert "pages recycled in place" in out


def test_paged_engine_cli_sharded():
    out = _serve(*TINY, "--engine", "paged", "--cache-dtype", "int4",
                 "--devices", "2",
                 extra_env={"XLA_FLAGS":
                            "--xla_force_host_platform_device_count=2"})
    assert "tp=2" in out
