"""``chipbench/run.py`` refuses to report: with no TPU it exits non-zero
and prints no result (it never falls back to the CPU), and so it does
in a directory that holds only ``BENCHMARK.json`` and the benchmark's
own files, without the program."""
import os
import shutil
import subprocess
import sys

from chipbench.cell import BENCH, ROOT

ARGS = ["chipbench/run.py", "--workload", "r1q15-chat-open", "--seed",
        "3000000019", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert '"correct"' not in line


def test_no_tpu_no_result():
    p = _run(ROOT)
    _no_result(p)
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    _no_result(_run(tmp_path))
    # past the look for a chip, the missing program stops it too
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.argv[0] = 'chipbench/run.py'; "
            "from chipbench import run; "
            f"sys.exit(run.main({ARGS[1:]!r}, require_tpu=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=240)
    _no_result(p)
    assert "No module named 'repro'" in p.stderr
