"""Run test code in a fresh interpreter over forced host devices.

jax fixes the device count when it first starts, so a test that needs
more devices than the one CPU runs its code in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.  src/ and
tests/ are on the child's path, so the code can import the package and
the shared ``tolerance`` helpers.
"""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_on_devices(code: str, devices: int = 8, timeout: int = 900) -> str:
    """Run ``code`` (dedented) over ``devices`` host devices, assert it
    exits 0, and return its stdout."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           os.path.join(ROOT, "tests")]))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout
