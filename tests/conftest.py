"""Shared test helpers.

The suite is a CPU suite: JAX_PLATFORMS defaults to cpu (so it stays one
on a machine with a TPU; `chip_smoke.py` is what runs there), and the
in-process suite runs with 8 fake CPU devices (the flag below is set
before any test module imports jax, which is what makes it stick): mesh
tests build real 2-8 way `jax.sharding.Mesh`es without subprocess
machinery, and everything else just sees extra idle devices — arrays
live on device 0 exactly as before. `run_subprocess` still exists for
tests that need a *different* device count or a cold jax runtime; it
overwrites XLA_FLAGS wholesale, so it is unaffected by the default."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_subprocess(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run `code` in a fresh python with n_devices fake CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    return proc.stdout


@pytest.fixture
def subproc():
    return run_subprocess
