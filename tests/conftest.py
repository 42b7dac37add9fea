"""Shared fixtures.  NB: no XLA_FLAGS here — smoke tests and benches must
see the real single CPU device; multi-device tests spawn subprocesses that
set --xla_force_host_platform_device_count themselves."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

def run_with_devices(code: str, n_devices: int = 4, timeout: int = 300):
    """Run a python snippet in a subprocess with N fake host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if out.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
        )
    return out.stdout


@pytest.fixture
def rng():
    return np.random.RandomState(0)
