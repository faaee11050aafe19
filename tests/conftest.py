"""Shared test utilities.

NOTE: tests intentionally run with the default single CPU device (the
512-device override lives ONLY in launch/dryrun.py). Multi-device
behaviour is tested through subprocesses that set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before importing
jax — see ``run_multidevice``.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 480) -> str:
    """Run ``code`` in a fresh python with n virtual CPU devices."""
    prelude = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n_devices}'\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"multidevice subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def multidevice():
    return run_multidevice
