"""Shared test helpers.

NOTE: XLA_FLAGS / device-count forcing is NEVER set
globally here -- single-device tests must see 1 device.  Multi-device tests
spawn subprocesses with their own XLA_FLAGS via run_with_devices().
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(code: str, n_devices: int, timeout: int = 900) -> str:
    """Run python code in a subprocess with n fake XLA host devices.
    Returns stdout; raises on nonzero exit."""
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n_devices}'\n"
        + textwrap.dedent(code)
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=timeout, env=env)
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed (exit {res.returncode}):\n--- stdout ---\n"
            f"{res.stdout}\n--- stderr ---\n{res.stderr[-4000:]}")
    return res.stdout
