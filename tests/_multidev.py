"""Run a snippet in a subprocess with N forced host devices.

Multi-device tests must not set XLA_FLAGS in this process (smoke tests and
benches see 1 device, per the dry-run contract), so ring/collective tests
spawn a child with the flag set before jax imports.  The child is a
host-device rehearsal: it is pinned to the CPU, so it never contends with
this process for an accelerator.
"""
from __future__ import annotations

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout
