"""Helpers for multi-device tests: run a snippet in a subprocess with a
forced host-platform device count (the only way to get >1 CPU device
without polluting the parent process's jax state)."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


PREAMBLE = """\
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import sys
sys.path.insert(0, {src!r})
import jax
import jax.numpy as jnp
"""


def run_with_devices(snippet: str, n_devices: int = 8,
                     timeout: int = 600) -> str:
    """Run ``snippet`` under ``n_devices`` fake CPU devices; returns stdout.
    Raises CalledProcessError (with stderr attached) on failure."""
    code = PREAMBLE.format(n=n_devices, src=str(REPO / "src")) + snippet
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(
            f"subprocess failed\nstdout:\n{proc.stdout}\n"
            f"stderr:\n{proc.stderr[-4000:]}")
    return proc.stdout
