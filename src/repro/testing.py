"""Shared multi-device subprocess harness for CPU tests and benchmarks.

Host-platform virtual devices are fixed by XLA_FLAGS *before* jax imports,
so anything that wants an N-device CPU mesh must run in a fresh
interpreter while the parent process keeps its single-device view. This is
the ONE implementation of that recipe — tests/conftest.py and
benchmarks/bench_distributed.py both use it.

It is a CPU harness only: the child runs with ``JAX_PLATFORMS=cpu``, so
it can never reach for an accelerator that its parent may hold (a chip
belongs to one process at a time).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

#: repo src/ root (this file lives at src/repro/testing.py)
SRC_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_mesh_subprocess(code: str, *, devices: int = 8,
                        timeout: int = 1200) -> str:
    """Run ``code`` in a fresh interpreter with ``devices`` virtual CPU
    devices (XLA_FLAGS prelude prepended; PYTHONPATH gains src/;
    ``JAX_PLATFORMS=cpu``). Returns captured stdout; raises RuntimeError
    with the stderr tail on a non-zero exit."""
    prelude = (
        f'import os\n'
        f'os.environ["XLA_FLAGS"] = '
        f'"--xla_force_host_platform_device_count={devices}"\n'
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"mesh subprocess failed (exit {out.returncode}):\n"
            f"{out.stderr[-3000:]}")
    return out.stdout
