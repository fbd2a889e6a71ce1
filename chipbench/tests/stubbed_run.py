"""Drive ``chipbench/run.py`` on the CPU at a small n: the look for a chip
is skipped, everything after it is the run as the chip sees it.

    rc, result = run_cell("exp2-smiley-45k-explicit", n=400)

Nothing here is a chip measurement: the device is the CPU, its memory
counter is stubbed, and Pallas runs in interpret mode.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import run as harness  # noqa: E402

CPU_PEAKS = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0, "hbm_bytes": 1.0}


def run_cell(workload: str, *, n: int, seconds: float = 0.5,
             trace: int = 0, seed: int = 3_000_000_017):
    """(exit code, the last stdout line as JSON or None, stderr)."""
    load = harness.load_cell

    def small(name):
        cell, config, traffic, e2e, layers = load(name)
        return cell, dict(config, n=n), traffic, e2e, layers

    import jax
    saved = {k: getattr(harness, k) for k in (
        "load_cell", "check_device", "peak_memory", "peaks_for",
        "setup_jax")}
    harness.load_cell = small
    harness.check_device = lambda jax, chips: jax.devices()[:chips]
    harness.peak_memory = lambda devices: 1
    harness.peaks_for = lambda kind: CPU_PEAKS
    # the tests leave JAX's persistent cache as they found it
    harness.setup_jax = lambda: jax
    out, err = io.StringIO(), io.StringIO()
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = harness.main(["--workload", workload, "--seed", str(seed),
                               "--seconds", str(seconds),
                               "--trace", str(trace)])
    finally:
        for k, v in saved.items():
            setattr(harness, k, v)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
