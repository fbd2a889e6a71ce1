"""The work the algorithm needs, from its shapes, at the precision the
configuration states: what a kernel's roofline share is measured against.

These count what power iteration clustering has to do, whatever kernel
does it: a sweep of the explicit engine reads the stored A once and
multiplies it by the (n, r) block; the build computes A once and writes
it. Streaming work is bound by ``exp`` on the vector unit, which neither
published peak sees, so it has no roofline here. Counts are per chip: on
``chips`` chips each holds a stripe of n / chips rows.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
F32 = 4


def sweep_work(config: dict, n: int, r: int) -> tuple[float, float]:
    """(flops, bytes) of one explicit sweep U = D^-1 A V on one chip."""
    rows = n / config["chips"]
    a_bytes = DTYPE_BYTES[config["a_dtype"]]
    flops = 2.0 * rows * n * r
    nbytes = rows * n * a_bytes + (n * r + rows * r + rows) * F32
    return flops, nbytes


def build_work(config: dict, n: int) -> tuple[float, float]:
    """(flops, bytes) of building A and its degrees once on one chip: the
    m-wide distance products, and A written once at its stored dtype."""
    rows = n / config["chips"]
    m = config["m"]
    a_bytes = DTYPE_BYTES[config["a_dtype"]]
    flops = 2.0 * rows * n * m
    nbytes = rows * n * a_bytes + (rows + n) * m * F32 + rows * F32
    return flops, nbytes
