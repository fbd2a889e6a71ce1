"""Tile-size and matmul-precision policy of the GPIC Pallas kernels
(DESIGN.md §6).

The kernels are tiled over a (n/TM, n/TN) grid; the tile size trades
MXU utilization (bigger is better) against VMEM footprint and padding
waste (n is rounded up to lcm(TM, TN)). ``choose_tiles`` is a static,
shape-only heuristic — it sees only python ints, so it is safe to call
from inside a ``jax.jit`` region on traced arrays' ``.shape``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: contraction precision of every kernel ``dot_general`` (DESIGN.md §6):
#: f32 passes on the MXU. Every GPIC contraction is skinny — K = m for the
#: affinity tiles, N = r for the sweeps, Gram and k-means — so the extra
#: passes cost little against the tile's HBM or VPU work, while the rbf
#: distance ``|x|² + |c|² − 2x·c`` cancels and the 1e-5/n convergence test
#: both need f32 products.
MXU_PRECISION = jax.lax.Precision.HIGHEST


def f32_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at :data:`MXU_PRECISION` — the jnp products outside the
    kernels that results rest on: the references, the matrix-free sweep,
    Gram and k-means updates."""
    return jnp.matmul(a, b, precision=MXU_PRECISION)

#: candidate square tile edges, largest first (multiples of the 128-lane
#: MXU/VPU width; 8-sublane aligned for f32, 16 for bf16).
TILE_CANDIDATES = (512, 256, 128)

#: per-core VMEM budget the working set must fit in, with headroom for
#: Mosaic's double buffering (hence the factor 2 in the fit check).
VMEM_BUDGET_BYTES = 16 * 2**20


def round_up_to_lcm(n: int, tm: int, tn: int) -> int:
    """Smallest n' >= n divisible by both tm and tn (the kernel pad size)."""
    blk = math.lcm(tm, tn)
    return ((n + blk - 1) // blk) * blk


def tile_working_set_bytes(t: int, *, r: int = 1, m: int = 0,
                           a_bytes: int = 4) -> int:
    """HBM->VMEM bytes resident per grid step for a t x t tile.

    Counts the A tile (or, for the streaming kernel with feature width
    ``m`` > 0, the two feature slabs that regenerate it), the (t, r)
    V/U blocks in f32, and the (t, 1) degree block.
    """
    a_tile = t * t * a_bytes
    slabs = 2 * t * m * 4
    vecs = 2 * t * max(r, 1) * 4 + t * 4
    return a_tile + slabs + vecs


def choose_tiles(
    n: int,
    *,
    r: int = 1,
    m: int = 0,
    a_bytes: int = 4,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> tuple[int, int]:
    """Pick (tm, tn) for an n x n sweep with r power vectors.

    Policy (largest candidate wins):
      1. fit: 2x the per-step working set must fit in ``vmem_budget``
         (the 2x models Mosaic's input double buffering);
      2. waste: the lcm padding must not add more than max(n/4, 128)
         phantom rows — small problems get small tiles instead of
         mostly-padding grids.
    Falls back to the smallest candidate when nothing satisfies both.
    """
    for t in TILE_CANDIDATES:
        if 2 * tile_working_set_bytes(t, r=r, m=m, a_bytes=a_bytes) > vmem_budget:
            continue
        if round_up_to_lcm(n, t, t) - n > max(n // 4, 128):
            continue
        return t, t
    t = TILE_CANDIDATES[-1]
    return t, t
