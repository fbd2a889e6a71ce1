"""Pallas TPU kernel: fused affinity-matrix + degree construction.

TPU adaptation of the paper's ``AffinityMatrix`` + ``RowSum`` CUDA kernels
(DESIGN.md §2). One HBM sweep produces both the affinity tile grid and the
degree vector D — the paper's separate RowSum kernel (an extra O(n²) read)
is fused into the tile epilogue (optimization O1a).

The kernel computes a general *stripe* A[row_offset:row_offset+R,
col_offset:col_offset+C] from a (R, m) row-feature slab and a (C, m)
col-feature slab (DESIGN.md §9): the single-device build is the square
self-stripe (xc = xn, offsets 0), and the sharded explicit path calls the
SAME kernel on its local row block against the gathered feature matrix.
The global offsets drive the diagonal mask and arrive as traced scalars in
SMEM, so one compiled program serves every shard position.

Graph-construction policies (DESIGN.md §11) are applied in-tile:

- adaptive local scaling (``scale_r``/``scale_c`` given, rbf): the tile
  transform becomes exp(-d² / (σᵢ σⱼ)) from the per-row scale columns —
  the (R,)/(C,) pass-1 statistics ride in as (·, 1) VMEM blocks.
- kNN truncation (``thr`` given): entries below the row's threshold
  τᵢ (the row's knn_k-th largest similarity, pass 1) fold into the same
  validity mask as the diagonal/padding — truncated entries are written as
  exact zeros and never reach the degree accumulation. The mask is free:
  it merges into the one ``jnp.where`` the kernel always executes.

The default dense fixed-bandwidth spec passes no extra operands and
compiles the exact PR-3 program (bitwise-pinned baseline).

Grid: (R/TM, C/TN); each step loads a (TM, m) row-slab and a (TN, m)
col-slab into VMEM, runs the (TM, m)·(m, TN) product on the MXU, applies
the similarity transform on the VPU, masks the diagonal / padding /
truncation, writes the A tile, and accumulates the partial row-sums into D.

Tile sizes default to 256×256 (512 KiB f32 per A tile — comfortably inside
a ~16 MiB VMEM budget together with the two input slabs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import MXU_PRECISION


def affinity_tile_transform(dot, sqr, sqc, *, kind: str,
                            inv_two_sigma_sq: float,
                            sclr=None, sclc=None):
    """The one similarity transform every GPIC kernel applies to an MXU
    tile: ``dot`` (TM, TN) row·col products, ``sqr``/``sqc`` the (TM, 1) /
    (TN, 1) squared norms (rbf only), ``sclr``/``sclc`` the (TM, 1) /
    (TN, 1) adaptive local scales (rbf + adaptive bandwidth only). Shared
    by the explicit build, both streaming kernels, and the row-top-k pass
    so all paths compute bitwise-identical tile values."""
    if kind == "cosine":
        return dot
    if kind == "cosine_shifted":
        return 0.5 * (1.0 + dot)
    if kind == "rbf":
        d2 = sqr + sqc.T - 2.0 * dot                     # (TM,1)+(1,TN)
        if sclr is not None:
            return jnp.exp(-jnp.maximum(d2, 0.0) / (sclr * sclc.T))
        return jnp.exp(-jnp.maximum(d2, 0.0) * inv_two_sigma_sq)
    raise ValueError(kind)


def tile_masks(i, j, off_ref, *, tm: int, tn: int, n_rows: int, n_cols: int):
    """(valid, ) in-tile mask: local row/col ids bound the padding, the
    global ids (local + the SMEM stripe offsets) locate the diagonal."""
    lrows = i * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    lcols = j * tn + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 1)
    grows = off_ref[0, 0] + lrows
    gcols = off_ref[0, 1] + lcols
    return (grows != gcols) & (lrows < n_rows) & (lcols < n_cols)


def unpack_policy_refs(rest, adaptive: bool, truncate: bool,
                       truncate_col: bool = False):
    """(sclr, sclc, thr, thr_c) refs from a kernel's flag-dependent operand
    tail. Shared by the affinity, streaming, and row-top-k kernels so the
    operand order is defined in exactly one place. ``truncate_col`` is the
    transpose-side mask (a column's OWN row threshold, applied while
    computing Aᵀ products for the reachability probe)."""
    sclr_ref = sclc_ref = thr_ref = thr_c_ref = None
    rest = list(rest)
    if adaptive:
        sclr_ref, sclc_ref = rest[0], rest[1]
        rest = rest[2:]
    if truncate:
        thr_ref = rest[0]
        rest = rest[1:]
    if truncate_col:
        thr_c_ref = rest[0]
        rest = rest[1:]
    assert not rest
    return sclr_ref, sclc_ref, thr_ref, thr_c_ref


def policy_specs_and_operands(scale_r, scale_c, thr, thr_c=None, *, tm, tn,
                              rp, cp, n_rows, n_cols):
    """(in_specs, operands) for the pass-1 policy columns — the ONE
    definition of their padding semantics, which the cross-engine bitwise
    discipline rests on: padded rows carry neutral values (scale 1,
    threshold +inf, so padding masks to exact zeros). ``thr_c`` is the
    (C,) column-side threshold of the transpose mask (padded +inf too)."""
    in_specs, operands = [], []
    if scale_r is not None:
        sclr = jnp.pad(scale_r.astype(jnp.float32), (0, rp - n_rows),
                       constant_values=1.0)[:, None]
        sclc = jnp.pad(scale_c.astype(jnp.float32), (0, cp - n_cols),
                       constant_values=1.0)[:, None]
        in_specs += [pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
                     pl.BlockSpec((tn, 1), lambda i, j: (j, 0))]
        operands += [sclr, sclc]
    if thr is not None:
        thr_p = jnp.pad(thr.astype(jnp.float32), (0, rp - n_rows),
                        constant_values=jnp.inf)[:, None]
        in_specs.append(pl.BlockSpec((tm, 1), lambda i, j: (i, 0)))
        operands.append(thr_p)
    if thr_c is not None:
        thr_cp = jnp.pad(thr_c.astype(jnp.float32), (0, cp - n_cols),
                         constant_values=jnp.inf)[:, None]
        in_specs.append(pl.BlockSpec((tn, 1), lambda i, j: (j, 0)))
        operands.append(thr_cp)
    return in_specs, operands


def _affinity_kernel(
    off_ref,                           # (1, 2) SMEM: global row/col offsets
    *refs,                             # inputs then outputs (flag-dependent)
    kind: str, n_rows: int, n_cols: int, tm: int, tn: int,
    inv_two_sigma_sq: float, adaptive: bool, truncate: bool,
):
    refs = list(refs)
    a_ref, d_ref = refs[-2], refs[-1]
    xr_ref, xc_ref, sqr_ref, sqc_ref = refs[:4]
    sclr_ref, sclc_ref, thr_ref, _ = unpack_policy_refs(
        refs[4:-2], adaptive, truncate)

    i = pl.program_id(0)
    j = pl.program_id(1)

    xr = xr_ref[...]                   # (TM, m) row slab
    xc = xc_ref[...]                   # (TN, m) col slab
    dot = jax.lax.dot_general(
        xr, xc, (((1,), (1,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                  # (TM, TN) on the MXU

    a = affinity_tile_transform(
        dot, sqr_ref[...] if kind == "rbf" else None,
        sqc_ref[...] if kind == "rbf" else None,
        kind=kind, inv_two_sigma_sq=inv_two_sigma_sq,
        sclr=sclr_ref[...] if adaptive else None,
        sclc=sclc_ref[...] if adaptive else None,
    )

    valid = tile_masks(i, j, off_ref, tm=tm, tn=tn,
                       n_rows=n_rows, n_cols=n_cols)
    if truncate:
        valid = valid & (a >= thr_ref[...])              # (TM, 1) broadcast
    a = jnp.where(valid, a, 0.0)

    a_ref[...] = a.astype(a_ref.dtype)

    # fused RowSum: accumulate partial degrees across the col-grid dimension
    partial = jnp.sum(a, axis=1, keepdims=True)          # (TM, 1)
    @pl.when(j == 0)
    def _init():
        d_ref[...] = partial.astype(d_ref.dtype)

    @pl.when(j != 0)
    def _acc():
        d_ref[...] += partial.astype(d_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "sigma", "tm", "tn", "interpret", "out_dtype"),
)
def affinity_and_degree(
    xn: jax.Array,
    xc: jax.Array | None = None,
    *,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = False,
    out_dtype=jnp.float32,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (A, D (R,)) for the affinity stripe of ``xn`` vs ``xc``.

    A comes at its padded storage shape (R, C rounded up to tm, tn
    multiples); pad rows and columns are masked to exact zeros, so the
    sweeps consume the array as it is and no (R, C) copy is ever sliced
    out of it.

    ``xc=None`` is the square self-affinity (the paper's build): A is
    (n, n) and D its row sums. With ``xc`` given, A is the
    ``A[row_offset:row_offset+R, col_offset:col_offset+C]`` stripe of the
    global matrix and D its stripe row sums; the offsets (traced scalars
    are fine — they ride in SMEM) locate the global diagonal to mask.

    For ``kind='rbf'`` pass the *raw* features and a bandwidth ``sigma``;
    for the cosine kinds pass L2-row-normalized features. ``scale_r`` /
    ``scale_c`` (R,)/(C,) switch rbf to adaptive local scaling
    exp(-d²/(σᵢσⱼ)); ``thr`` (R,) truncates each row below its threshold
    (both pass-1 statistics from kernels/row_topk.py, DESIGN.md §11).
    """
    if xc is None:
        xc = xn
    adaptive = scale_r is not None
    truncate = thr is not None
    if adaptive and (kind != "rbf" or scale_c is None):
        raise ValueError("adaptive scaling needs kind='rbf' and both "
                         "scale_r and scale_c")
    n_rows, m = xn.shape
    n_cols = xc.shape[0]
    rp = pl.cdiv(n_rows, tm) * tm
    cp = pl.cdiv(n_cols, tn) * tn
    xr32 = jnp.pad(xn.astype(jnp.float32), ((0, rp - n_rows), (0, 0)))
    xc32 = jnp.pad(xc.astype(jnp.float32), ((0, cp - n_cols), (0, 0)))
    sqr = jnp.sum(xr32 * xr32, axis=1, keepdims=True)    # (rp, 1)
    sqc = jnp.sum(xc32 * xc32, axis=1, keepdims=True)    # (cp, 1)
    off = jnp.array([row_offset, col_offset], jnp.int32).reshape(1, 2)

    grid = (rp // tm, cp // tn)
    kernel = functools.partial(
        _affinity_kernel,
        kind=kind, n_rows=n_rows, n_cols=n_cols, tm=tm, tn=tn,
        inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        adaptive=adaptive, truncate=truncate,
    )
    in_specs = [
        pl.BlockSpec((1, 2), lambda i, j: (0, 0),
                     memory_space=pltpu.SMEM),        # global offsets
        pl.BlockSpec((tm, m), lambda i, j: (i, 0)),   # row slab
        pl.BlockSpec((tn, m), lambda i, j: (j, 0)),   # col slab
        pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),   # row sq-norms
        pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),   # col sq-norms
    ]
    operands = [off, xr32, xc32, sqr, sqc]
    pol_specs, pol_ops = policy_specs_and_operands(
        scale_r, scale_c, thr, tm=tm, tn=tn, rp=rp, cp=cp,
        n_rows=n_rows, n_cols=n_cols)

    a, d = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + pol_specs,
        out_specs=[
            pl.BlockSpec((tm, tn), lambda i, j: (i, j)),  # A tile
            pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),   # degree (acc over j)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, cp), out_dtype),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        ],
        interpret=interpret,
        name="affinity_and_degree",
    )(*operands, *pol_ops)
    return a, d[:n_rows, 0]
