"""Pallas TPU kernel: streaming (A-free) affinity x power-vector fusion.

Computes U = (A @ V) / d WITHOUT ever materializing A (DESIGN.md §5): each
(i, j) grid step regenerates the (TM, TN) affinity tile on the MXU from the
(TM, m) row slab and (TN, m) col slab of the features — exactly the tile the
``affinity_and_degree`` kernel would have written to HBM — applies the
similarity transform and diagonal/padding masks on the VPU, multiplies the
tile by the (TN, r) slice of V, and accumulates the (TM, r) output block.

This is the paper's AffinityMatrix kernel fused INTO the power step: instead
of one O(n^2) write at build time plus an O(n^2) read per iteration, the
engine pays 2 n m reads per tile row/col pass and O(n^2 m / TILE) extra
flops — a bandwidth->compute trade that wins whenever A would spill HBM
(the paper's 36.5 GB matrix at n = 45k) or whenever m << TILE. Unlike the
jnp matrix-free path (cosine kinds only, DESIGN.md §2 O2) this works for
ALL affinity kinds including rbf, because the tile transform is elementwise.

Like the explicit build, the kernels compute a general *stripe*: row
features ``x`` (R, m) against col features ``xc`` (C, m) with global
``row_offset``/``col_offset`` locating the diagonal to mask (traced SMEM
scalars — one compiled program serves every shard position). The sharded
streaming ring (DESIGN.md §9) calls this once per ring stage with the
feature block that just arrived over the mesh, so each device's peak
memory stays O(n·m/P).

The graph-construction policies (DESIGN.md §11) stream exactly like the
explicit build: adaptive local scales ride in as (·, 1) blocks next to the
squared norms and swap the tile transform to exp(-d²/(σᵢσⱼ)); the per-row
truncation threshold merges into the validity mask, so truncated entries
contribute exact zeros to the product/degrees — the streamed sweep and the
explicit masked matrix stay bitwise-consistent at matching tile sizes.

Passing d = ones (or ``affinity_matmat(..., d=None)``) turns off the degree
normalization, which with V = ones((n, 1)) computes the degree vector itself
in one streamed sweep — the RowSum kernel without the matrix. ``d=None``
also leaves the output un-normalized for callers that accumulate partial
stripes (the ring) and divide once at the end.

Grid: (R/TM, C/TN) with rows/cols padded to TM/TN multiples independently;
accumulation over the col-grid dimension j, same revisit pattern as
kernels/power_step.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .affinity import (
    affinity_tile_transform,
    policy_specs_and_operands,
    tile_masks,
    unpack_policy_refs,
)
from .tuning import MXU_PRECISION


def _masked_tile(i, j, off_ref, xr_ref, xc_ref, sqr_ref, sqc_ref,
                 sclr_ref, sclc_ref, thr_ref, thr_c_ref=None,
                 *, kind, n_rows, n_cols, tm, tn, inv_two_sigma_sq,
                 adaptive, truncate, truncate_col=False):
    """Regenerate the masked affinity tile — the shared body of both
    streaming kernels (and their block-sparse variants, which pass the
    gathered col-block id as ``j``), matching kernels/affinity.py
    op-for-op. ``thr_c_ref`` applies the COLUMN's own row threshold
    (the transpose mask used by the Aᵀ reachability product — exact
    because the score transform is symmetric in its arguments)."""
    xr = xr_ref[...]                   # (TM, m) row slab
    xc = xc_ref[...]                   # (TN, m) col slab
    dot = jax.lax.dot_general(
        xr, xc, (((1,), (1,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                  # (TM, TN) affinity tile on the MXU

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
    if truncate_col:
        valid = valid & (a >= thr_c_ref[...].T)          # (1, TN) broadcast
    return jnp.where(valid, a, 0.0)


def _streaming_kernel(
    off_ref,                                          # (1, 2) SMEM offsets
    *refs,
    kind: str, n_rows: int, n_cols: int, tm: int, tn: int,
    inv_two_sigma_sq: float, nj: int, normalize: bool,
    adaptive: bool, truncate: bool, truncate_col: bool,
):
    refs = list(refs)
    u_ref = refs[-1]
    xr_ref, xc_ref, sqr_ref, sqc_ref, v_ref, d_ref = refs[:6]
    sclr_ref, sclc_ref, thr_ref, thr_c_ref = unpack_policy_refs(
        refs[6:-1], adaptive, truncate, truncate_col)

    i = pl.program_id(0)
    j = pl.program_id(1)

    a = _masked_tile(i, j, off_ref, xr_ref, xc_ref, sqr_ref, sqc_ref,
                     sclr_ref, sclc_ref, thr_ref, thr_c_ref,
                     kind=kind, n_rows=n_rows, n_cols=n_cols, tm=tm, tn=tn,
                     inv_two_sigma_sq=inv_two_sigma_sq,
                     adaptive=adaptive, truncate=truncate,
                     truncate_col=truncate_col)

    v = v_ref[...]                     # (TN, r) slice of V
    partial = jax.lax.dot_general(
        a, v, (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                  # (TM, r)

    @pl.when(j == 0)
    def _init():
        u_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        u_ref[...] += partial

    if normalize:
        @pl.when(j == nj - 1)
        def _norm():
            # floored divide, zero-degree safe as-is: d = 0 implies the
            # whole (nonnegative) A row is zero, so the accumulated u row
            # is an exact 0 and stays 0; NaN degrees propagate to the
            # loop's non-finite latch (DESIGN.md §12). The divide form is
            # pinned — masked-where variants perturb interpret-mode XLA
            # fusion and break local/sharded trajectory parity (the
            # kernels/ops.py::_tiles discipline). Padding rows carry
            # d = 1.0.
            d = d_ref[...]                 # (TM, 1)
            u_ref[...] = u_ref[...] / jnp.maximum(d, 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "sigma", "tm", "tn", "interpret"),
)
def affinity_matmat(
    x: jax.Array,
    v: jax.Array,
    d: jax.Array | None = None,
    xc: jax.Array | None = None,
    *,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = False,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
    thr_c: jax.Array | None = None,
) -> jax.Array:
    """U = (A @ V) / d with A regenerated tile-by-tile from features.

    Shapes: x (R, m) row features, xc (C, m) col features (None — the
    square self-stripe xc = x), v (C, r), d (R,) or None (no
    normalization); returns (R, r) f32. The offsets locate the stripe in
    the global matrix for the diagonal mask. For the cosine kinds pass
    L2-row-normalized features; for ``rbf`` pass raw features plus the
    bandwidth ``sigma``. ``scale_r``/``scale_c`` (R,)/(C,) switch rbf to
    adaptive local scaling; ``thr`` (R,) truncates rows below their pass-1
    threshold (DESIGN.md §11). ``thr_c`` (C,) instead applies each COLUMN's
    own threshold — Aᵀ[stripe] @ V for the symmetrized reachability probe
    (score symmetry makes the column-side mask the exact transpose
    pattern). No (R, C) array is ever allocated — peak memory is
    O((R + C)·m + (R + C)·r).
    """
    if xc is None:
        xc = x
    adaptive = scale_r is not None
    truncate = thr is not None
    truncate_col = thr_c is not None
    if adaptive and (kind != "rbf" or scale_c is None):
        raise ValueError("adaptive scaling needs kind='rbf' and both "
                         "scale_r and scale_c")
    n_rows, m = x.shape
    n_cols = xc.shape[0]
    r = v.shape[1]
    rp = pl.cdiv(n_rows, tm) * tm
    cp = pl.cdiv(n_cols, tn) * tn
    normalize = d is not None
    if d is None:
        d = jnp.ones((n_rows,), jnp.float32)
    xr32 = jnp.pad(x.astype(jnp.float32), ((0, rp - n_rows), (0, 0)))
    xc32 = jnp.pad(xc.astype(jnp.float32), ((0, cp - n_cols), (0, 0)))
    vp = jnp.pad(v.astype(jnp.float32), ((0, cp - n_cols), (0, 0)))
    dp = jnp.pad(d.astype(jnp.float32), (0, rp - n_rows), constant_values=1.0)
    sqr = jnp.sum(xr32 * xr32, axis=1, keepdims=True)    # (rp, 1)
    sqc = jnp.sum(xc32 * xc32, axis=1, keepdims=True)    # (cp, 1)
    off = jnp.array([row_offset, col_offset], jnp.int32).reshape(1, 2)

    grid = (rp // tm, cp // tn)
    kernel = functools.partial(
        _streaming_kernel,
        kind=kind, n_rows=n_rows, n_cols=n_cols, tm=tm, tn=tn,
        inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        nj=grid[1], normalize=normalize,
        adaptive=adaptive, truncate=truncate, truncate_col=truncate_col,
    )
    in_specs = [
        pl.BlockSpec((1, 2), lambda i, j: (0, 0),
                     memory_space=pltpu.SMEM),        # global offsets
        pl.BlockSpec((tm, m), lambda i, j: (i, 0)),   # row slab
        pl.BlockSpec((tn, m), lambda i, j: (j, 0)),   # col slab
        pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),   # row sq-norms
        pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),   # col sq-norms
        pl.BlockSpec((tn, r), lambda i, j: (j, 0)),   # V slice
        pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),   # degree
    ]
    operands = [off, xr32, xc32, sqr, sqc, vp, dp[:, None]]
    pol_specs, pol_ops = policy_specs_and_operands(
        scale_r, scale_c, thr, thr_c, tm=tm, tn=tn, rp=rp, cp=cp,
        n_rows=n_rows, n_cols=n_cols)
    u = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + pol_specs,
        out_specs=pl.BlockSpec((tm, r), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, r), jnp.float32),
        interpret=interpret,
        name="affinity_matmat",
    )(*operands, *pol_ops)
    return u[:n_rows]


def _streaming_degree_kernel(
    off_ref,
    *refs,
    kind: str, n_rows: int, n_cols: int, tm: int, tn: int,
    inv_two_sigma_sq: float, adaptive: bool, truncate: bool,
):
    refs = list(refs)
    d_ref = refs[-1]
    xr_ref, xc_ref, sqr_ref, sqc_ref = refs[:4]
    sclr_ref, sclc_ref, thr_ref, _ = unpack_policy_refs(
        refs[4:-1], adaptive, truncate)

    i = pl.program_id(0)
    j = pl.program_id(1)

    a = _masked_tile(i, j, off_ref, xr_ref, xc_ref, sqr_ref, sqc_ref,
                     sclr_ref, sclc_ref, thr_ref,
                     kind=kind, n_rows=n_rows, n_cols=n_cols, tm=tm, tn=tn,
                     inv_two_sigma_sq=inv_two_sigma_sq,
                     adaptive=adaptive, truncate=truncate)

    # identical VPU reduction to the fused RowSum in kernels/affinity.py, so
    # the streaming engine's degrees (and hence its whole power trajectory)
    # are bitwise-equal to the explicit-A engine's at matching tile sizes
    partial = jnp.sum(a, axis=1, keepdims=True)          # (TM, 1)

    @pl.when(j == 0)
    def _init():
        d_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        d_ref[...] += partial


@functools.partial(
    jax.jit,
    static_argnames=("kind", "sigma", "tm", "tn", "interpret"),
)
def affinity_degree_streaming(
    x: jax.Array,
    xc: jax.Array | None = None,
    *,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = False,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
) -> jax.Array:
    """Degree stripe D = A[stripe] @ 1 in one streamed sweep — the paper's
    AffinityMatrix + RowSum fusion (O1a) without the O(n^2) A write. With
    ``xc`` given, returns the partial row sums over that column block only
    (the ring accumulates these across stages). ``scale_r``/``scale_c``/
    ``thr`` apply the adaptive-scaling / truncation policies in-tile."""
    if xc is None:
        xc = x
    adaptive = scale_r is not None
    truncate = thr is not None
    if adaptive and (kind != "rbf" or scale_c is None):
        raise ValueError("adaptive scaling needs kind='rbf' and both "
                         "scale_r and scale_c")
    n_rows, m = x.shape
    n_cols = xc.shape[0]
    rp = pl.cdiv(n_rows, tm) * tm
    cp = pl.cdiv(n_cols, tn) * tn
    xr32 = jnp.pad(x.astype(jnp.float32), ((0, rp - n_rows), (0, 0)))
    xc32 = jnp.pad(xc.astype(jnp.float32), ((0, cp - n_cols), (0, 0)))
    sqr = jnp.sum(xr32 * xr32, axis=1, keepdims=True)
    sqc = jnp.sum(xc32 * xc32, axis=1, keepdims=True)
    off = jnp.array([row_offset, col_offset], jnp.int32).reshape(1, 2)

    grid = (rp // tm, cp // tn)
    kernel = functools.partial(
        _streaming_degree_kernel,
        kind=kind, n_rows=n_rows, n_cols=n_cols, tm=tm, tn=tn,
        inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        adaptive=adaptive, truncate=truncate,
    )
    in_specs = [
        pl.BlockSpec((1, 2), lambda i, j: (0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((tm, m), lambda i, j: (i, 0)),
        pl.BlockSpec((tn, m), lambda i, j: (j, 0)),
        pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),
    ]
    operands = [off, xr32, xc32, sqr, sqc]
    pol_specs, pol_ops = policy_specs_and_operands(
        scale_r, scale_c, thr, tm=tm, tn=tn, rp=rp, cp=cp,
        n_rows=n_rows, n_cols=n_cols)
    d = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + pol_specs,
        out_specs=pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        interpret=interpret,
        name="affinity_degree_streaming",
    )(*operands, *pol_ops)
    return d[:n_rows, 0]
