"""Pallas TPU kernels: block-CSR stripe sweeps over live affinity tiles.

kNN truncation (DESIGN.md §11) zeroes ~97% of A at knn30/n=1024, but until
this PR every sweep still visited the zero tiles: the dense grid walks
(R/TM)·(C/TN) steps regardless of sparsity, so sweep bandwidth tracked n²
instead of nnz. This module adds the block-CSR counterpart of each sweep
kernel (DESIGN.md §13): after the build, the caller derives a *block plan* —
per row-block, the ascending list of column-block indices with at least one
surviving entry — and the kernels iterate ONLY live blocks.

The plan rides in as scalar-prefetch SMEM operands (`PrefetchScalarGridSpec`):

  counts   (nI,)     int32   live column-blocks in row-block i
  col_idx  (nI, nJ)  int32   ascending live block ids first; the tail is
                             padded with the remaining (dead) ids so every
                             entry stays a valid block index for the DMA
                             index maps even on skipped steps
  max_b    scalar    int32   max(counts) (≥ 1), the traced second grid dim

The grid is (nI, max_b): step (i, j) gathers block `col_idx[i, j]` via the
BlockSpec index maps and accumulates its partial. Ragged tail steps
(j >= counts[i]) gather a DEAD block — all-zero by construction — whose
partial is an exact zero, so no per-step liveness gate is needed: the step
program stays IDENTICAL to the dense kernels' (dot outside any
conditional, assign-at-0/accumulate split, pinned floored divide), which
is what keeps the block-sparse sweeps bitwise-equal to their dense-storage
counterparts at matching tile sizes (asserted in
tests/test_block_sparse.py; nesting the dot inside a pl.when perturbs
interpret-mode XLA fusion at r=1). max_b is a *traced* grid dimension: one
compiled program serves every sparsity pattern, and on hardware the DMA
volume (the real cost) scales with nnz blocks.

Three sweep variants mirror the dense kernels they shadow:

  block_sparse_matmat             kernels/power_step.degree_normalized_matmat
  block_sparse_streaming_matmat   kernels/streaming.affinity_matmat
  block_sparse_streaming_degree   kernels/streaming.affinity_degree_streaming

plus `block_liveness`, the A-free plan *source* for streaming engines: a
full-grid pass that regenerates each masked tile from the feature slabs
(the shared `_masked_tile` body) and emits the (nI, nJ) 0/1 live-block map
without ever materializing A. Explicit engines read liveness off the stored
matrix instead (core/affinity.py::dense_block_live).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .affinity import policy_specs_and_operands, unpack_policy_refs
from .streaming import _masked_tile
from .tuning import MXU_PRECISION


def _prefetch_policy_specs(scale_r, thr, *, tm, tn):
    """Block-sparse twins of the policy specs: same operand ORDER and
    padding as kernels/affinity.py::policy_specs_and_operands (which
    callers still use to build the padded operands), but with
    scalar-prefetch-aware index maps — the column-side scale block follows
    the gathered block id col[i, j], not the grid coordinate j."""
    specs = []
    if scale_r is not None:
        specs += [
            pl.BlockSpec((tm, 1), lambda i, j, off, cnt, col: (i, 0)),
            pl.BlockSpec((tn, 1), lambda i, j, off, cnt, col: (col[i, j], 0)),
        ]
    if thr is not None:
        specs.append(pl.BlockSpec((tm, 1), lambda i, j, off, cnt, col: (i, 0)))
    return specs


def _bs_matmat_kernel(cnt_ref, col_ref, a_ref, v_ref, d_ref, u_ref):
    del cnt_ref  # ragged tail steps gather DEAD (all-zero) blocks whose
    del col_ref  # partials are exact zeros — no per-step gate needed, and
    # keeping the step program IDENTICAL to _power_step_kernel (dot outside
    # any conditional, assign-at-0/accumulate split, pinned floored divide)
    # is what keeps the sweep bitwise-equal to the dense kernel: nesting
    # the dot inside a pl.when perturbs interpret-mode XLA fusion at r=1
    # (the same discipline that pins the divide form, DESIGN.md §12)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    a = a_ref[...].astype(jnp.float32)
    partial = jax.lax.dot_general(
        a, v_ref[...], (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        u_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        u_ref[...] += partial

    @pl.when(j == nb - 1)
    def _norm():
        u_ref[...] = u_ref[...] / jnp.maximum(d_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def block_sparse_matmat(
    a: jax.Array,
    v: jax.Array,
    d: jax.Array,
    counts: jax.Array,
    col_idx: jax.Array,
    max_b: jax.Array,
    *,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """U = (A @ V) / d visiting only the live blocks of the stored A.

    ``a`` is the (R, C) truncated matrix exactly as the dense path stores
    it (zeros in-tile), at its logical or its zero-padded storage shape
    (see degree_normalized_matmat); the plan (``counts``/``col_idx``/
    ``max_b``, from core/affinity.py::block_plan over the same tile grid)
    tells each row-block which column tiles survive. Bitwise-equal to
    degree_normalized_matmat at matching (tm, tn).
    """
    n_rows, n_cols = d.shape[0], v.shape[0]
    r = v.shape[1]
    rp = pl.cdiv(a.shape[0], tm) * tm
    cp = pl.cdiv(a.shape[1], tn) * tn
    ap = a
    if (rp, cp) != a.shape:
        ap = jnp.pad(a, ((0, rp - a.shape[0]), (0, cp - a.shape[1])))
    vp = jnp.pad(v.astype(jnp.float32), ((0, cp - n_cols), (0, 0)))
    dp = jnp.pad(d.astype(jnp.float32), (0, rp - n_rows),
                 constant_values=1.0)[:, None]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rp // tm, jnp.maximum(max_b, 1)),
        in_specs=[
            pl.BlockSpec((tm, tn), lambda i, j, cnt, col: (i, col[i, j])),
            pl.BlockSpec((tn, r), lambda i, j, cnt, col: (col[i, j], 0)),
            pl.BlockSpec((tm, 1), lambda i, j, cnt, col: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tm, r), lambda i, j, cnt, col: (i, 0)),
    )
    u = pl.pallas_call(
        _bs_matmat_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, r), jnp.float32),
        interpret=interpret,
    )(counts, col_idx, ap, vp, dp)
    return u[:n_rows]


def _bs_streaming_kernel(
    off_ref, cnt_ref, col_ref,
    *refs,
    kind: str, n_rows: int, n_cols: int, tm: int, tn: int,
    inv_two_sigma_sq: float, normalize: bool,
    adaptive: bool, truncate: bool,
):
    refs = list(refs)
    u_ref = refs[-1]
    xr_ref, xc_ref, sqr_ref, sqc_ref, v_ref, d_ref = refs[:6]
    rest = refs[6:-1]
    sclr_ref = sclc_ref = thr_ref = None
    if adaptive:
        sclr_ref, sclc_ref = rest[0], rest[1]
        rest = rest[2:]
    if truncate:
        thr_ref = rest[0]

    del cnt_ref  # ragged tail steps regenerate DEAD tiles — every entry is
    # below its row threshold, so the masked tile and its partial are exact
    # zeros; no per-step gate, and the step program mirrors
    # streaming._streaming_kernel exactly (dot outside any conditional) to
    # stay bitwise-equal to the dense-grid sweep (see _bs_matmat_kernel)
    i = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    # the gathered col-block id drives the diagonal/padding mask — the
    # shared tile body takes it in place of the grid coordinate
    a = _masked_tile(i, col_ref[i, j], off_ref,
                     xr_ref, xc_ref, sqr_ref, sqc_ref,
                     sclr_ref, sclc_ref, thr_ref,
                     kind=kind, n_rows=n_rows, n_cols=n_cols,
                     tm=tm, tn=tn, inv_two_sigma_sq=inv_two_sigma_sq,
                     adaptive=adaptive, truncate=truncate)
    partial = jax.lax.dot_general(
        a, v_ref[...], (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        u_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        u_ref[...] += partial

    if normalize:
        @pl.when(j == nb - 1)
        def _norm():
            u_ref[...] = u_ref[...] / jnp.maximum(d_ref[...], 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "sigma", "tm", "tn", "interpret"),
)
def block_sparse_streaming_matmat(
    x: jax.Array,
    v: jax.Array,
    d: jax.Array | None = None,
    xc: jax.Array | None = None,
    *,
    counts: jax.Array,
    col_idx: jax.Array,
    max_b: jax.Array,
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
    """U = (A @ V) / d regenerating ONLY the live feature tiles.

    The A-free twin of block_sparse_matmat: same signature contract as
    kernels/streaming.affinity_matmat plus the block plan (for streaming
    engines the plan comes from `block_liveness`, not a stored matrix).
    ``d=None`` skips normalization and returns partial stripe sums — the
    sharded ring accumulates those across stages, slicing its per-stage
    plan out of a stacked (P, nI, nJ) liveness ring.
    """
    if xc is None:
        xc = x
    adaptive = scale_r is not None
    truncate = thr is not None
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
    sqr = jnp.sum(xr32 * xr32, axis=1, keepdims=True)
    sqc = jnp.sum(xc32 * xc32, axis=1, keepdims=True)
    off = jnp.array([row_offset, col_offset], jnp.int32).reshape(1, 2)

    kernel = functools.partial(
        _bs_streaming_kernel,
        kind=kind, n_rows=n_rows, n_cols=n_cols, tm=tm, tn=tn,
        inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        normalize=normalize, adaptive=adaptive, truncate=truncate,
    )
    in_specs = [
        pl.BlockSpec((tm, m), lambda i, j, off, cnt, col: (i, 0)),
        pl.BlockSpec((tn, m), lambda i, j, off, cnt, col: (col[i, j], 0)),
        pl.BlockSpec((tm, 1), lambda i, j, off, cnt, col: (i, 0)),
        pl.BlockSpec((tn, 1), lambda i, j, off, cnt, col: (col[i, j], 0)),
        pl.BlockSpec((tn, r), lambda i, j, off, cnt, col: (col[i, j], 0)),
        pl.BlockSpec((tm, 1), lambda i, j, off, cnt, col: (i, 0)),
    ]
    _, pol_ops = policy_specs_and_operands(
        scale_r, scale_c, thr, tm=tm, tn=tn, rp=rp, cp=cp,
        n_rows=n_rows, n_cols=n_cols)
    pol_specs = _prefetch_policy_specs(scale_r, thr, tm=tm, tn=tn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rp // tm, jnp.maximum(max_b, 1)),
        in_specs=in_specs + pol_specs,
        out_specs=pl.BlockSpec((tm, r), lambda i, j, off, cnt, col: (i, 0)),
    )
    u = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, r), jnp.float32),
        interpret=interpret,
    )(off, counts, col_idx, xr32, xc32, sqr, sqc, vp, dp[:, None], *pol_ops)
    return u[:n_rows]


def _bs_degree_kernel(
    off_ref, cnt_ref, col_ref,
    *refs,
    kind: str, n_rows: int, n_cols: int, tm: int, tn: int,
    inv_two_sigma_sq: float, adaptive: bool, truncate: bool,
):
    refs = list(refs)
    d_ref = refs[-1]
    xr_ref, xc_ref, sqr_ref, sqc_ref = refs[:4]
    rest = refs[4:-1]
    sclr_ref = sclc_ref = thr_ref = None
    if adaptive:
        sclr_ref, sclc_ref = rest[0], rest[1]
        rest = rest[2:]
    if truncate:
        thr_ref = rest[0]

    del cnt_ref  # dead tail tiles row-sum to exact zero; same pinned step
    # structure as streaming._streaming_degree_kernel (see _bs_matmat_kernel)
    i = pl.program_id(0)
    j = pl.program_id(1)

    a = _masked_tile(i, col_ref[i, j], off_ref,
                     xr_ref, xc_ref, sqr_ref, sqc_ref,
                     sclr_ref, sclc_ref, thr_ref,
                     kind=kind, n_rows=n_rows, n_cols=n_cols,
                     tm=tm, tn=tn, inv_two_sigma_sq=inv_two_sigma_sq,
                     adaptive=adaptive, truncate=truncate)
    partial = jnp.sum(a, axis=1, keepdims=True)

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
def block_sparse_streaming_degree(
    x: jax.Array,
    xc: jax.Array | None = None,
    *,
    counts: jax.Array,
    col_idx: jax.Array,
    max_b: jax.Array,
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
    """Degree stripe over live blocks only — the block-sparse twin of
    kernels/streaming.affinity_degree_streaming. Bitwise-equal to it
    because skipped tiles are all-zero and contribute exact +0 partials
    to the nonnegative row-sum accumulation."""
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

    kernel = functools.partial(
        _bs_degree_kernel,
        kind=kind, n_rows=n_rows, n_cols=n_cols, tm=tm, tn=tn,
        inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        adaptive=adaptive, truncate=truncate,
    )
    in_specs = [
        pl.BlockSpec((tm, m), lambda i, j, off, cnt, col: (i, 0)),
        pl.BlockSpec((tn, m), lambda i, j, off, cnt, col: (col[i, j], 0)),
        pl.BlockSpec((tm, 1), lambda i, j, off, cnt, col: (i, 0)),
        pl.BlockSpec((tn, 1), lambda i, j, off, cnt, col: (col[i, j], 0)),
    ]
    _, pol_ops = policy_specs_and_operands(
        scale_r, scale_c, thr, tm=tm, tn=tn, rp=rp, cp=cp,
        n_rows=n_rows, n_cols=n_cols)
    pol_specs = _prefetch_policy_specs(scale_r, thr, tm=tm, tn=tn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rp // tm, jnp.maximum(max_b, 1)),
        in_specs=in_specs + pol_specs,
        out_specs=pl.BlockSpec((tm, 1), lambda i, j, off, cnt, col: (i, 0)),
    )
    d = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        interpret=interpret,
    )(off, counts, col_idx, xr32, xc32, sqr, sqc, *pol_ops)
    return d[:n_rows, 0]


def _liveness_kernel(
    off_ref,
    *refs,
    kind: str, n_rows: int, n_cols: int, tm: int, tn: int,
    inv_two_sigma_sq: float, adaptive: bool, truncate: bool,
):
    refs = list(refs)
    o_ref = refs[-1]
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
    o_ref[...] = jnp.any(a != 0.0).astype(jnp.int32).reshape(1, 1)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "sigma", "tm", "tn", "interpret"),
)
def block_liveness(
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
    """(nI, nJ) int32 live-block map of the masked stripe, A-free.

    One full-grid pass (this is build-time work, paid once) regenerating
    each masked tile through the SAME `_masked_tile` body the streaming
    sweeps use, so liveness is exact for the tiles those sweeps would
    compute: live[i, j] = 1 iff any entry of the masked tile is nonzero.
    """
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
        _liveness_kernel,
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
    live = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + pol_specs,
        out_specs=pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(grid, jnp.int32),
        interpret=interpret,
    )(*operands, *pol_ops)
    return live
