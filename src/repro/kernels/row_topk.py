"""Pallas TPU kernel: streamed per-row top-k statistics (pass 1 of the
two-pass affinity-graph build, DESIGN.md §11).

The adaptive-bandwidth and kNN-truncation policies of
:class:`~repro.core.affinity.AffinitySpec` both reduce to ONE per-row order
statistic of the (n, n) score matrix:

  stat='neg_sqdist'    top-k of -||x_i - x_j||²  →  [:, k-1] is the k-th
                       nearest-neighbor distance (the self-tuning local
                       scale σᵢ, after sqrt(-·))
  stat='similarity'    top-k of the affinity value itself (kind / sigma /
                       adaptive scales applied)  →  [:, k-1] is the row's
                       truncation threshold τᵢ

Like every GPIC kernel this computes a general *stripe* (row slab × col
slab with global SMEM offsets masking the diagonal), and it is STREAMED:
each (i, j) grid step regenerates the (TM, TN) score tile on the MXU —
reusing the exact tile transform of the affinity kernels — and folds it
into a running (TM, K) top-k buffer in the output ref, accumulated across
the col-grid dimension. No (n, n) array ever exists, so pass 1 costs the
A-free paths nothing in residency.

The in-tile top-k is K rounds of extract-the-row-max over the
(TM, K + TN) merge candidates: max / compare / select ops only (VPU
friendly — no general sort), with an index tie-break so duplicated scores
are consumed one at a time. Rows with fewer than K valid entries pad with
-inf (callers bound k < n, so the k-th statistic itself is always finite).

Cost: O(K) VPU passes over each tile on top of the O(n² m / TILE) MXU
work — one extra "sweep" per clustering, amortized over every power
iteration that then runs on a k-sparse graph.
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
    unpack_policy_refs,
)
from .tuning import MXU_PRECISION

STATS = ("similarity", "neg_sqdist")

_NEG_INF = float("-inf")


def row_topk_merge(buf: jax.Array, cand: jax.Array, k: int) -> jax.Array:
    """Descending top-k over the columns of [buf | cand] — K rounds of
    masked row-max extraction (max/where/iota only, so the same code runs
    on the VPU inside the kernel and as plain jnp in the ring's cross-stage
    merge). Ties are consumed once each via a first-column tie-break."""
    s = jnp.concatenate([buf, cand], axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    out = []
    for _ in range(k):
        m = jnp.max(s, axis=1, keepdims=True)
        out.append(m)
        first = jnp.min(jnp.where(s == m, cols, s.shape[1]),
                        axis=1, keepdims=True)
        s = jnp.where(cols == first, _NEG_INF, s)
    return jnp.concatenate(out, axis=1)


def _row_topk_kernel(
    off_ref,                           # (1, 2) SMEM: global row/col offsets
    *refs,
    stat: str, kind: str, n_rows: int, n_cols: int, tm: int, tn: int,
    k: int, inv_two_sigma_sq: float, adaptive: bool,
):
    refs = list(refs)
    o_ref = refs[-1]                   # (TM, K) running top-k buffer
    xr_ref, xc_ref, sqr_ref, sqc_ref = refs[:4]
    sclr_ref, sclc_ref, _thr, _thr_c = unpack_policy_refs(
        refs[4:-1], adaptive, truncate=False)

    i = pl.program_id(0)
    j = pl.program_id(1)

    xr = xr_ref[...]
    xc = xc_ref[...]
    dot = jax.lax.dot_general(
        xr, xc, (((1,), (1,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )

    if stat == "similarity":
        s = affinity_tile_transform(
            dot, sqr_ref[...] if kind == "rbf" else None,
            sqc_ref[...] if kind == "rbf" else None,
            kind=kind, inv_two_sigma_sq=inv_two_sigma_sq,
            sclr=sclr_ref[...] if adaptive else None,
            sclc=sclc_ref[...] if adaptive else None,
        )
    elif stat == "neg_sqdist":
        d2 = sqr_ref[...] + sqc_ref[...].T - 2.0 * dot
        s = -jnp.maximum(d2, 0.0)
    else:
        raise ValueError(stat)

    lrows = i * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    lcols = j * tn + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 1)
    grows = off_ref[0, 0] + lrows
    gcols = off_ref[0, 1] + lcols
    valid = (grows != gcols) & (lrows < n_rows) & (lcols < n_cols)
    s = jnp.where(valid, s, _NEG_INF)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = row_topk_merge(
            jnp.full((tm, k), _NEG_INF, jnp.float32), s, k)

    @pl.when(j != 0)
    def _merge():
        o_ref[...] = row_topk_merge(o_ref[...], s, k)


@functools.partial(
    jax.jit,
    static_argnames=("stat", "kind", "sigma", "k", "tm", "tn", "interpret"),
)
def row_topk(
    x: jax.Array,
    xc: jax.Array | None = None,
    *,
    k: int,
    stat: str = "similarity",
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = False,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
) -> jax.Array:
    """(R, k) descending per-row top-k scores of the stripe of ``x`` vs
    ``xc`` (None = the square self-stripe), diagonal excluded.

    ``stat='similarity'`` scores with the affinity transform (pass
    ``scale_r``/``scale_c`` for adaptive rbf); ``stat='neg_sqdist'`` scores
    with the negated squared distance (the k-th nearest-neighbor pass).
    Rows with fewer than k valid entries pad with -inf — ring callers
    merge per-stage results with :func:`row_topk_merge`.
    """
    if stat not in STATS:
        raise ValueError(f"unknown stat {stat!r} (expected one of {STATS})")
    if xc is None:
        xc = x
    adaptive = scale_r is not None
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
        _row_topk_kernel,
        stat=stat, kind=kind, n_rows=n_rows, n_cols=n_cols, tm=tm, tn=tn,
        k=k, inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        adaptive=adaptive,
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
        scale_r, scale_c, None, tm=tm, tn=tn, rp=rp, cp=cp,
        n_rows=n_rows, n_cols=n_cols)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + pol_specs,
        out_specs=pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, k), jnp.float32),
        interpret=interpret,
    )(*operands, *pol_ops)
    return out[:n_rows]


def topk_thresholds_from_scores(
    scores: jax.Array,
    *,
    k: int,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
) -> jax.Array:
    """(R,) per-row k-th-largest similarity from an UNMASKED score stripe —
    the fused one-pass build's threshold epilogue (DESIGN.md §13).

    ``scores`` is the stripe the build kernel writes with ``thr=None``: the
    true similarity values everywhere except the global diagonal, which the
    kernel masks to 0. The diagonal is re-excluded here BY INDEX (never by
    value — plain-cosine scores can be negative, so a written 0 could
    outrank real entries) and the k-th order statistic taken with
    ``jnp.partition`` (an O(n) selection — an order of magnitude faster
    than ``lax.top_k``'s sorted-prefix on CPU, and the threshold only
    needs the VALUE, not the sorted prefix). Selection is exact, so the
    statistic equals the one the streamed ``row_topk`` kernel keeps: both
    paths score tiles through the shared ``affinity_tile_transform``, so
    the thresholds are bitwise-equal to the two-pass build's.
    """
    grows = row_offset + jnp.arange(scores.shape[0])[:, None]
    gcols = col_offset + jnp.arange(scores.shape[1])[None, :]
    s = jnp.where(grows == gcols, _NEG_INF, scores.astype(jnp.float32))
    return -jnp.partition(-s, k - 1, axis=1)[:, k - 1]
