"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Every product runs at HIGHEST precision (DESIGN.md §6): an oracle whose
f32 matmul took one bf16 pass on the MXU would be taken for a wrong
kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .tuning import f32_matmul as _mm


def _affinity_scores_ref(
    x: jax.Array,
    c: jax.Array,
    *,
    kind: str,
    sigma: float,
    scale_r: jax.Array | None,
    scale_c: jax.Array | None,
) -> jax.Array:
    """Dense (R, C) similarity scores before any masking — the one place
    the reference similarity transform (fixed or adaptive bandwidth) lives."""
    if kind in ("cosine", "cosine_shifted"):
        a = _mm(x, c.T)
        if kind == "cosine_shifted":
            a = 0.5 * (1.0 + a)
        return a
    if kind == "rbf":
        sqr = jnp.sum(x * x, axis=1)
        sqc = jnp.sum(c * c, axis=1)
        d2 = jnp.maximum(sqr[:, None] + sqc[None, :] - 2.0 * _mm(x, c.T),
                         0.0)
        if scale_r is not None:
            return jnp.exp(-d2 / (scale_r.astype(jnp.float32)[:, None]
                                  * scale_c.astype(jnp.float32)[None, :]))
        return jnp.exp(-d2 / (2.0 * sigma * sigma))
    raise ValueError(kind)


def affinity_and_degree_ref(
    xn: jax.Array,
    xc: jax.Array | None = None,
    *,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for kernels.affinity.affinity_and_degree (stripe-general).

    ``scale_r``/``scale_c`` are the (R,)/(C,) adaptive local scales (rbf
    only; replaces the 2 sigma^2 denominator with scale_i * scale_j);
    ``thr`` is the (R,) per-row truncation threshold — entries strictly
    below it are zeroed (DESIGN.md §11).
    """
    x = xn.astype(jnp.float32)
    c = x if xc is None else xc.astype(jnp.float32)
    a = _affinity_scores_ref(x, c, kind=kind, sigma=sigma,
                             scale_r=scale_r, scale_c=scale_c)
    grows = row_offset + jnp.arange(a.shape[0])[:, None]
    gcols = col_offset + jnp.arange(a.shape[1])[None, :]
    valid = grows != gcols
    if thr is not None:
        valid = valid & (a >= thr.astype(jnp.float32)[:, None])
    a = jnp.where(valid, a, 0.0)
    return a, jnp.sum(a, axis=1)


def row_topk_ref(
    x: jax.Array,
    xc: jax.Array | None = None,
    *,
    k: int,
    stat: str = "similarity",
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
) -> jax.Array:
    """Oracle for kernels.row_topk.row_topk: per-row descending top-k of

      stat='similarity'  the affinity value (kind/sigma/scales applied)
      stat='neg_sqdist'  -||x_i - c_j||^2  (so [:, k-1] is the k-th
                         nearest-neighbor statistic)

    over the VALID entries of the stripe (global diagonal excluded). Rows
    with fewer than k valid entries pad with -inf.
    """
    x = x.astype(jnp.float32)
    c = x if xc is None else xc.astype(jnp.float32)
    if stat == "similarity":
        s = _affinity_scores_ref(x, c, kind=kind, sigma=sigma,
                                 scale_r=scale_r, scale_c=scale_c)
    elif stat == "neg_sqdist":
        sqr = jnp.sum(x * x, axis=1)
        sqc = jnp.sum(c * c, axis=1)
        s = -jnp.maximum(sqr[:, None] + sqc[None, :] - 2.0 * _mm(x, c.T),
                         0.0)
    else:
        raise ValueError(f"unknown stat {stat!r}")
    grows = row_offset + jnp.arange(s.shape[0])[:, None]
    gcols = col_offset + jnp.arange(s.shape[1])[None, :]
    s = jnp.where(grows != gcols, s, -jnp.inf)
    return jax.lax.top_k(s, k)[0]


def _floored_degree_divide(u: jax.Array, d: jax.Array) -> jax.Array:
    """u / d with the floored reciprocal the Pallas kernels use — already
    zero-degree safe (d = 0 implies the whole nonnegative A row, hence u,
    is an exact 0; NaN degrees propagate to the loop's non-finite latch).
    The divide form is pinned: masked-where variants are value-identical
    on healthy rows but perturb interpret-mode XLA fusion and break
    local/sharded trajectory parity (DESIGN.md §12)."""
    return u / jnp.maximum(d.astype(jnp.float32), 1e-30)


def degree_normalized_matvec_ref(
    a: jax.Array, v: jax.Array, d: jax.Array
) -> jax.Array:
    """Oracle for kernels.power_step.degree_normalized_matvec."""
    a = a[:d.shape[0], :v.shape[0]]      # padded storage → logical shape
    u = _mm(a.astype(jnp.float32), v.astype(jnp.float32))
    return _floored_degree_divide(u, d)


def degree_normalized_matmat_ref(
    a: jax.Array, v: jax.Array, d: jax.Array
) -> jax.Array:
    """Oracle for kernels.power_step.degree_normalized_matmat (v is (n, r));
    ``a`` may be at its zero-padded storage shape, like the kernel's."""
    a = a[:d.shape[0], :v.shape[0]]
    u = _mm(a.astype(jnp.float32), v.astype(jnp.float32))
    return _floored_degree_divide(u, d[:, None])


def affinity_matmat_ref(
    x: jax.Array,
    v: jax.Array,
    d: jax.Array | None = None,
    xc: jax.Array | None = None,
    *,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
    thr_c: jax.Array | None = None,
) -> jax.Array:
    """Oracle for kernels.streaming.affinity_matmat: (A @ V) / d, dense A.
    ``thr_c`` masks each COLUMN below its own threshold (the Aᵀ-stripe
    product of the symmetrized reachability probe)."""
    a, _ = affinity_and_degree_ref(x, xc, kind=kind, sigma=sigma,
                                   row_offset=row_offset,
                                   col_offset=col_offset,
                                   scale_r=scale_r, scale_c=scale_c, thr=thr)
    if thr_c is not None:
        a = jnp.where(a >= thr_c.astype(jnp.float32)[None, :], a, 0.0)
    u = _mm(a, v.astype(jnp.float32))
    if d is None:
        return u
    return _floored_degree_divide(u, d[:, None])


def affinity_degree_streaming_ref(
    x: jax.Array,
    xc: jax.Array | None = None,
    *,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
) -> jax.Array:
    """Oracle for kernels.streaming.affinity_degree_streaming."""
    _, deg = affinity_and_degree_ref(x, xc, kind=kind, sigma=sigma,
                                     row_offset=row_offset,
                                     col_offset=col_offset,
                                     scale_r=scale_r, scale_c=scale_c,
                                     thr=thr)
    return deg


def gram_ref(v: jax.Array) -> jax.Array:
    """Oracle for kernels.gram.gram: G = VᵀV in f32."""
    v32 = v.astype(jnp.float32)
    return _mm(v32.T, v32)


def power_step_ref(a: jax.Array, v: jax.Array, d: jax.Array) -> jax.Array:
    """Oracle for kernels.power_step.power_step."""
    u = degree_normalized_matvec_ref(a, v, d)
    return u / jnp.maximum(jnp.sum(jnp.abs(u)), 1e-30)


def flash_attention_ref(q, k, v, *, causal=True):
    """Oracle for kernels.flash_attention: q (bh, s, d), k/v (bkv, s, d)."""
    bh, s, d = q.shape
    rep = bh // k.shape[0]
    kk = jnp.repeat(k, rep, axis=0).astype(jnp.float32)
    vv = jnp.repeat(v, rep, axis=0).astype(jnp.float32)
    logits = jnp.einsum("hsd,htd->hst", q.astype(jnp.float32), kk)
    logits = logits / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hst,htd->hsd", probs, vv).astype(q.dtype)


def kmeans_assign_ref(
    x: jax.Array, cents: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Oracle for kernels.kmeans_assign.kmeans_assign."""
    x = x.astype(jnp.float32)
    c = cents.astype(jnp.float32)
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    cc = jnp.sum(c * c, axis=1)[None, :]
    d2 = xx + cc - 2.0 * _mm(x, c.T)
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)


def _plan_live_ref(counts: jax.Array, col_idx: jax.Array) -> jax.Array:
    """(nI, nJ) boolean live map from a block plan (scatter with .max so
    the padded dead-id tail never clobbers a live block)."""
    n_i, n_j = col_idx.shape
    slot_live = jnp.arange(n_j)[None, :] < counts[:, None]
    live = jnp.zeros((n_i, n_j), bool)
    return live.at[jnp.arange(n_i)[:, None], col_idx].max(slot_live)


def _apply_plan_ref(a: jax.Array, counts, col_idx, tm: int, tn: int):
    """Zero every block of ``a`` the plan marks dead (tile grid padded to
    (tm, tn) multiples like the kernels pad)."""
    n_rows, n_cols = a.shape
    rp = -(-n_rows // tm) * tm
    cp = -(-n_cols // tn) * tn
    ap = jnp.pad(a, ((0, rp - n_rows), (0, cp - n_cols)))
    live = _plan_live_ref(counts, col_idx)
    mask = jnp.repeat(jnp.repeat(live, tm, axis=0), tn, axis=1)
    return jnp.where(mask, ap, 0.0)[:n_rows, :n_cols]


def block_sparse_matmat_ref(
    a: jax.Array, v: jax.Array, d: jax.Array,
    counts: jax.Array, col_idx: jax.Array, *, tm: int, tn: int
) -> jax.Array:
    """Oracle for kernels.block_sparse.block_sparse_matmat: the plan's dead
    blocks contribute nothing, everything else is the dense oracle."""
    return degree_normalized_matmat_ref(
        _apply_plan_ref(a.astype(jnp.float32), counts, col_idx, tm, tn), v, d)


def block_sparse_streaming_matmat_ref(
    x: jax.Array,
    v: jax.Array,
    d: jax.Array | None = None,
    xc: jax.Array | None = None,
    *,
    counts: jax.Array,
    col_idx: jax.Array,
    tm: int,
    tn: int,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
) -> jax.Array:
    """Oracle for kernels.block_sparse.block_sparse_streaming_matmat."""
    a, _ = affinity_and_degree_ref(x, xc, kind=kind, sigma=sigma,
                                   row_offset=row_offset,
                                   col_offset=col_offset,
                                   scale_r=scale_r, scale_c=scale_c, thr=thr)
    u = _mm(_apply_plan_ref(a, counts, col_idx, tm, tn),
            v.astype(jnp.float32))
    if d is None:
        return u
    return _floored_degree_divide(u, d[:, None])


def block_sparse_streaming_degree_ref(
    x: jax.Array,
    xc: jax.Array | None = None,
    *,
    counts: jax.Array,
    col_idx: jax.Array,
    tm: int,
    tn: int,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
) -> jax.Array:
    """Oracle for kernels.block_sparse.block_sparse_streaming_degree."""
    a, _ = affinity_and_degree_ref(x, xc, kind=kind, sigma=sigma,
                                   row_offset=row_offset,
                                   col_offset=col_offset,
                                   scale_r=scale_r, scale_c=scale_c, thr=thr)
    return jnp.sum(_apply_plan_ref(a, counts, col_idx, tm, tn), axis=1)


def block_liveness_ref(
    x: jax.Array,
    xc: jax.Array | None = None,
    *,
    tm: int,
    tn: int,
    kind: str = "cosine_shifted",
    sigma: float = 1.0,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    thr: jax.Array | None = None,
) -> jax.Array:
    """Oracle for kernels.block_sparse.block_liveness: per-(tm, tn)-tile
    any-nonzero of the masked stripe, padding blocks dead."""
    a, _ = affinity_and_degree_ref(x, xc, kind=kind, sigma=sigma,
                                   row_offset=row_offset,
                                   col_offset=col_offset,
                                   scale_r=scale_r, scale_c=scale_c, thr=thr)
    n_rows, n_cols = a.shape
    rp = -(-n_rows // tm) * tm
    cp = -(-n_cols // tn) * tn
    ap = jnp.pad(a, ((0, rp - n_rows), (0, cp - n_cols)))
    tiles = ap.reshape(rp // tm, tm, cp // tn, tn)
    return jnp.any(tiles != 0, axis=(1, 3)).astype(jnp.int32)
