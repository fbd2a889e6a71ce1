"""Paper-faithful Power Iteration Clustering (PIC) — Algorithm 1 of GPIC.

This module is the *reference* implementation: explicit W = D^-1 A, the
truncated power iteration with the paper's acceleration-based stopping rule,
then k-means on the 1-D embedding.

Two variants:
  - ``pic_reference``: pure-jnp, jit-compiled (the correctness oracle).
  - ``pic_serial_numpy``: deliberately un-fused row-loop numpy implementation
    standing in for the paper's serial MATLAB baseline (used by the Table-2
    benchmark to measure speedup structure).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.tuning import f32_matmul
from .affinity import (
    AffinityKind,
    AffinitySpec,
    affinity_matrix,
    as_affinity_spec,
)
from .health import HealthReport, count_bad_rows
from .kmeans import kmeans
from .power import (
    batched_power_iteration,
    init_power_vectors,
    run_power_embedding,
    standardize_columns,
)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PICResult:
    labels: jax.Array      # (n,) int32 cluster assignment
    embedding: jax.Array   # (n,) final power-iteration vector v_t (column 0)
    n_iter: jax.Array      # iterations actually executed (column 0)
    converged: jax.Array   # bool — stopped by the epsilon rule (vs max_iter)
    embeddings: jax.Array      # the (n, c) matrix k-means clustered: the
    #   (n, r) engine block for 'pic'/'orthogonal', the (n, r·S) snapshot
    #   concatenation for 'ensemble' — see ``embedding_mode``
    n_iter_cols: jax.Array     # (r,) int32 per-column iteration counts
    converged_cols: jax.Array  # (r,) bool per-column convergence flags
    #: which embedding mode ('pic' | 'orthogonal' | 'ensemble') produced
    #: ``embeddings`` — static metadata, not a traced leaf
    embedding_mode: str = field(metadata=dict(static=True), default="pic")
    #: per-run diagnostics (core/health.py, DESIGN.md §12): per-column
    #: COL_* status codes, isolated-row count, component probe results.
    #: None only for hand-built results that skipped the engine.
    health: Optional[HealthReport] = None


def make_pic_result(labels, v, t_cols, done, *, embedding="pic",
                    embeddings=None, health=None) -> PICResult:
    """Assemble a PICResult from the engine outputs: labels (n,), the final
    (n, r) state, and the per-column (r,) iteration counts / flags. Column 0
    (the paper's degree-seeded vector) backs the scalar back-compat fields;
    the full state rides along so multi-vector callers stop re-deriving it.

    ``embedding`` records which embedding mode produced the clustered
    matrix; ``embeddings`` overrides that matrix when it is wider than the
    engine state (the ensemble concatenation) — ``v`` still supplies the
    column-0 scalars. ``health`` attaches the run's
    :class:`~repro.core.health.HealthReport`.
    """
    return PICResult(
        labels=labels, embedding=v[:, 0], n_iter=t_cols[0], converged=done[0],
        embeddings=v if embeddings is None else embeddings,
        n_iter_cols=t_cols, converged_cols=done, embedding_mode=embedding,
        health=health,
    )


def _power_iterate(
    w_matvec,
    v0: jax.Array,
    eps: float,
    max_iter: int,
):
    """Single-vector truncated power iteration with the paper's stopping rule.

    Stop when || delta_{t+1} - delta_t ||_inf <= eps  where
    delta_{t+1} = |v_{t+1} - v_t|  (Algorithm 1 lines 4-7). The r=1 slice of
    the batched engine loop (core/power.py), kept for single-vector callers.
    """
    v, t_cols, done = batched_power_iteration(
        lambda vv: w_matvec(vv[:, 0])[:, None], v0[:, None], eps, max_iter
    )
    return v[:, 0], t_cols[0], done[0]


def standardize_embedding(v: jax.Array) -> jax.Array:
    """Zero-mean / unit-variance rescale of the 1-D embedding before k-means.

    PIC's embedding has a dynamic range ~1e-5 of its magnitude (values cluster
    around 1/n); standardizing keeps k-means numerically meaningful in f32.
    """
    return (v - jnp.mean(v)) / jnp.maximum(jnp.std(v), 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("k", "max_iter", "kmeans_iters", "affinity_kind",
                     "affinity", "n_vectors", "embedding", "qr_every",
                     "snapshot_iters", "residual_tol"),
)
def pic_reference(
    x: jax.Array,
    k: int,
    *,
    key: jax.Array,
    eps: float | None = None,
    max_iter: int = 50,
    kmeans_iters: int = 25,
    affinity_kind: AffinityKind = "cosine_shifted",
    sigma: float | None = None,
    affinity: AffinitySpec | None = None,
    n_vectors: int = 1,
    embedding: str = "pic",
    qr_every: int = 1,
    snapshot_iters: tuple | None = None,
    residual_tol: float | None = None,
) -> PICResult:
    """Paper Algorithm 1 end-to-end on raw features ``x`` of shape (n, m).

    ``affinity`` (an :class:`AffinitySpec`) runs the dense jnp reference of
    the full graph-construction policy (adaptive local scaling / kNN
    truncation — the oracle the Pallas two-pass build is tested against);
    the legacy ``affinity_kind``/``sigma`` shorthand keeps the classic
    dense builds, including the sigma=None bandwidth heuristic.
    """
    if affinity is not None:
        a = affinity_matrix(x, spec=affinity)
    else:
        a = affinity_matrix(x, kind=affinity_kind, sigma=sigma)
    return pic_from_affinity(
        a, k, key=key, eps=eps, max_iter=max_iter, kmeans_iters=kmeans_iters,
        n_vectors=n_vectors, embedding=embedding, qr_every=qr_every,
        snapshot_iters=snapshot_iters, residual_tol=residual_tol,
    )


@functools.partial(
    jax.jit, static_argnames=("k", "max_iter", "kmeans_iters", "n_vectors",
                              "embedding", "qr_every", "snapshot_iters",
                              "residual_tol")
)
def pic_from_affinity(
    a: jax.Array,
    k: int,
    *,
    key: jax.Array,
    eps: float | None = None,
    max_iter: int = 50,
    kmeans_iters: int = 25,
    n_vectors: int = 1,
    embedding: str = "pic",
    qr_every: int = 1,
    snapshot_iters: tuple | None = None,
    residual_tol: float | None = None,
) -> PICResult:
    """PIC given a pre-built dense affinity matrix A (paper-faithful path).

    W = D^-1 A is materialized explicitly, exactly as Algorithm 1/2 do.
    v_0 = D / sum(D) (GPIC Algorithm 2 lines 4-5). ``eps`` defaults to the
    paper's 1e-5 / n. ``n_vectors > 1`` adds extra power vectors from random
    starts and clusters the stacked embedding (Lin & Cohen's multi-vector
    extension; beyond-paper robustness option O3). All vectors iterate as
    ONE (n, r) batched state — a single W mat-mat per iteration instead of
    r separate sweeps (core/power.py). ``embedding`` selects the block mode
    ('pic' | 'orthogonal' | 'ensemble', DESIGN.md §10); this oracle path
    runs the block algebra through the bare ``w @ V`` operator (jnp Gram)
    and k-means through the jnp assignment oracle, so no Pallas kernel is
    on this path: it is a reference independent of the kernels under test.
    """
    n = a.shape[0]
    if eps is None:
        eps = 1e-5 / n
    d = jnp.sum(a, axis=1)
    # masked normalization: an isolated row (zero or non-finite degree)
    # contributes an exact-zero W row instead of a 1e30-scaled junk one;
    # healthy rows divide bitwise as before (DESIGN.md §12)
    dok = d > 0
    w = jnp.where(dok[:, None], a / jnp.where(dok, d, 1.0)[:, None], 0.0)

    kkm, krand = jax.random.split(key)
    v0 = init_power_vectors(krand, d, n_vectors, dtype=a.dtype)
    v, t_cols, done, emb_raw, status = run_power_embedding(
        lambda vv: f32_matmul(w, vv), v0, eps, max_iter, embedding=embedding,
        qr_every=qr_every, snapshot_iters=snapshot_iters,
        residual_tol=residual_tol)
    emb = standardize_columns(emb_raw)
    labels, _cent = kmeans(kkm, emb, k, iters=kmeans_iters,
                           force_reference=True)
    health = HealthReport(
        col_status=status, isolated_rows=count_bad_rows(d),
        n_components=jnp.int32(-1),        # no spec here — probe not armed
        components=jnp.full((n,), -1, jnp.int32))
    return make_pic_result(labels, v, t_cols, done, embedding=embedding,
                           embeddings=emb_raw, health=health)


# ---------------------------------------------------------------------------
# Serial baseline (stands in for the MATLAB implementation the paper times).
# ---------------------------------------------------------------------------


def pic_serial_numpy(
    x: np.ndarray,
    k: int,
    *,
    seed: int = 0,
    eps: float | None = None,
    max_iter: int = 50,
    kmeans_iters: int = 25,
    affinity_kind: AffinityKind = "cosine_shifted",
    sigma: float | None = None,
    return_timings: bool = False,
):
    """Row-at-a-time serial PIC. Mirrors the structure the paper profiles:

    an O(n^2 m) affinity loop (their Table-1 bottleneck), explicit RowSum /
    NormMatrix passes, then an un-fused power loop. Intentionally not vectorized
    across rows so the affinity stage dominates like the MATLAB original.
    """
    import time

    n = x.shape[0]
    x = np.asarray(x, np.float64)
    if eps is None:
        eps = 1e-5 / n

    t0 = time.perf_counter()
    if affinity_kind in ("cosine", "cosine_shifted"):
        xn = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
        a = np.empty((n, n), np.float64)
        for i in range(n):  # deliberate serial row loop (see docstring)
            row = xn[i] @ xn.T
            if affinity_kind == "cosine_shifted":
                row = 0.5 * (1.0 + row)
            row[i] = 0.0
            a[i] = row
    else:
        sq = np.sum(x * x, axis=1)
        if sigma is not None:
            sig = float(sigma)
        else:
            # strided sample, matching core.affinity.rbf_bandwidth_heuristic
            # (a leading slice is biased on cluster-ordered inputs; the
            # ceil-division stride spans the whole row range)
            take = min(512, n)
            xs = x[:: max(-(-n // take), 1)][:take]
            sqs = np.sum(xs * xs, axis=1)
            sig = float(np.median(np.sqrt(np.maximum(
                sqs[:, None] + sqs[None, :] - 2 * xs @ xs.T, 0)
                + np.eye(len(xs)) * 1e9)))
        a = np.empty((n, n), np.float64)
        for i in range(n):
            d2 = np.maximum(sq[i] + sq - 2.0 * (x[i] @ x.T), 0.0)
            row = np.exp(-d2 / (2.0 * sig * sig))
            row[i] = 0.0
            a[i] = row
    t_affinity = time.perf_counter() - t0

    t1 = time.perf_counter()
    d = a.sum(axis=1)                    # RowSum kernel
    w = a / np.maximum(d, 1e-30)[:, None]  # NormMatrix kernel
    t_norm = time.perf_counter() - t1

    t1 = time.perf_counter()
    v = d / max(d.sum(), 1e-30)          # Reduction + Norm
    delta = v.copy()
    it = 0
    for it in range(1, max_iter + 1):    # power loop (Multiply/Reduction/Norm)
        wv = w @ v
        v_next = wv / max(np.abs(wv).sum(), 1e-30)
        delta_next = np.abs(v_next - v)
        accel = np.max(np.abs(delta_next - delta))
        v, delta = v_next, delta_next
        if accel <= eps:
            break
    t_power = time.perf_counter() - t1

    t2 = time.perf_counter()
    v_std = (v - v.mean()) / max(v.std(), 1e-30)
    labels, _ = kmeans(jax.random.key(seed), jnp.asarray(v_std)[:, None], k,
                       iters=kmeans_iters)
    labels = np.asarray(labels)
    t_kmeans = time.perf_counter() - t2

    if return_timings:
        return labels, v, {
            "affinity_s": t_affinity,
            "norm_s": t_norm,
            "power_s": t_power,
            "kmeans_s": t_kmeans,
            "total_s": t_affinity + t_norm + t_power + t_kmeans,
            "n_iter": it,
        }
    return labels, v
