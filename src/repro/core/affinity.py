"""Affinity-graph construction for Power Iteration Clustering.

The paper (GPIC §4.2) uses cosine similarity between input rows; the affinity
step is the measured bottleneck (88.6 % of serial PIC runtime, Table 1).

Three affinity kinds are provided:

- ``cosine``          raw cosine similarity  (may be negative on signed data)
- ``cosine_shifted``  (1 + cos)/2  — non-negative AND factorable, so the
                      matrix-free path reproduces it exactly (DESIGN.md §2, O2)
- ``rbf``             exp(-||x-y||^2 / (2 sigma^2))

On top of the kind, :class:`AffinitySpec` selects the *graph construction*
policies (DESIGN.md §11):

- bandwidth: ``'fixed'`` (one global sigma) or ``'adaptive'`` — self-tuning
  local scaling where sigma_i is the distance to the ``scale_k``-th nearest
  neighbor and A_ij = exp(-d_ij^2 / (sigma_i sigma_j)) (Zelnik-Manor &
  Perona style; rbf only).
- truncation: ``knn_k=None`` keeps the dense matrix; an int zeroes every
  row entry below that row's ``knn_k``-th largest similarity (the directed
  kNN graph), which both repairs manifold datasets (two_moons) and cuts
  per-sweep cost at scale.

All kinds zero the diagonal (no self-loops), matching the PIC convention.
This module is pure jnp — the reference semantics. The Pallas realizations
live in kernels/ (two-pass build: kernels/row_topk.py computes the per-row
k-th statistics, the affinity/streaming kernels apply scale + mask in-tile).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Literal

import jax
import jax.numpy as jnp

from ..kernels.tuning import f32_matmul

AffinityKind = Literal["cosine", "cosine_shifted", "rbf"]

AFFINITY_KINDS = ("cosine", "cosine_shifted", "rbf")
BANDWIDTHS = ("fixed", "adaptive")

#: floor for adaptive local scales (duplicated points have a zero k-th
#: neighbor distance; the floor keeps sigma_i * sigma_j away from 0)
SCALE_FLOOR = 1e-6


@dataclass(frozen=True)
class AffinitySpec:
    """Everything that defines the affinity graph, in one hashable value.

    Fields:
      kind:      similarity ('cosine' | 'cosine_shifted' | 'rbf').
      sigma:     global bandwidth (read by 'rbf' with bandwidth='fixed').
      bandwidth: 'fixed' or 'adaptive' (per-row local scaling, rbf only):
                 sigma_i = distance to the scale_k-th nearest neighbor,
                 A_ij = exp(-d_ij^2 / (sigma_i sigma_j)).
      scale_k:   the neighbor rank defining the local scale ('adaptive').
      knn_k:     None = dense; an int truncates each row to entries >= its
                 knn_k-th largest similarity (zeroed in-tile, never stored).

    Instances are frozen + hashable so they ride through ``jax.jit`` static
    arguments; the same spec value drives the single-device kernels, the
    sharded stripe build, and the ppermute ring identically.
    """
    kind: AffinityKind = "cosine_shifted"
    sigma: float = 1.0
    bandwidth: str = "fixed"
    scale_k: int = 7
    knn_k: int | None = None

    def __post_init__(self):
        if self.kind not in AFFINITY_KINDS:
            raise ValueError(
                f"unknown affinity kind {self.kind!r} "
                f"(expected one of {AFFINITY_KINDS})")
        if self.bandwidth not in BANDWIDTHS:
            raise ValueError(
                f"unknown bandwidth policy {self.bandwidth!r} "
                f"(expected one of {BANDWIDTHS})")
        if not float(self.sigma) > 0.0:
            raise ValueError(
                f"sigma must be > 0 (a bandwidth), got {self.sigma}")
        if self.bandwidth == "adaptive":
            if self.kind != "rbf":
                raise ValueError(
                    "bandwidth='adaptive' rescales squared distances "
                    f"(exp(-d^2/(s_i s_j))) — rbf only, got kind={self.kind!r}")
            if int(self.scale_k) < 1:
                raise ValueError(
                    f"scale_k must be >= 1 (a neighbor rank), got {self.scale_k}")
        if self.knn_k is not None and int(self.knn_k) < 1:
            raise ValueError(
                f"knn_k must be >= 1 (a neighbor rank) or None, got {self.knn_k}")

    # -- derived policy flags (read everywhere the spec is threaded) -------

    @property
    def adaptive(self) -> bool:
        return self.bandwidth == "adaptive"

    @property
    def truncated(self) -> bool:
        return self.knn_k is not None

    @property
    def dense_fixed(self) -> bool:
        """True when the spec is the classic PR-2/PR-3 build (no pass 1):
        global bandwidth, no truncation — the bitwise-pinned default path."""
        return not (self.adaptive or self.truncated)

    @property
    def factorable(self) -> bool:
        """True when A V factors as X̂(X̂ᵀV) ± shifts (the O2 matrix-free
        path): cosine kinds only, and only without scaling/truncation."""
        return self.kind in ("cosine", "cosine_shifted") and self.dense_fixed

    def validate_for_n(self, n: int) -> None:
        """Reject neighbor ranks that don't exist among the n-1 off-diagonal
        entries of a row (the [1, n) bound of the front-door contract)."""
        if self.adaptive and not 1 <= int(self.scale_k) < n:
            raise ValueError(
                f"scale_k={self.scale_k} outside [1, n) for n={n} "
                "(each row has n-1 neighbors)")
        if self.truncated and not 1 <= int(self.knn_k) < n:
            raise ValueError(
                f"knn_k={self.knn_k} outside [1, n) for n={n} "
                "(each row has n-1 neighbors)")


def as_affinity_spec(
    spec: AffinitySpec | str | None = None,
    *,
    kind: AffinityKind = "cosine_shifted",
    sigma: float = 1.0,
) -> AffinitySpec:
    """Coerce to an :class:`AffinitySpec`.

    ``spec`` wins when given (an instance passes through; a string is a
    kind); otherwise the legacy ``kind``/``sigma`` kwargs build the dense
    fixed-bandwidth spec they always meant.
    """
    if isinstance(spec, AffinitySpec):
        return spec
    if isinstance(spec, str):
        return AffinitySpec(kind=spec, sigma=sigma)
    if spec is not None:
        raise TypeError(
            f"spec must be an AffinitySpec, a kind string, or None; "
            f"got {type(spec).__name__}")
    return AffinitySpec(kind=kind, sigma=sigma)


def row_normalize_features(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    """L2-normalize each row (unit-norm embeddings for cosine affinity)."""
    nrm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(nrm, eps)


def rbf_bandwidth_heuristic(x: jax.Array, sample: int = 512) -> jax.Array:
    """Median-pairwise-distance bandwidth estimate from a STRIDED sample.

    A leading slice (``x[:sample]``) is badly biased on sorted or
    cluster-ordered inputs — every synthetic generator in data/synthetic.py
    emits points class-by-class, so the first 512 rows can all lie in one
    cluster and the median collapses to the intra-cluster distance. The
    strided sample touches every region of the input regardless of row
    order (regression-tested in tests/test_affinity_spec.py).
    """
    n = x.shape[0]
    take = min(sample, n)
    # ceil-division stride: floor would degenerate to the leading slice
    # for sample < n < 2*sample and drop the tail whenever n/take is
    # non-integral — the stride must span the WHOLE row range
    s = x[:: max(-(-n // take), 1)][:take]
    d2 = (
        jnp.sum(s * s, axis=1)[:, None]
        + jnp.sum(s * s, axis=1)[None, :]
        - 2.0 * f32_matmul(s, s.T)
    )
    d2 = jnp.maximum(d2, 0.0)
    med = jnp.median(jnp.sqrt(d2 + jnp.eye(s.shape[0]) * 1e9))
    return jnp.maximum(med, 1e-6)


def _zero_diag(a: jax.Array) -> jax.Array:
    n = a.shape[0]
    return a * (1.0 - jnp.eye(n, dtype=a.dtype))


def pairwise_sq_dists(x: jax.Array, xc: jax.Array | None = None) -> jax.Array:
    """Dense (R, C) squared euclidean distances (clamped at 0)."""
    c = x if xc is None else xc
    sqr = jnp.sum(x * x, axis=1)
    sqc = jnp.sum(c * c, axis=1)
    return jnp.maximum(
        sqr[:, None] + sqc[None, :] - 2.0 * f32_matmul(x, c.T), 0.0)


def local_scales(x: jax.Array, scale_k: int) -> jax.Array:
    """Per-row adaptive bandwidth: sigma_i = ||x_i - x_(scale_k)|| — the
    distance to the scale_k-th nearest neighbor (self excluded), floored at
    ``SCALE_FLOOR``. Dense jnp reference for the streamed two-pass build."""
    n = x.shape[0]
    d2 = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, pairwise_sq_dists(x))
    kth = -jax.lax.top_k(-d2, scale_k)[0][:, -1]          # k-th smallest d2
    return jnp.maximum(jnp.sqrt(kth), SCALE_FLOOR)


def knn_thresholds(a: jax.Array, knn_k: int) -> jax.Array:
    """Per-row truncation threshold: the knn_k-th largest off-diagonal
    similarity of each row of the (already diagonal-zeroed) dense A."""
    n = a.shape[0]
    masked = jnp.where(jnp.eye(n, dtype=bool), -jnp.inf, a)
    return jax.lax.top_k(masked, knn_k)[0][:, -1]


@functools.partial(jax.jit, static_argnames=("kind", "spec"))
def affinity_matrix(
    x: jax.Array,
    kind: AffinityKind = "cosine_shifted",
    sigma: float | jax.Array | None = None,
    *,
    spec: AffinitySpec | None = None,
) -> jax.Array:
    """Dense (n, n) affinity matrix. Pure-jnp reference (oracle for kernels).

    ``spec`` selects the full graph-construction policy (adaptive local
    scaling, kNN truncation); the legacy ``kind``/``sigma`` arguments cover
    the dense fixed-bandwidth builds (``sigma=None`` on 'rbf' applies the
    strided median heuristic — a data-dependent value the hashable spec
    deliberately does not model).
    """
    if spec is not None:
        spec.validate_for_n(x.shape[0])
        if spec.kind in ("cosine", "cosine_shifted"):
            xn = row_normalize_features(x)
            a = f32_matmul(xn, xn.T)
            if spec.kind == "cosine_shifted":
                a = 0.5 * (1.0 + a)
        elif spec.adaptive:
            scl = local_scales(x, spec.scale_k)
            a = jnp.exp(-pairwise_sq_dists(x) / (scl[:, None] * scl[None, :]))
        else:
            a = jnp.exp(-pairwise_sq_dists(x)
                        / (2.0 * spec.sigma * spec.sigma))
        a = _zero_diag(a)
        if spec.truncated:
            thr = knn_thresholds(a, spec.knn_k)
            a = jnp.where(a >= thr[:, None], a, 0.0)
            a = _zero_diag(a)
        return a

    if kind in ("cosine", "cosine_shifted"):
        xn = row_normalize_features(x)
        a = f32_matmul(xn, xn.T)
        if kind == "cosine_shifted":
            a = 0.5 * (1.0 + a)
        return _zero_diag(a)
    if kind == "rbf":
        sig = rbf_bandwidth_heuristic(x) if sigma is None else jnp.asarray(sigma)
        sq = jnp.sum(x * x, axis=1)
        d2 = jnp.maximum(
            sq[:, None] + sq[None, :] - 2.0 * f32_matmul(x, x.T), 0.0)
        a = jnp.exp(-d2 / (2.0 * sig * sig))
        return _zero_diag(a)
    raise ValueError(f"unknown affinity kind {kind!r}")


def affinity_chunked(
    x: jax.Array,
    kind: AffinityKind = "cosine_shifted",
    sigma: float | None = None,
    chunk: int = 4096,
) -> jax.Array:
    """Row-chunked affinity build (the paper's host->device chunking analogue).

    Computes A in row-stripes so the peak temporary is (chunk, n) instead of
    (n, n) intermediates; used by the explicit path when n is large.
    """
    n = x.shape[0]
    if kind in ("cosine", "cosine_shifted"):
        x = row_normalize_features(x)
        xn = x

        def stripe(xc, i0):
            a = f32_matmul(xc, xn.T)
            if kind == "cosine_shifted":
                a = 0.5 * (1.0 + a)
            cols = jnp.arange(n)[None, :]
            rows = i0 + jnp.arange(xc.shape[0])[:, None]
            return a * (cols != rows)

    else:
        sig = rbf_bandwidth_heuristic(x) if sigma is None else jnp.asarray(sigma)
        sq = jnp.sum(x * x, axis=1)

        def stripe(xc, i0):
            sqc = jnp.sum(xc * xc, axis=1)
            d2 = jnp.maximum(
                sqc[:, None] + sq[None, :] - 2.0 * f32_matmul(xc, x.T), 0.0)
            a = jnp.exp(-d2 / (2.0 * sig * sig))
            cols = jnp.arange(n)[None, :]
            rows = i0 + jnp.arange(xc.shape[0])[:, None]
            return a * (cols != rows)

    stripe = jax.jit(stripe)
    out = []
    for i0 in range(0, n, chunk):
        out.append(stripe(x[i0 : i0 + chunk], i0))
    return jnp.concatenate(out, axis=0)


def matmat_matrix_free(
    xn: jax.Array, v: jax.Array,
    kind: AffinityKind | AffinitySpec = "cosine_shifted",
    *, psum=None,
) -> jax.Array:
    """A @ V without materializing A (DESIGN.md §2, optimization O2).

    ``v`` may be a single vector (n,) or a batch of power vectors (n, r) —
    the factored product applies per column, so all r vectors share the two
    O(n·m·r) skinny matmuls (the engine's one-sweep property, DESIGN.md §4).

    For cosine:           A V = X̂ (X̂ᵀ V) − V          (diag of X̂X̂ᵀ is 1)
    For cosine_shifted:   A V = (ΣV · 1 + X̂(X̂ᵀV))/2 − V  (diag is 1 → −1·V)
    Cost O(n·m·r) instead of O(n²·r); exact (same float ops up to
    association). ``xn`` must already be row-normalized.

    ``kind`` may be an :class:`AffinitySpec`; only factorable specs are
    accepted (adaptive scaling and kNN truncation destroy the low-rank ±
    diagonal structure the factorization rests on).

    ``psum`` finishes the cross-chunk sums when ``xn``/``v`` are the local
    row chunks of a sharded matrix (it closes over the mesh axes; the
    (m, r) block X̂ᵀV and the (r,) column sums ΣV are the ONLY values that
    cross devices — O(m r) per sweep). None means single-chunk (identity).
    The (n_loc, r) skinny product X̂ s is computed exactly once per sweep.
    """
    if isinstance(kind, AffinitySpec):
        if not kind.factorable:
            raise ValueError(
                "matrix-free path needs a factorable spec (cosine kinds, "
                f"fixed bandwidth, no truncation); got {kind}")
        kind = kind.kind
    if psum is None:
        psum = lambda x: x
    if kind == "cosine":
        return f32_matmul(xn, psum(f32_matmul(xn.T, v))) - v
    if kind == "cosine_shifted":
        vsum = psum(jnp.sum(v, axis=0))
        return 0.5 * (vsum + f32_matmul(xn, psum(f32_matmul(xn.T, v)))) - v
    raise ValueError(f"matrix-free path supports cosine affinities, got {kind!r}")


def matvec_matrix_free(
    xn: jax.Array, v: jax.Array,
    kind: AffinityKind | AffinitySpec = "cosine_shifted",
) -> jax.Array:
    """Single-vector alias of ``matmat_matrix_free`` (kept for callers)."""
    return matmat_matrix_free(xn, v, kind)


def degree_matrix_free(
    xn: jax.Array, kind: AffinityKind | AffinitySpec = "cosine_shifted"
) -> jax.Array:
    """Row sums of A (degree vector) without materializing A."""
    ones = jnp.ones((xn.shape[0],), xn.dtype)
    return matvec_matrix_free(xn, ones, kind)


# ---------------------------------------------------------------------------
# Block-index planning for truncated specs (DESIGN.md §13)
# ---------------------------------------------------------------------------

def block_plan(live: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(counts, col_idx, max_b) block-CSR plan from an (nI, nJ) live map.

    ``live`` is boolean/int: live[i, j] != 0 iff column-block j of row-block
    i holds at least one surviving affinity entry. The plan is the
    scalar-prefetch operand set of the kernels/block_sparse.py sweeps:

      counts[i]        number of live column-blocks in row-block i
      col_idx[i, :]    the live block ids in ASCENDING order first — the
                       sweep accumulates blocks in the same order the dense
                       grid visits them, which is what keeps the two paths
                       bitwise-equal; the tail holds the dead ids (any
                       valid in-range index works, skipped steps only
                       prefetch) in ascending order too
      max_b            max(counts) clamped to >= 1 — the traced grid extent

    Everything is traced (jit-safe); only the (nI, nJ) SHAPE is static.

    The stable partition is built from prefix sums (live id j lands at slot
    cumsum(live)[j]-1, dead id j at counts + cumsum(dead)[j]-1), NOT from
    ``argsort(~live, stable=True)``, although the two are value-identical:
    on jax 0.4.x CPU, a sort whose output feeds the scalar-prefetch index
    maps of an interpret-mode kernel inside ``shard_map`` miscompiles — the
    gathered ids silently degrade to the identity, which reads dead (zero)
    stripe tiles on every device whose live blocks are off-diagonal and
    collapses the power iteration onto one component (DESIGN.md §13).
    """
    live = jnp.asarray(live) != 0
    n_i, n_j = live.shape
    counts = jnp.sum(live, axis=1).astype(jnp.int32)
    csum = jnp.cumsum(live.astype(jnp.int32), axis=1)
    ids = jnp.arange(n_j, dtype=jnp.int32)[None, :]
    slot = jnp.where(live, csum - 1, counts[:, None] + ids - csum)
    col_idx = (jnp.zeros((n_i, n_j), jnp.int32)
               .at[jnp.arange(n_i)[:, None], slot]
               .set(jnp.broadcast_to(ids, (n_i, n_j))))
    max_b = jnp.maximum(jnp.max(counts), 1).astype(jnp.int32)
    return counts, col_idx, max_b


def plan_to_live(counts: jax.Array, col_idx: jax.Array) -> jax.Array:
    """Invert a block plan back to its (nI, nJ) boolean live map — the
    property-test oracle: scattering True through the first counts[i]
    entries of col_idx[i] must reproduce the map the plan came from. The
    scatter uses ``.max`` (not ``.set``) because the padded tail repeats
    dead ids with False and must not clobber a live True."""
    n_i, n_j = col_idx.shape
    slot_live = jnp.arange(n_j)[None, :] < counts[:, None]
    live = jnp.zeros((n_i, n_j), bool)
    return live.at[jnp.arange(n_i)[:, None], col_idx].max(slot_live)


def dense_block_live(a: jax.Array, tm: int, tn: int) -> jax.Array:
    """(nI, nJ) live map of a STORED truncated matrix on the (tm, tn) tile
    grid (rows/cols zero-padded up to tile multiples, so padding blocks are
    dead). The explicit engines plan from the matrix they just built;
    streaming engines use kernels/block_sparse.block_liveness instead."""
    n_rows, n_cols = a.shape
    rp = -(-n_rows // tm) * tm
    cp = -(-n_cols // tn) * tn
    ap = a
    if (rp, cp) != a.shape:
        ap = jnp.pad(a, ((0, rp - n_rows), (0, cp - n_cols)))
    tiles = ap.reshape(rp // tm, tm, cp // tn, tn)
    return jnp.any(tiles != 0, axis=(1, 3))


def invert_permutation(perm: jax.Array) -> jax.Array:
    """inv with inv[perm[i]] = i — undoes a row permutation: if the
    pipeline clustered ``x[perm]``, then ``labels[inv]`` (and every other
    per-row output indexed the same way) lines back up with the caller's
    rows (the row-reorder pass, core/graph.py). Pure index arithmetic, so
    applying it is exact: permute + inverse-permute is the identity on
    bits."""
    return jnp.argsort(perm).astype(perm.dtype)
