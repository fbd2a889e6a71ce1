"""Two-pass affinity-graph construction (pass 1) over the kernel registry.

This module turns an :class:`~repro.core.affinity.AffinitySpec` into the
per-row statistic arrays the pass-2 kernels consume (DESIGN.md §11):

  pass 1a  adaptive local scales   sigma_i = ||x_i - x_(scale_k)||
           from the streamed row-top-k of -d² (stat='neg_sqdist')
  pass 1b  truncation thresholds   tau_i = row's knn_k-th largest
           similarity (stat='similarity', adaptive scales applied)

Both passes stream through ``kernels.ops.row_topk`` — no (n, n) array is
ever allocated, so the A-free engines keep their O(n·m) residency. The
dense default spec skips pass 1 entirely (``affinity_stats`` returns
(None, None)) and pass 2 compiles the exact PR-3 kernels.

Sharded callers reuse :func:`scales_from_topk` on their stripe/ring
top-k reductions (core/operators.py); the dense jnp oracles live in
core/affinity.py (``local_scales`` / ``knn_thresholds``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..kernels.row_topk import topk_thresholds_from_scores
from .affinity import SCALE_FLOOR, AffinitySpec


def scales_from_topk(neg_sqdist_topk: jax.Array) -> jax.Array:
    """(R,) adaptive local scales from an (R, k) neg-sq-dist top-k buffer:
    sigma_i = sqrt(k-th smallest d²), floored at ``SCALE_FLOOR`` so
    duplicated points cannot zero the sigma_i * sigma_j denominator."""
    kth = jnp.maximum(-neg_sqdist_topk[:, -1], 0.0)
    return jnp.maximum(jnp.sqrt(kth), SCALE_FLOOR)


def affinity_stats(
    x: jax.Array,
    spec: AffinitySpec,
    *,
    tile: int | None = None,
    use_pallas: bool = True,
) -> tuple[jax.Array | None, jax.Array | None]:
    """(scale, thr) pass-1 statistics for the square self-affinity of ``x``.

    Either entry is None when the spec does not need it; the dense
    fixed-bandwidth default returns (None, None) without launching
    anything — keeping the default build bitwise-pinned to PR 3.
    """
    scale = thr = None
    if spec.adaptive:
        nk = ops.row_topk(
            x, k=spec.scale_k, stat="neg_sqdist", spec=spec,
            tm=tile, tn=tile, force_reference=not use_pallas)
        scale = scales_from_topk(nk)
    if spec.truncated:
        tk = ops.row_topk(
            x, k=spec.knn_k, stat="similarity", spec=spec,
            scale_r=scale, scale_c=scale,
            tm=tile, tn=tile, force_reference=not use_pallas)
        thr = tk[:, -1]
    return scale, thr


def fused_affinity_build(
    x: jax.Array,
    xc: jax.Array | None = None,
    *,
    spec: AffinitySpec,
    scale_r: jax.Array | None = None,
    scale_c: jax.Array | None = None,
    tm: int | None = None,
    tn: int | None = None,
    use_pallas: bool = True,
    a_dtype=jnp.float32,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(A, D, thr) one-pass truncated build for the explicit engines — A at
    its zero-padded storage shape (as ``ops.affinity_and_degree`` returns it)
    (DESIGN.md §13) — replaces pass 1b + the masked rebuild with ONE sweep
    over the feature blocks plus cheap epilogues:

      1. build the stripe UNMASKED at f32 (the similarity pass the old
         row-top-k kernel re-did is the build itself)
      2. thr from ``topk_thresholds_from_scores`` — bitwise-equal to the
         streamed pass-1b statistic (shared tile transform + exact order
         statistic, both value-selecting)
      3. elementwise re-mask ``a >= thr[:, None]`` — bitwise-equal to the
         in-tile mask of the old rebuild (same f32 values, same compare),
         then cast to the storage dtype (same rounding the kernel applies)
      4. degrees by replaying the build kernel's fused RowSum on the
         masked f32 stripe: one ``jnp.sum(axis=1)`` per (·, tn) tile
         column (the kernel's per-tile VPU row sum on the same values)
         accumulated left-to-right in tile order (the kernel's sequential
         ``+=`` across the grid) — bitwise-equal to the old two-pass
         build's degrees (and to the streaming engines', the cross-engine
         discipline) WITHOUT re-scoring the features in a second kernel
         sweep

    The old two-pass path (``affinity_stats`` + masked build) remains the
    ``block_sparse=False`` route of the operators; this function is
    bitwise-equal to it, asserted in tests/test_block_sparse.py.

    Adaptive scales stay a caller concern (they come from the neg-sq-dist
    pass, which has no build to fuse into). Callers resolve (tm, tn) once
    and reuse them for the block plan and every sweep.
    """
    assert spec.truncated, "fused_affinity_build is the truncated-spec build"
    n_rows = x.shape[0]
    n_cols = n_rows if xc is None else xc.shape[0]
    # A at its tile-padded storage shape (pad entries exact zeros): the
    # sweeps consume it in place, so the engine stores one copy
    a_raw, _ = ops.affinity_and_degree(
        x, xc, spec=spec, scale_r=scale_r, scale_c=scale_c, thr=None,
        tm=tm, tn=tn, out_dtype=jnp.float32,
        row_offset=row_offset, col_offset=col_offset,
        force_reference=not use_pallas,
    )
    thr = topk_thresholds_from_scores(
        a_raw[:n_rows, :n_cols], k=spec.knn_k,
        row_offset=row_offset, col_offset=col_offset)
    thr_p = jnp.pad(thr, (0, a_raw.shape[0] - n_rows),
                    constant_values=jnp.inf)
    a_f32 = jnp.where(a_raw >= thr_p[:, None], a_raw, 0.0)
    _, tn_r = ops.resolve_tiles(
        n_cols, tm, tn, m=x.shape[1],
        a_bytes=jnp.dtype(jnp.float32).itemsize)
    cp = -(-a_f32.shape[1] // tn_r) * tn_r
    ap = jnp.pad(a_f32, ((0, 0), (0, cp - a_f32.shape[1])))
    d = jnp.sum(ap[:, :tn_r], axis=1)
    for j in range(1, cp // tn_r):
        d = d + jnp.sum(ap[:, j * tn_r:(j + 1) * tn_r], axis=1)
    return a_f32.astype(a_dtype), d[:n_rows], thr


# ---------------------------------------------------------------------------
# Graph-aware row reordering (DESIGN.md §15)
# ---------------------------------------------------------------------------
#
# The block-sparse plan (§13) only skips a tile when truncation kills ALL
# tm×tn entries at once, so its wins depend on neighboring rows sitting in
# the same tile rows — true for cluster-sorted benchmarks, destroyed by a
# row shuffle of the same data. The reorder pass canonicalizes: it computes
# a permutation that is a function of row CONTENT only, clusters x[perm],
# and inverse-permutes the outputs. Because any two row orderings of the
# same multiset map to the IDENTICAL canonical array, the entire pipeline
# (pass 1, plan, sweeps, k-means) runs on bitwise-identical inputs — so
# shuffled and unshuffled runs return bitwise-identical (re-aligned)
# results, and the plan sees tile-local neighborhoods regardless of how
# the caller's rows arrived.


def content_row_score(x: jax.Array) -> jax.Array:
    """(n,) per-row ordering score that depends on row content only.

    Squared distance to the per-column median. Both ingredients are
    exactly permutation-invariant in f32: ``jnp.median`` sorts each
    column (the result is determined by the value multiset), and the
    per-row sum runs over the fixed column axis. Rows land on a 1-D
    radial profile of the dataset — nearby rows usually score nearby —
    which is what the tile grid needs; ties (e.g. duplicated rows) are
    exchangeable, so any tie-break still yields the same canonical
    array."""
    med = jnp.median(x.astype(jnp.float32), axis=0)
    return jnp.sum((x.astype(jnp.float32) - med) ** 2, axis=1)


def reorder_permutation(score: jax.Array,
                        components: jax.Array | None = None,
                        *, max_components: int = 16) -> jax.Array:
    """The canonical row permutation from content scores (+ optional
    graph components).

    Without ``components``: a stable argsort of ``score`` — the dense-spec
    ordering.

    With ``components`` (ids from the component probe, −1 = never
    reached): rows group by component first — the probe's weakly-connected
    components are exactly the sets truncation separates, so grouping them
    makes whole tiles dead — and order by score inside each group.
    Component IDS follow probe seeding order (input-order-dependent), so
    each group is keyed by its MIN member score instead: the partition is
    content-only when the probe converges (core/health.py), min-score is
    content-only given the partition, hence the permutation stays
    canonical. Unreached rows (id −1) sort after every group, by score.
    """
    score = score.astype(jnp.float32)
    if components is None:
        return jnp.argsort(score, stable=True)
    comp_min = jax.ops.segment_min(score, components + 1,
                                   num_segments=max_components + 2)
    group_key = jnp.where(components < 0, jnp.inf, comp_min[components + 1])
    return jnp.lexsort((score, group_key))


def graph_reorder_permutation(x: jax.Array, spec: AffinitySpec, *,
                              tile: int | None = None,
                              use_pallas: bool = True,
                              max_components: int = 16) -> jax.Array:
    """Single-device reorder pass: content scores, plus the component
    probe's grouping for truncated specs. The probe runs on the DENSE-GRID
    streaming operator (A-free, O(n·m) residency): the block-sparse plan
    is what the reorder exists for, so the permutation must never depend
    on it. Dense specs skip the probe — every row is one weak component.
    The mesh front door realizes the same probe via
    core/distributed.py::distributed_component_ids and calls
    ``reorder_permutation`` directly."""
    score = content_row_score(x)
    if not spec.truncated:
        return reorder_permutation(score)
    # lazy: operators/health import this module at import time
    from .health import graph_component_probe
    from .operators import streaming_operator
    op = streaming_operator(x, spec=spec, tile=tile, use_pallas=use_pallas,
                            block_sparse=False)
    _, comp = graph_component_probe(op, x.shape[0],
                                    max_components=max_components)
    return reorder_permutation(score, comp, max_components=max_components)
