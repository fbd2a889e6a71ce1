"""PowerOperator builders — every GPIC scenario as one engine binding.

The convergence engine (core/power.py) is parameterized by a
:class:`~repro.core.power.PowerOperator`; this module is the ONLY place
operators are assembled (DESIGN.md §9). Local builders bind the reduction
primitives to jnp identities; sharded builders (called INSIDE a
``shard_map`` body) bind them to ``psum``/``pmax``/``all_gather`` over the
mesh axes and realize the sweep with the exact same `(op, mode)` kernel
dispatch (kernels/ops.py) the single-device path uses — bf16 A storage,
autotuned tiles, streamed tile regeneration and all.

Every builder takes an :class:`~repro.core.affinity.AffinitySpec` (legacy
``kind``/``sigma`` kwargs coerce to the dense fixed spec). Specs that need
pass-1 statistics (adaptive local scaling, kNN truncation — DESIGN.md §11)
run the streamed row-top-k reduction first:

  local builders           one self-stripe row_topk per statistic
  sharded explicit         row_topk on the local (n/P, n) stripe against
                           the gathered features; local scales are
                           all-gathered once (an O(n) collective) so the
                           column side of exp(-d²/(σᵢσⱼ)) is available
  sharded streaming ring   an extra ppermute ring sweep per statistic:
                           per-stage (n/P, n/P) row_topk partials merged
                           with ``row_topk_merge`` as the feature blocks
                           rotate — pass 1 never materializes anything
                           larger than the (n/P, k) buffer

Operator menu (entry points in core/gpic.py, core/pic.py,
core/distributed.py, front door in core/pipeline.py):

  explicit_operator            square Pallas A build + fused mat-mat sweeps
  streaming_operator           A-free: tiles regenerated inside each sweep
  matrix_free_operator         factored jnp product (factorable specs only)
  sharded_explicit_operator    per-device (n/P, n) stripe of the SAME
                               Pallas build; V replicated per sweep
  sharded_matrix_free_operator X̂ row-sharded; O(m r) collectives per sweep
  sharded_streaming_operator   row-striped features, ring-rotated col
                               blocks (ppermute): O(n·m/P) peak memory per
                               device AND all affinity specs — the
                               production configuration
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..kernels.row_topk import row_topk_merge
from ..kernels.tuning import f32_matmul
from .affinity import (
    AffinityKind,
    AffinitySpec,
    as_affinity_spec,
    block_plan,
    dense_block_live,
    matmat_matrix_free,
    row_normalize_features,
)
from .graph import affinity_stats, fused_affinity_build, scales_from_topk
from .power import PowerOperator


def _axis_tuple(axes) -> tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _ring_sweep(ring, n_stages, payload, fold, acc0, *, overlap=True):
    """Run one ring sweep: ``n_stages`` stages over a rotating payload.

    ``payload`` is a tuple of (n_loc, ...) arrays that rotate together
    around the mesh; ``fold(s, acc, *payload) -> acc`` consumes stage
    ``s``'s arriving blocks. The LAST stage is always consumed in place —
    its blocks are never rotated (a rotation after the final fold would be
    a pure-waste collective). That rule used to be re-stated per sweep;
    routing every sweep — including the 3-payload probe ring — through
    this helper enforces it structurally.

    ``overlap=True`` (default): double-buffered schedule. Each rotated
    stage issues ONE ppermute carrying the whole payload BEFORE the fold
    consumes the current blocks, so the jaxpr exposes the rotation for
    stage ``s+1`` ahead of stage ``s``'s stripe kernels and the transfer
    can be in flight while they run. Same-dtype leaves are packed into a
    single (n_loc, Σwidths) array (1-D leaves ride as one column);
    ``concatenate``/``slice`` are value-exact, so the kernels consume
    bitwise-identical blocks. Collectives per sweep: P−1, independent of
    payload arity. Mixed-dtype payloads (none today) fall back to one
    ppermute per leaf but stay double-buffered.

    ``overlap=False``: the sequential baseline — consume stage ``s``,
    then rotate each leaf with its own ppermute. len(payload)·(P−1)
    collectives per sweep; kept bitwise-identical to the historical
    schedule for parity tests and paired benchmarks.

    Both schedules move identical bytes and consume identical block
    values at identical stages, so ``_col0(s)`` stage arithmetic and
    stage-indexed fault injection target the same logical block.
    """
    payload = tuple(payload)
    if overlap:
        packable = (len(payload) > 1
                    and len({p.dtype for p in payload}) == 1)
        if packable:
            parts = [p[:, None] if p.ndim == 1 else p for p in payload]
            widths = [p.shape[1] for p in parts]
            ndims = [p.ndim for p in payload]
            packed0 = jnp.concatenate(parts, axis=1)

            def unpack(packed):
                out, off = [], 0
                for nd, w in zip(ndims, widths):
                    part = jax.lax.slice_in_dim(packed, off, off + w, axis=1)
                    out.append(part[:, 0] if nd == 1 else part)
                    off += w
                return tuple(out)
        else:
            packed0 = payload if len(payload) > 1 else payload[0]

            def unpack(packed):
                return tuple(packed) if len(payload) > 1 else (packed,)

        def stage(s, carry):
            acc, packed = carry
            # next stage's rotation is issued BEFORE this stage's fold
            nxt = (ring(packed) if packable or len(payload) == 1
                   else tuple(ring(p) for p in packed))
            acc = fold(s, acc, *unpack(packed))
            return acc, nxt

        acc, packed = jax.lax.fori_loop(0, n_stages - 1, stage,
                                        (acc0, packed0))
        return fold(n_stages - 1, acc, *unpack(packed))

    def stage(s, carry):
        acc, leaves = carry
        acc = fold(s, acc, *leaves)
        return acc, tuple(ring(p) for p in leaves)

    acc, leaves = jax.lax.fori_loop(0, n_stages - 1, stage, (acc0, payload))
    return fold(n_stages - 1, acc, *leaves)


def mesh_reductions(axes):
    """(sum, max, all_gather) bound to collectives over the mesh axes."""
    axes = _axis_tuple(axes)
    return (
        lambda x: jax.lax.psum(x, axes),
        lambda x: jax.lax.pmax(x, axes),
        lambda x: jax.lax.all_gather(x, axes, axis=0, tiled=True),
    )


def _gram_binding(use_pallas: bool):
    """The operator's local-chunk Gram: the Pallas tall-skinny kernel, or
    its jnp oracle when the caller routes everything to references. Local
    and sharded builders share this so the block algebra of the orthogonal
    embedding runs the identical kernel on both paths (DESIGN.md §10)."""
    return functools.partial(ops.gram, force_reference=not use_pallas)


# ---------------------------------------------------------------------------
# Local operators (single device / single chunk)
# ---------------------------------------------------------------------------


def _transpose_partial(a, v):
    """Aᵀ V over the stored (possibly tile-padded, zero-filled) A, for V
    with one row per logical row of A: V is zero-padded to A's storage
    rows, so the product reads the one stored copy in place. Returns the
    (storage cols, r) partial."""
    vp = jnp.pad(v.astype(jnp.float32),
                 ((0, a.shape[0] - v.shape[0]), (0, 0)))
    return f32_matmul(a.astype(jnp.float32).T, vp)


def _dense_transpose_matmat(a):
    """Local Aᵀ V binding for explicit (stored-A) operators: positivity-only
    transpose product for the symmetrized reachability probe — plain jnp,
    probe-frequency work (a handful of matvecs), never the power sweep."""
    def matmat_t(v):
        return _transpose_partial(a, v)[:v.shape[0]]
    return matmat_t


def explicit_operator(inp, *, spec: AffinitySpec | None = None,
                      kind: AffinityKind = "cosine_shifted",
                      sigma: float = 1.0, a_dtype=jnp.float32,
                      tile: int | None = None,
                      use_pallas: bool = True,
                      block_sparse: bool = True) -> PowerOperator:
    """Paper-faithful: build A once (optionally bf16-stored, O4), then
    fused degree-normalized mat-mat sweeps. ``inp`` is row-normalized
    features for the cosine kinds, raw features for rbf.

    Truncated specs with ``block_sparse=True`` (the default) take the
    one-pass fused build (core/graph.py::fused_affinity_build) and route
    every sweep through the block-CSR plan so sweep traffic tracks nnz
    (DESIGN.md §13); ``block_sparse=False`` keeps the dense-storage
    two-pass path — bitwise-equal results, the comparison baseline. Dense
    specs always take the unchanged dense path. Truncated specs also bind
    ``matmat_t`` so the component probe walks A + Aᵀ reachability."""
    spec = as_affinity_spec(spec, kind=kind, sigma=sigma)
    n, m = inp.shape
    use_bs = block_sparse and spec.truncated
    if use_bs:
        # one pinned tile resolution serves the build, the plan, and every
        # sweep — the plan's block coordinates are grid-relative, and the
        # autotuner's choice is call-shape-sensitive (kernels/ops.py)
        tm, tn = ops.resolve_tiles(n, tile, tile, m=m,
                                   a_bytes=jnp.dtype(a_dtype).itemsize)
        # a single column block can skip nothing, and its traced grid
        # lowers through a dynamic loop while the dense kernel's one-step
        # static grid inlines — a fusion difference the bitwise discipline
        # (DESIGN.md §13) forbids; degenerate grids keep the dense path
        use_bs = -(-n // tn) > 1
    if use_bs:
        scale = None
        if spec.adaptive:
            scale = scales_from_topk(ops.row_topk(
                inp, k=spec.scale_k, stat="neg_sqdist", spec=spec,
                tm=tile, tn=tile, force_reference=not use_pallas))
        a, d, _thr = fused_affinity_build(
            inp, spec=spec, scale_r=scale, scale_c=scale, tm=tm, tn=tn,
            use_pallas=use_pallas, a_dtype=a_dtype)
        counts, col_idx, max_b = block_plan(dense_block_live(a, tm, tn))

        def matmat(v):
            return ops.block_sparse_matmat(
                a, v, d, counts, col_idx, max_b, tm=tm, tn=tn,
                force_reference=not use_pallas)

        return PowerOperator(matmat=matmat, degree=d,
                             gram=_gram_binding(use_pallas),
                             matmat_t=_dense_transpose_matmat(a))

    scale, thr = affinity_stats(inp, spec, tile=tile, use_pallas=use_pallas)
    a, d = ops.affinity_and_degree(
        inp, spec=spec, scale_r=scale, scale_c=scale, thr=thr,
        tm=tile, tn=tile, out_dtype=a_dtype,
        force_reference=not use_pallas,
    )

    def matmat(v):
        return ops.degree_normalized_matmat(
            a, v, d, tm=tile, tn=tile, force_reference=not use_pallas)

    return PowerOperator(matmat=matmat, degree=d,
                         gram=_gram_binding(use_pallas),
                         matmat_t=(_dense_transpose_matmat(a)
                                   if spec.truncated else None))


def streaming_operator(inp, *, spec: AffinitySpec | None = None,
                       kind: AffinityKind = "cosine_shifted",
                       sigma: float = 1.0, tile: int | None = None,
                       use_pallas: bool = True,
                       block_sparse: bool = True) -> PowerOperator:
    """A-free: affinity tiles are regenerated from the feature slabs inside
    every power step (DESIGN.md §5). All specs incl. adaptive/kNN rbf;
    peak memory O(n m + n r + n k), no (n, n) allocation ever — pass 1
    streams through the row-top-k kernel.

    Truncated specs with ``block_sparse=True`` pay one extra A-free
    liveness pass at build time (kernels/block_sparse.block_liveness) and
    then regenerate ONLY the live feature tiles in every sweep — same
    bitwise results as the dense-grid streaming sweep, nnz-scaled grid
    steps (DESIGN.md §13). Truncated specs bind ``matmat_t`` (the
    column-thresholded streaming stripe — still A-free) so the component
    probe walks A + Aᵀ reachability."""
    spec = as_affinity_spec(spec, kind=kind, sigma=sigma)
    n, m = inp.shape
    scale, thr = affinity_stats(inp, spec, tile=tile, use_pallas=use_pallas)

    matmat_t = None
    if spec.truncated:
        def matmat_t(v):
            return ops.streaming_matmat(
                inp, v, None, spec=spec, scale_r=scale, scale_c=scale,
                thr=None, thr_c=thr, tm=tile, tn=tile,
                force_reference=not use_pallas)

    use_bs = block_sparse and spec.truncated
    if use_bs:
        tm, tn = ops.resolve_tiles(n, tile, tile, m=m)
        # degenerate single-column-block grids keep the dense-grid kernel
        # (see explicit_operator — same bitwise-discipline rationale)
        use_bs = -(-n // tn) > 1
    if use_bs:
        live = ops.block_liveness(
            inp, spec=spec, scale_r=scale, scale_c=scale, thr=thr,
            tm=tm, tn=tn, force_reference=not use_pallas)
        counts, col_idx, max_b = block_plan(live)
        d = ops.block_sparse_streaming_degree(
            inp, counts=counts, col_idx=col_idx, max_b=max_b,
            spec=spec, scale_r=scale, scale_c=scale, thr=thr,
            tm=tm, tn=tn, force_reference=not use_pallas)

        def matmat(v):
            return ops.block_sparse_streaming_matmat(
                inp, v, d, counts=counts, col_idx=col_idx, max_b=max_b,
                spec=spec, scale_r=scale, scale_c=scale, thr=thr,
                tm=tm, tn=tn, force_reference=not use_pallas)

        return PowerOperator(matmat=matmat, degree=d,
                             gram=_gram_binding(use_pallas),
                             matmat_t=matmat_t)

    d = ops.streaming_degree(
        inp, spec=spec, scale_r=scale, scale_c=scale, thr=thr,
        tm=tile, tn=tile, force_reference=not use_pallas,
    )

    def matmat(v):
        return ops.streaming_matmat(
            inp, v, d, spec=spec, scale_r=scale, scale_c=scale, thr=thr,
            tm=tile, tn=tile, force_reference=not use_pallas,
        )

    return PowerOperator(matmat=matmat, degree=d,
                         gram=_gram_binding(use_pallas),
                         matmat_t=matmat_t)


def matrix_free_operator(xn, *, spec: AffinitySpec | None = None,
                         kind: AffinityKind = "cosine_shifted",
                         use_pallas: bool = True) -> PowerOperator:
    """Factored jnp product A V = f(X̂(X̂ᵀV)) − V (O2): O(n·m·r) per sweep,
    factorable specs only (cosine kinds, no scaling/truncation — the
    rejection lives in ``matmat_matrix_free``). ``xn`` must be
    row-normalized. The sweep has no Pallas realization; ``use_pallas``
    governs the Gram binding only."""
    spec = as_affinity_spec(spec, kind=kind)
    n = xn.shape[0]
    d = matmat_matrix_free(xn, jnp.ones((n,), xn.dtype), spec)

    def matmat(v):
        return matmat_matrix_free(xn, v, spec) / jnp.maximum(d, 1e-30)[:, None]

    return PowerOperator(matmat=matmat, degree=d,
                         gram=_gram_binding(use_pallas))


# ---------------------------------------------------------------------------
# Sharded operators (call INSIDE a shard_map body; x_loc is the device's
# row block of the global (n, m) feature matrix)
# ---------------------------------------------------------------------------


def sharded_explicit_operator(x_loc, *, axes,
                              spec: AffinitySpec | None = None,
                              kind: AffinityKind = "cosine_shifted",
                              sigma: float = 1.0, a_dtype=jnp.float32,
                              fold_shift: bool = False,
                              tile: int | None = None,
                              use_pallas: bool = True,
                              block_sparse: bool = True) -> PowerOperator:
    """Per-device (n/P, n) stripe of the Pallas affinity build; V is
    replicated per sweep via all-gather (O(n r) bytes/step against
    O(n²/P) local compute — collective-light).

    Non-dense specs run pass 1 on the stripe: the local block's row-top-k
    against the gathered features (same tile program as the single-device
    pass, so the statistics match it bitwise), with the adaptive scales
    all-gathered once for the column side of the build.

    ``fold_shift`` (O5, cosine_shifted only) stores the stripe as RAW
    masked cosine (the (1+a)/2 transform never touches the O(n²/P) array)
    and folds the shift into an O(n_loc r) epilogue:
    (A V)_i = (ΣV − v_i + (A_cos V)_i)/2, d_i = (n − 1 + d_cos,i)/2.
    Folding is a storage-algebra trick on the DENSE matrix — a truncated
    row has no closed-form shift mass — so it requires a dense fixed spec.

    Truncated specs with ``block_sparse=True`` take the fused one-pass
    stripe build (thresholds from the stripe's own unmasked scores — the
    full row is present, so the epilogue statistic equals the streamed
    pass-1b bitwise) and block-CSR sweeps over the stripe's live tiles;
    they also bind ``matmat_t`` (psum of the local stripe's transpose
    partials) for the symmetrized component probe (DESIGN.md §13).
    """
    spec = as_affinity_spec(spec, kind=kind, sigma=sigma)
    if fold_shift and not spec.dense_fixed:
        raise ValueError(
            "fold_shift (O5) rewrites the dense shift algebra; it cannot "
            f"be combined with adaptive/truncated specs (got {spec})")
    psum, pmax, gather = mesh_reductions(axes)
    idx = jax.lax.axis_index(_axis_tuple(axes))
    n_loc = x_loc.shape[0]
    row0 = idx * n_loc
    if spec.kind != "rbf":
        x_loc = row_normalize_features(x_loc)
    x_full = gather(x_loc)
    n = x_full.shape[0]

    scale_loc = scale_full = thr_loc = None
    if spec.adaptive:
        nk = ops.row_topk(
            x_loc, x_full, k=spec.scale_k, stat="neg_sqdist", spec=spec,
            tm=tile, tn=tile, row_offset=row0,
            force_reference=not use_pallas)
        scale_loc = scales_from_topk(nk)
        scale_full = gather(scale_loc)

    def _stripe_matmat_t(a_loc):
        """Aᵀ V local chunk from the stored (n_loc, n) stripe: each device
        contributes its stripe's transpose partial, psum completes the
        column sums, and the local rows are sliced back out. Positivity-
        only probe work — the O(n r) collective runs a handful of times."""
        def matmat_t(v_loc):
            part = _transpose_partial(a_loc, v_loc)
            return jax.lax.dynamic_slice_in_dim(psum(part), row0, n_loc)
        return matmat_t

    use_bs = block_sparse and spec.truncated
    if use_bs:
        tm, tn = ops.resolve_tiles(n, tile, tile, m=x_loc.shape[1],
                                   a_bytes=jnp.dtype(a_dtype).itemsize)
        # degenerate single-column-block grids keep the dense-grid kernel
        # (see explicit_operator — same bitwise-discipline rationale)
        use_bs = -(-n // tn) > 1
    if use_bs:
        a_loc, d_loc, thr_loc = fused_affinity_build(
            x_loc, x_full, spec=spec, scale_r=scale_loc, scale_c=scale_full,
            tm=tm, tn=tn, use_pallas=use_pallas, a_dtype=a_dtype,
            row_offset=row0)
        counts, col_idx, max_b = block_plan(dense_block_live(a_loc, tm, tn))

        def matmat(v_loc):
            v_full = gather(v_loc)
            return ops.block_sparse_matmat(
                a_loc, v_full, d_loc, counts, col_idx, max_b, tm=tm, tn=tn,
                force_reference=not use_pallas)

        return PowerOperator(matmat=matmat, degree=d_loc,
                             sum=psum, max=pmax, all_gather=gather,
                             gram=_gram_binding(use_pallas),
                             matmat_t=_stripe_matmat_t(a_loc))

    if spec.truncated:
        tk = ops.row_topk(
            x_loc, x_full, k=spec.knn_k, stat="similarity", spec=spec,
            scale_r=scale_loc, scale_c=scale_full,
            tm=tile, tn=tile, row_offset=row0,
            force_reference=not use_pallas)
        thr_loc = tk[:, -1]

    fold = fold_shift and spec.kind == "cosine_shifted"
    build_kind = "cosine" if fold else spec.kind
    a_loc, d_raw = ops.affinity_and_degree(
        x_loc, x_full, kind=build_kind, sigma=spec.sigma,
        scale_r=scale_loc, scale_c=scale_full, thr=thr_loc,
        tm=tile, tn=tile, out_dtype=a_dtype, row_offset=row0,
        force_reference=not use_pallas,
    )

    if fold:
        d_loc = 0.5 * (n - 1.0 + d_raw)
        ones = jnp.ones((n_loc,), jnp.float32)

        def matmat(v_loc):
            v_full = gather(v_loc)
            raw = ops.degree_normalized_matmat(     # (A_cos V) stripe, d=1
                a_loc, v_full, ones, tm=tile, tn=tile,
                force_reference=not use_pallas)
            sv = jnp.sum(v_full, axis=0)            # (r,) — V is replicated
            av = 0.5 * (sv[None, :] + raw - v_loc)
            return av / jnp.maximum(d_loc, 1e-30)[:, None]

    else:
        d_loc = d_raw

        def matmat(v_loc):
            v_full = gather(v_loc)
            return ops.degree_normalized_matmat(
                a_loc, v_full, d_loc, tm=tile, tn=tile,
                force_reference=not use_pallas)

    return PowerOperator(matmat=matmat, degree=d_loc,
                         sum=psum, max=pmax, all_gather=gather,
                         gram=_gram_binding(use_pallas),
                         matmat_t=(_stripe_matmat_t(a_loc)
                                   if spec.truncated else None))


def sharded_matrix_free_operator(x_loc, *, axes,
                                 spec: AffinitySpec | None = None,
                                 kind: AffinityKind = "cosine_shifted",
                                 use_pallas: bool = True) -> PowerOperator:
    """X̂ row-sharded factored product: per sweep one psum of an (m, r)
    block and one (r,) psum — O(m r) collectives, the configuration that
    scales to thousands of nodes. Factorable specs only (they factor)."""
    spec = as_affinity_spec(spec, kind=kind)
    psum, pmax, gather = mesh_reductions(axes)
    n_loc = x_loc.shape[0]
    xn_loc = row_normalize_features(x_loc)
    d_loc = matmat_matrix_free(
        xn_loc, jnp.ones((n_loc,), xn_loc.dtype), spec, psum=psum)

    def matmat(v_loc):
        av = matmat_matrix_free(xn_loc, v_loc, spec, psum=psum)
        return av / jnp.maximum(d_loc, 1e-30)[:, None]

    return PowerOperator(matmat=matmat, degree=d_loc,
                         sum=psum, max=pmax, all_gather=gather,
                         gram=_gram_binding(use_pallas))


def sharded_streaming_operator(x_loc, *, axes, mesh_size: int,
                               spec: AffinitySpec | None = None,
                               kind: AffinityKind = "cosine_shifted",
                               sigma: float = 1.0, tile: int | None = None,
                               use_pallas: bool = True,
                               block_sparse: bool = True,
                               overlap: bool = True,
                               inject_fault: tuple | None = None
                               ) -> PowerOperator:
    """Row-striped A-free engine: each sweep ring-rotates the (n/P, m)
    feature blocks (and the matching V blocks) around the mesh with
    ``ppermute``; every stage regenerates the (n/P, n/P) affinity stripe
    tiles on the fly and accumulates the partial product. Features are
    never gathered: peak per-device memory is O(n·m/P + n·r/P) — and the
    tile transform is elementwise, so EVERY affinity spec works (rbf,
    adaptive scaling and kNN truncation included). This is the production
    configuration: the only one that is simultaneously A-free, fully
    sharded, and all-specs (DESIGN.md §9, §11).

    Pass 1 for non-dense specs runs as extra ppermute ring sweeps BEFORE
    the degree sweep: per stage the row-top-k kernel scores the local rows
    against the block that just arrived and ``row_topk_merge`` folds the
    (n/P, k) partial into the running buffer — order-independent, so the
    statistics equal the single-device pass bitwise. The adaptive scales
    are then all-gathered once (an (n,) vector — negligible against the
    O(n·m/P) block budget) so every later stage can slice its column
    block's scales without a second ring.

    ``mesh_size`` is the static number of devices P spanned by ``axes``
    (ring length). Collectives per mat-mat sweep: P−1 ppermutes — under
    ``overlap=True`` (the default) the feature and V blocks ride ONE
    packed rotation per stage (``_ring_sweep``), each moving the
    (n/P, m+r) stage payload, i.e. O(n(m+r)/P) bytes per stage and
    O(n(m+r)) total per device — the all-gather-equivalent byte count,
    but with O(n m / P) residency instead of O(n m). Every stage issues
    the next rotation BEFORE consuming the arriving blocks (double
    buffering), so on hardware the transfer overlaps the stripe kernels;
    the last stage is consumed in place and never rotated.
    ``overlap=False`` keeps the historical split schedule — one ppermute
    per payload leaf AFTER each stage's compute (2(P−1) per mat-mat
    sweep, 3(P−1) for the probe ring) — as the paired bitwise baseline;
    both schedules produce bitwise-identical results.

    Truncated specs with ``block_sparse=True`` pay ONE extra liveness ring
    at build time: each stage emits its (nI, nJ) live-block map (A-free,
    kernels/block_sparse.block_liveness) into a stacked (P, nI, nJ) plan
    ring, and every later degree/mat-mat stage runs the block-sparse
    streaming kernel over stage ``s``'s slice of the stacked plan. The
    traced ``max_b`` grid bound is the MAX over all stages, so the stage
    launch is loop-invariant and one compiled kernel serves the whole
    ring (DESIGN.md §13). Bitwise-equal to the dense-grid ring. Truncated
    specs also bind ``matmat_t`` for the symmetrized component probe: a
    third ring rotating (features, V, thr) together, each stage computing
    the column-thresholded stripe (``thr_c`` — the arriving block's OWN
    row thresholds applied on the column side; exact because tile scores
    are bitwise symmetric) so the partials sum to the local rows of Aᵀ V
    without ever materializing A.

    ``inject_fault`` (static; fault-injection harness only, DESIGN.md §12)
    corrupts one mat-mat ring stage: ``("ring_nan", s)`` poisons the V
    block consumed at stage ``s`` of every sweep with NaN — a simulated
    transient interconnect corruption the power loop's non-finite latches
    must detect and contain.
    """
    if inject_fault is not None and (
            len(inject_fault) != 2 or inject_fault[0] != "ring_nan"
            or not 0 <= int(inject_fault[1]) < mesh_size):
        raise ValueError(
            f"inject_fault must be ('ring_nan', stage<{mesh_size}), got "
            f"{inject_fault!r}")
    spec = as_affinity_spec(spec, kind=kind, sigma=sigma)
    psum, pmax, gather = mesh_reductions(axes)
    axes_t = _axis_tuple(axes)
    idx = jax.lax.axis_index(axes_t)
    n_loc = x_loc.shape[0]
    row0 = idx * n_loc
    if spec.kind != "rbf":
        x_loc = row_normalize_features(x_loc)
    perm = [(i, (i - 1) % mesh_size) for i in range(mesh_size)]

    def ring(x):
        return jax.lax.ppermute(x, axes_t, perm)

    def _col0(s):
        return ((idx + s) % mesh_size) * n_loc

    # every sweep (top-k pass 1, liveness, degrees, mat-mat, probe) runs
    # through _ring_sweep: P-1 rotated stages, the last stage consumed in
    # place, and under overlap=True one packed in-flight rotation per stage

    def sweep(payload, fold, acc0):
        return _ring_sweep(ring, mesh_size, payload, fold, acc0,
                           overlap=overlap)

    def topk_ring_sweep(k, stat, scale_full):
        """(n_loc, k) merged top-k of the local rows vs every ring block."""
        def partial(s, x_ring):
            scl_c = (None if scale_full is None else
                     jax.lax.dynamic_slice_in_dim(
                         scale_full, _col0(s), n_loc))
            return ops.row_topk(
                x_loc, x_ring, k=k, stat=stat, spec=spec,
                scale_r=None if scale_full is None else scale_loc,
                scale_c=scl_c, tm=tile, tn=tile,
                row_offset=row0, col_offset=_col0(s),
                force_reference=not use_pallas)

        def fold(s, buf, x_ring):
            return row_topk_merge(buf, partial(s, x_ring), k)
        buf0 = jnp.full((n_loc, k), -jnp.inf, jnp.float32)
        return sweep((x_loc,), fold, buf0)

    scale_loc = scale_full = thr_loc = None
    if spec.adaptive:
        scale_loc = scales_from_topk(
            topk_ring_sweep(spec.scale_k, "neg_sqdist", None))
        scale_full = gather(scale_loc)
    if spec.truncated:
        thr_loc = topk_ring_sweep(
            spec.knn_k, "similarity", scale_full)[:, -1]

    def _stage_scales(s):
        if scale_full is None:
            return None, None
        return scale_loc, jax.lax.dynamic_slice_in_dim(
            scale_full, _col0(s), n_loc)

    matmat_t = None
    if spec.truncated:
        def matmat_t(v_loc):
            # ring Aᵀ V: rotate (features, V, thr) together; the arriving
            # block's own row thresholds mask the stripe on the COLUMN side
            # (thr_c), so each stage's tile (i, j) equals A[c0+j, r0+i] —
            # tile scores are bitwise symmetric — and the stage partials
            # sum to the local rows of Aᵀ V. Unnormalized (probe-only).
            def partial(s, x_ring, v_ring, thr_ring):
                scl_r, scl_c = _stage_scales(s)
                return ops.streaming_matmat(
                    x_loc, v_ring, None, x_ring, spec=spec,
                    scale_r=scl_r, scale_c=scl_c, thr=None, thr_c=thr_ring,
                    tm=tile, tn=tile, row_offset=row0, col_offset=_col0(s),
                    force_reference=not use_pallas)

            def fold(s, u, x_ring, v_ring, thr_ring):
                return u + partial(s, x_ring, v_ring, thr_ring)
            u0 = jnp.zeros((n_loc, v_loc.shape[1]), jnp.float32)
            return sweep((x_loc, v_loc.astype(jnp.float32), thr_loc),
                         fold, u0)

    use_bs = block_sparse and spec.truncated
    if use_bs:
        tm, tn = ops.resolve_tiles(n_loc, tile, tile, m=x_loc.shape[1])
        # degenerate single-column-block stage grids keep the dense-grid
        # ring (see explicit_operator — same bitwise-discipline rationale)
        use_bs = -(-n_loc // tn) > 1
    if use_bs:

        def liveness_ring():
            def partial(s, x_ring):
                scl_r, scl_c = _stage_scales(s)
                return ops.block_liveness(
                    x_loc, x_ring, spec=spec, scale_r=scl_r, scale_c=scl_c,
                    thr=thr_loc, tm=tm, tn=tn,
                    row_offset=row0, col_offset=_col0(s),
                    force_reference=not use_pallas)

            def fold(s, acc, x_ring):
                return jax.lax.dynamic_update_index_in_dim(
                    acc, partial(s, x_ring), s, axis=0)
            n_i = -(-n_loc // tm)
            n_j = -(-n_loc // tn)
            return sweep((x_loc,), fold,
                         jnp.zeros((mesh_size, n_i, n_j), jnp.int32))

        # stacked (P, nI, nJ) plan ring; max_b is the global max so the
        # per-stage kernel launch is loop-invariant (one compiled program)
        counts_all, col_idx_all, max_bs = jax.vmap(block_plan)(
            liveness_ring())
        max_b = jnp.max(max_bs)

        def degree_sweep_bs():
            def partial(s, x_ring):
                scl_r, scl_c = _stage_scales(s)
                return ops.block_sparse_streaming_degree(
                    x_loc, x_ring, counts=counts_all[s],
                    col_idx=col_idx_all[s], max_b=max_b,
                    spec=spec, scale_r=scl_r, scale_c=scl_c,
                    thr=thr_loc, tm=tm, tn=tn,
                    row_offset=row0, col_offset=_col0(s),
                    force_reference=not use_pallas)

            def fold(s, d, x_ring):
                return d + partial(s, x_ring)
            return sweep((x_loc,), fold, jnp.zeros((n_loc,), jnp.float32))

        d_loc = degree_sweep_bs()

        def matmat(v_loc):
            def partial(s, x_ring, v_ring):
                if inject_fault is not None:
                    v_ring = jnp.where(s == int(inject_fault[1]),
                                       jnp.float32(jnp.nan), v_ring)
                scl_r, scl_c = _stage_scales(s)
                return ops.block_sparse_streaming_matmat(
                    x_loc, v_ring, None, x_ring, counts=counts_all[s],
                    col_idx=col_idx_all[s], max_b=max_b,
                    spec=spec, scale_r=scl_r, scale_c=scl_c, thr=thr_loc,
                    tm=tm, tn=tn, row_offset=row0, col_offset=_col0(s),
                    force_reference=not use_pallas)

            def fold(s, u, x_ring, v_ring):
                return u + partial(s, x_ring, v_ring)
            u0 = jnp.zeros((n_loc, v_loc.shape[1]), jnp.float32)
            u = sweep((x_loc, v_loc.astype(jnp.float32)), fold, u0)
            return u / jnp.maximum(d_loc, 1e-30)[:, None]

        return PowerOperator(matmat=matmat, degree=d_loc,
                             sum=psum, max=pmax, all_gather=gather,
                             gram=_gram_binding(use_pallas),
                             matmat_t=matmat_t)

    def degree_sweep():
        def partial(s, x_ring):
            scl_r, scl_c = _stage_scales(s)
            return ops.streaming_degree(
                x_loc, x_ring, spec=spec, scale_r=scl_r, scale_c=scl_c,
                thr=thr_loc, tm=tile, tn=tile,
                row_offset=row0, col_offset=_col0(s),
                force_reference=not use_pallas)

        def fold(s, d, x_ring):
            return d + partial(s, x_ring)
        return sweep((x_loc,), fold, jnp.zeros((n_loc,), jnp.float32))

    d_loc = degree_sweep()

    def matmat(v_loc):
        def partial(s, x_ring, v_ring):
            if inject_fault is not None:
                # poison only the block CONSUMED at the faulted stage (the
                # rotating carry stays clean — a transient corruption, not
                # a persistently dead link)
                v_ring = jnp.where(s == int(inject_fault[1]),
                                   jnp.float32(jnp.nan), v_ring)
            scl_r, scl_c = _stage_scales(s)
            return ops.streaming_matmat(
                x_loc, v_ring, None, x_ring, spec=spec,
                scale_r=scl_r, scale_c=scl_c, thr=thr_loc,
                tm=tile, tn=tile, row_offset=row0, col_offset=_col0(s),
                force_reference=not use_pallas)

        def fold(s, u, x_ring, v_ring):
            return u + partial(s, x_ring, v_ring)
        u0 = jnp.zeros((n_loc, v_loc.shape[1]), jnp.float32)
        u = sweep((x_loc, v_loc.astype(jnp.float32)), fold, u0)
        return u / jnp.maximum(d_loc, 1e-30)[:, None]

    return PowerOperator(matmat=matmat, degree=d_loc,
                         sum=psum, max=pmax, all_gather=gather,
                         gram=_gram_binding(use_pallas),
                         matmat_t=matmat_t)
