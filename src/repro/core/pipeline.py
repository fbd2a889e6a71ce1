"""The GPIC front door: one config dataclass, one entry point.

Every scenario the repo supports — local or sharded, explicit / streaming /
matrix-free, any affinity kind, any number of power vectors — is a field
combination on :class:`GPICConfig`; :func:`run_gpic` routes it to the right
operator-backed entry point. Examples, benchmarks, and launch/ call this
instead of hand-assembling keyword lists against five functions.

    from repro.core import GPICConfig, run_gpic

    # single device, paper-faithful
    res = run_gpic(x, k=4, config=GPICConfig(affinity_kind="rbf", sigma=0.3))

    # production config: sharded A-free streaming on a mesh
    cfg = GPICConfig(engine="streaming", mesh=mesh, shard_axes="data",
                     affinity_kind="rbf", sigma=0.3, n_vectors=4)
    res = run_gpic(shard_points(x, mesh), k=4, config=cfg)

Routing table (operator names from core/operators.py):

    mesh   engine        entry point                    operator
    ------ ------------- ------------------------------ ---------------------------
    None   explicit      gpic(engine='explicit')        explicit_operator
    None   streaming     gpic(engine='streaming')       streaming_operator
    None   matrix_free   gpic_matrix_free               matrix_free_operator
    set    explicit      distributed_gpic               sharded_explicit_operator
    set    streaming     distributed_gpic('streaming')  sharded_streaming_operator
    set    matrix_free   distributed_gpic_matrix_free   sharded_matrix_free_operator
"""
from __future__ import annotations

import itertools
import os
import time
from dataclasses import dataclass, replace
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..kernels import ops
from . import spans
from .affinity import (
    AffinityKind,
    AffinitySpec,
    as_affinity_spec,
    invert_permutation,
)
from .distributed import (
    distributed_component_ids,
    distributed_gpic,
    distributed_gpic_matrix_free,
    distributed_gpic_segment,
    distributed_gpic_segment_finalize,
    distributed_gpic_segment_start,
)
from .graph import (
    content_row_score,
    graph_reorder_permutation,
    reorder_permutation,
)
from .gpic import (
    gpic,
    gpic_matrix_free,
    gpic_segment,
    gpic_segment_finalize,
    gpic_segment_start,
)
from .health import (
    GPICError,
    StragglerTimeout,
    raise_for_health,
    validate_features,
)
from .pic import PICResult
from .power import EMBEDDINGS, default_snapshot_iters, power_carry_like

ENGINES = ("explicit", "streaming", "matrix_free")
#: numbers the calls of ``run_gpic`` in this process: the metadata of each
#: call's span, shared by the spans of one job
_CALLS = itertools.count()


@dataclass(frozen=True)
class GPICConfig:
    """Everything that selects and tunes a GPIC run, in one hashable value.

    Engine / placement:
      engine:       'explicit' (paper-faithful A build), 'streaming'
                    (A-free tile regeneration), or 'matrix_free' (factored
                    jnp product, cosine kinds only).
      mesh:         None → single device; a Mesh → sharded via shard_map
                    (pass row-sharded x, e.g. from ``shard_points``).
      shard_axes:   mesh axis name(s) the rows stripe over.

    Clustering:
      affinity:     an :class:`AffinitySpec` — the full graph-construction
                    policy (kind, bandwidth: fixed sigma or adaptive local
                    scaling, kNN truncation; DESIGN.md §11). None derives
                    the dense fixed spec from affinity_kind/sigma.
      affinity_kind/sigma: legacy shorthand for the dense fixed spec
                    (sigma only read for 'rbf'); rejected alongside a
                    non-None ``affinity`` so the two routes cannot
                    silently disagree.
      n_vectors:    r power vectors in one engine state (O3).
      embedding:    'pic' (classic per-column loop), 'orthogonal' (block
                    iteration: column 0 pinned to the classic trajectory,
                    columns 1..r-1 QR-orthonormalized into the invariant
                    subspace — the nested-structure fix, DESIGN.md §10),
                    or 'ensemble' (diffusion-time snapshot concatenation).
      qr_every:     re-orthonormalization period in sweeps ('orthogonal').
      residual_tol: arm the subspace residual stopping rule ('orthogonal'
                    with n_vectors > 1): once column 0 converges
                    classically, a relative ||WV − VΛ|| residual below
                    this on a QR step stops the whole block instead of
                    running to max_iter (DESIGN.md §11). None = off (the
                    bitwise PR-3 loop).
      snapshot_iters: ascending iteration counts to snapshot ('ensemble';
                    None = geometric in max_iter).
      eps_scale:    convergence threshold numerator (eps = eps_scale / n).
      max_iter / kmeans_iters: loop caps.

    Performance:
      a_dtype:      A-stripe storage dtype ('explicit' engines; bf16 = O4).
      fold_shift:   O5 — fold the cosine_shifted transform out of the
                    O(n²/P) build (sharded explicit engine only).
      tile:         Pallas tile edge override (None = static autotuner).
      block_sparse: route truncated (kNN) specs through the fused one-pass
                    build and the block-CSR sweeps, so sweep traffic
                    tracks nnz instead of n² (DESIGN.md §13). False keeps
                    the dense-storage two-pass path — bitwise-equal
                    results, the comparison baseline. No effect on dense
                    specs or the matrix-free engine.
      overlap:      collective schedule of the sharded streaming ring
                    (DESIGN.md §15): True (default) packs each stage's
                    feature + V (+ thr) blocks into ONE double-buffered
                    ppermute issued before the stage's compute — P−1
                    collectives per sweep; False keeps the sequential
                    split schedule (2(P−1), 3(P−1) for the probe ring) as
                    the paired baseline. Bitwise-identical results either
                    way; no effect on routes without a ring (like
                    ``block_sparse`` on dense specs).
      row_reorder:  run the graph-aware row canonicalization pass
                    (DESIGN.md §15): cluster ``x[perm]`` for a permutation
                    computed from row CONTENT only (radial content scores,
                    grouped by the component probe's partition for
                    truncated specs) and inverse-permute every per-row
                    output. Makes the §13 block-sparse tile wins robust to
                    input row order — shuffled and pre-sorted inputs run
                    the pipeline on the identical canonical array, so
                    their (re-aligned) results are bitwise identical.
                    Off by default: it inserts a gather/scatter of x and,
                    for truncated specs, one component probe per run.
      use_pallas:   False routes every op to the jnp reference oracles.
      seed:         key for k-means init + extra power vectors when
                    ``run_gpic`` isn't handed an explicit key.

    Robustness (DESIGN.md §12):
      sanitize:     zero-fill non-finite feature values at the front door
                    (recorded in ``PICResult.health.notes``) instead of
                    raising :class:`~repro.core.health.NonFiniteInputError`.
      component_probe: run the on-device disconnected-component check on
                    truncated (kNN) graphs; the count lands in
                    ``PICResult.health.n_components``. False skips the
                    probe's extra sweeps.
      retry_on_fallback: when a kernel falls back to its reference oracle
                    MID-RUN (``kernel_fallback:<op>`` would be noted), the
                    trajectory mixes kernel and reference ops. True
                    re-runs the whole pipeline on the reference oracles
                    (``use_pallas=False``) for a CONSISTENT trajectory;
                    the note upgrades to ``kernel_fallback_retried:<op>``.
                    Under the supervisor (``checkpoint_every``) it upgrades
                    further: the tainted segment is discarded and the run
                    resumes from the last snapshot on the oracles
                    (``kernel_fallback_resumed:<op>``).

    Resumable execution (the PR-9 supervisor, DESIGN.md §14):
      checkpoint_every: run the power loop in bounded segments of this many
                    sweeps, snapshotting the full convergence carry after
                    each through ``train/checkpoint.py``. The segment
                    boundary only moves where the while_loop STOPS — every
                    sweep's arithmetic is the monolithic loop's, so a run
                    interrupted at any sweep and resumed is bitwise
                    identical to the uninterrupted run. Set together with
                    ckpt_dir (both or neither).
      ckpt_dir:     snapshot directory. If it already holds a valid
                    snapshot (a previous attempt died), the run resumes
                    from it (``resumed:<sweep>`` note) instead of
                    restarting at sweep 0. Corrupt snapshots (checksum
                    mismatch, truncated leaves) are quarantined and the
                    supervisor falls back to the previous valid step
                    (``checkpoint_skipped:<dir>``).
      max_retries:  attempts the supervisor may restart after a retryable
                    failure (typed GPICError, injected fault, straggler
                    timeout) before re-raising. Each retry resumes from
                    the last snapshot and is recorded as
                    ``retry:<n>:<ErrorClass>``.
      backoff:      base seconds for exponential backoff between retries
                    (sleep = backoff · 2^(attempt-1); 0 = immediate).
      straggler_timeout: wall-clock budget per segment in seconds; a
                    segment exceeding it raises
                    :class:`~repro.core.health.StragglerTimeout` (noted
                    ``straggler:<sweep>:<sec>``), which the retry loop
                    treats like any other retryable fault. Works without
                    checkpointing (the whole run is then one segment).
      inject_ring_fault: fault-injection hook forwarded to the sharded
                    streaming engine — ('ring_nan', stage) poisons that
                    ring stage's consumed block with NaN (requires mesh +
                    engine='streaming'; tests/test_resume.py).
    """
    engine: str = "explicit"
    mesh: Mesh | None = None
    shard_axes: str | Sequence[str] = "data"
    affinity: AffinitySpec | None = None
    affinity_kind: AffinityKind = "cosine_shifted"
    sigma: float = 1.0
    n_vectors: int = 1
    embedding: str = "pic"
    qr_every: int = 1
    residual_tol: float | None = None
    snapshot_iters: Sequence[int] | None = None
    eps_scale: float = 1e-5
    max_iter: int = 50
    kmeans_iters: int = 25
    a_dtype: Any = jnp.float32
    fold_shift: bool = False
    tile: int | None = None
    block_sparse: bool = True
    overlap: bool = True
    row_reorder: bool = False
    use_pallas: bool = True
    seed: int = 0
    sanitize: bool = False
    component_probe: bool = True
    retry_on_fallback: bool = False
    checkpoint_every: int | None = None
    ckpt_dir: str | None = None
    max_retries: int = 3
    backoff: float = 0.0
    straggler_timeout: float | None = None
    inject_ring_fault: tuple | None = None

    def with_(self, **updates) -> "GPICConfig":
        """Functional update (``dataclasses.replace`` with a shorter name)."""
        return replace(self, **updates)


def run_gpic(
    x: jax.Array,
    k: int,
    config: GPICConfig | None = None,
    *,
    key: jax.Array | None = None,
    segment_injector: Callable[[int], None] | None = None,
    **overrides,
) -> PICResult:
    """Run GPIC as described by ``config`` (plus keyword overrides).

    ``x`` is the (n, m) feature matrix — row-sharded on ``config.mesh``
    for distributed runs (see ``shard_points``), a plain array otherwise.
    Returns the extended :class:`PICResult` (full (n, r) embedding,
    per-column iteration stats, and the populated ``health`` report).

    Robustness contract (DESIGN.md §12): degenerate inputs raise a typed
    :class:`~repro.core.health.GPICError` subclass at the front door
    (non-finite features unless ``sanitize``, n < k, constant rows) or
    after the run (every row isolated, every power column dead); anything
    less total returns normally with the damage described in
    ``result.health`` — never silent garbage.

    ``segment_injector`` is the fault-injection hook of the supervised
    (resumable) path: a callable invoked with the current sweep count at
    every segment boundary, free to raise (e.g.
    ``FailureInjector.maybe_fail``) — the supervisor classifies the raise
    as retryable and resumes from the last snapshot. Passing it (or
    setting ``checkpoint_every`` / ``straggler_timeout``) routes the run
    through the segmented engines; the trajectory stays bitwise identical
    to the monolithic path (DESIGN.md §14).
    """
    with spans.span(spans.RUN, call=next(_CALLS)):
        with spans.span(spans.FRONT_DOOR):
            cfg = config or GPICConfig()
            if overrides:
                cfg = cfg.with_(**overrides)
            spec = _check_config(cfg, x.shape[0])
            if key is None:
                key = jax.random.key(cfg.seed)
            x, health_notes, inv = _prepare_features(x, k, cfg, spec)
        fallbacks_before = ops.kernel_fallbacks()

        snapshot_iters = (None if cfg.snapshot_iters is None
                          else tuple(cfg.snapshot_iters))
        common = dict(key=key, max_iter=cfg.max_iter,
                      kmeans_iters=cfg.kmeans_iters,
                      affinity=spec, n_vectors=cfg.n_vectors,
                      embedding=cfg.embedding, qr_every=cfg.qr_every,
                      snapshot_iters=snapshot_iters,
                      residual_tol=cfg.residual_tol)

        def _route(c: GPICConfig) -> PICResult:
            if c.mesh is None:
                if c.engine == "matrix_free":
                    return gpic_matrix_free(
                        x, k, eps=c.eps_scale / x.shape[0],
                        use_pallas=c.use_pallas, **common)
                return gpic(
                    x, k, engine=c.engine, a_dtype=c.a_dtype,
                    tile=c.tile, use_pallas=c.use_pallas,
                    block_sparse=c.block_sparse,
                    eps=c.eps_scale / x.shape[0],
                    probe_components=c.component_probe, **common)
            shard_axes = (c.shard_axes if isinstance(c.shard_axes, str)
                          else tuple(c.shard_axes))
            if c.engine == "matrix_free":
                return distributed_gpic_matrix_free(
                    x, k, mesh=c.mesh, shard_axes=shard_axes,
                    eps_scale=c.eps_scale, use_pallas=c.use_pallas, **common)
            return distributed_gpic(
                x, k, mesh=c.mesh, shard_axes=shard_axes,
                engine=c.engine, eps_scale=c.eps_scale,
                a_dtype=c.a_dtype, fold_shift=c.fold_shift,
                tile=c.tile, use_pallas=c.use_pallas,
                block_sparse=c.block_sparse, overlap=c.overlap,
                probe_components=c.component_probe,
                inject_ring_fault=c.inject_ring_fault, **common)

        supervised = (cfg.checkpoint_every is not None
                      or cfg.straggler_timeout is not None
                      or segment_injector is not None)
        if supervised:
            # the resumable path handles fallback classification itself
            # (it must not save a kernel/reference-mixed segment)
            res, sup_notes = _run_supervised(
                x, k, cfg, key=key, spec=spec,
                segment_injector=segment_injector)
        else:
            with spans.span(spans.LAUNCH):
                res = _route(cfg)
        with spans.span(spans.HEALTH):
            if supervised:
                notes = tuple(health_notes) + sup_notes
            else:
                # attach host-side events (kernel fallbacks that first
                # fired during this run)
                new_fallback_ops = tuple(sorted(
                    op for op in ops.kernel_fallbacks()
                    if op not in fallbacks_before))
                note_tag = "kernel_fallback"
                if (new_fallback_ops and cfg.retry_on_fallback
                        and cfg.use_pallas):
                    # a mid-run fallback leaves a MIXED kernel/reference
                    # trajectory (only the ops that failed were served by
                    # their oracles); re-run the whole pipeline on the
                    # reference oracles so every sweep of the reported
                    # result came from ONE consistent implementation
                    with spans.span(spans.LAUNCH):
                        res = _route(cfg.with_(use_pallas=False))
                    note_tag = "kernel_fallback_retried"
                notes = tuple(health_notes) + tuple(
                    f"{note_tag}:{op}" for op in new_fallback_ops)
            if inv is not None:
                res = _unpermute_result(res, inv)
            if res.health is not None and notes:
                res = replace(res, health=replace(
                    res.health, notes=res.health.notes + notes))
            if res.health is not None:
                raise_for_health(res.health, x.shape[0])
    return res


def _check_config(cfg: GPICConfig, n: int) -> AffinitySpec:
    """Reject unknown or contradictory field combinations; returns the
    resolved affinity spec."""
    if cfg.engine not in ENGINES:
        raise ValueError(
            f"unknown engine {cfg.engine!r} (expected one of {ENGINES})")
    if cfg.embedding not in EMBEDDINGS:
        raise ValueError(
            f"unknown embedding {cfg.embedding!r} "
            f"(expected one of {EMBEDDINGS})")
    if cfg.qr_every < 1:
        raise ValueError(
            f"qr_every must be >= 1 (a period in sweeps), got {cfg.qr_every}")
    if cfg.qr_every != 1 and cfg.embedding != "orthogonal":
        raise ValueError(
            "qr_every tunes the re-orthonormalization period of "
            "embedding='orthogonal' only")
    if cfg.snapshot_iters is not None and cfg.embedding != "ensemble":
        raise ValueError(
            "snapshot_iters selects the diffusion times of "
            "embedding='ensemble' only")
    if cfg.residual_tol is not None:
        if cfg.embedding != "orthogonal":
            raise ValueError(
                "residual_tol arms the subspace residual stopping rule of "
                "embedding='orthogonal' only")
        if cfg.n_vectors < 2:
            raise ValueError(
                "residual_tol stops the QR-coupled block columns; with "
                "n_vectors=1 the orthogonal loop IS the classic one and "
                "the rule can never arm — drop it or raise n_vectors")
        if not float(cfg.residual_tol) > 0.0:
            raise ValueError(
                f"residual_tol must be > 0 (a relative residual), got "
                f"{cfg.residual_tol}")
    # resolve the affinity spec: an explicit AffinitySpec wins; setting it
    # ALONGSIDE non-default legacy shorthand is ambiguous and rejected
    # (sigma <= 0 and bad bandwidth/kind combos are rejected by the spec's
    # own constructor; neighbor-rank bounds need n and are checked here)
    if cfg.affinity is not None and (
            cfg.affinity_kind != "cosine_shifted" or cfg.sigma != 1.0):
        raise ValueError(
            "set either GPICConfig.affinity (the full spec) or the legacy "
            "affinity_kind/sigma shorthand, not both")
    spec = as_affinity_spec(cfg.affinity, kind=cfg.affinity_kind,
                            sigma=cfg.sigma)
    spec.validate_for_n(n)
    # reject field combinations the selected route would silently ignore —
    # the front door must not mask misconfiguration a direct call rejects
    if cfg.engine == "matrix_free":
        dropped = [name for name, bad in (
            ("fold_shift", cfg.fold_shift),
            ("tile", cfg.tile is not None),
            ("a_dtype", cfg.a_dtype != jnp.float32),
        ) if bad]
        if dropped:
            raise ValueError(
                f"engine='matrix_free' does not use {dropped} (the factored "
                "jnp sweep has no A storage or Pallas tiles)")
        if not spec.factorable:
            raise ValueError(
                "engine='matrix_free' needs a factorable affinity spec "
                "(cosine kinds, fixed bandwidth, no truncation); got "
                f"{spec} — use the explicit or streaming engine for "
                "adaptive/kNN graphs")
    elif cfg.fold_shift and (cfg.mesh is None or cfg.engine != "explicit"
                             or spec.kind != "cosine_shifted"
                             or not spec.dense_fixed):
        raise ValueError(
            "fold_shift (O5) applies only to the sharded explicit engine "
            "with a dense fixed cosine_shifted spec (the shift being "
            "folded has no closed form on a truncated row)")
    if cfg.engine == "streaming" and cfg.a_dtype != jnp.float32:
        raise ValueError(
            "a_dtype (O4) selects the A *storage* dtype; the streaming "
            "engine never stores A")
    if (cfg.checkpoint_every is None) != (cfg.ckpt_dir is None):
        raise ValueError(
            "checkpoint_every and ckpt_dir come as a pair (a snapshot "
            "cadence needs a directory and vice versa); set both or "
            "neither")
    if cfg.checkpoint_every is not None and cfg.checkpoint_every < 1:
        raise ValueError(
            f"checkpoint_every must be >= 1 (a period in sweeps), got "
            f"{cfg.checkpoint_every}")
    if cfg.max_retries < 0:
        raise ValueError(f"max_retries must be >= 0, got {cfg.max_retries}")
    if cfg.backoff < 0:
        raise ValueError(f"backoff must be >= 0 seconds, got {cfg.backoff}")
    if cfg.straggler_timeout is not None and not cfg.straggler_timeout > 0:
        raise ValueError(
            f"straggler_timeout must be > 0 seconds, got "
            f"{cfg.straggler_timeout}")
    if cfg.inject_ring_fault is not None and (
            cfg.mesh is None or cfg.engine != "streaming"):
        raise ValueError(
            "inject_ring_fault poisons a sharded streaming ring stage; it "
            "needs mesh set and engine='streaming'")
    return spec


def _prepare_features(x, k, cfg: GPICConfig, spec: AffinitySpec):
    """Validate the features and, under ``row_reorder``, put the rows in
    canonical order. Returns (x, health notes, the inverse permutation or
    None)."""
    # front-door input validation (typed errors; value checks skip under
    # a tracer and the device-side latches carry the load)
    x, health_notes = validate_features(x, k, sanitize=cfg.sanitize)
    inv = None
    if cfg.row_reorder:
        # canonicalize row order BEFORE routing (including the supervisor:
        # the permutation is a function of row content, so a resumed
        # attempt recomputes the identical canonical array); every per-row
        # output is mapped back through ``inv`` at the end
        if cfg.mesh is not None:
            # scoring and the take are global row operations: replicate
            # once (a mesh with Explicit axes, the default of
            # ``jax.make_mesh``, refuses them on a row-sharded array)
            x = jax.device_put(x, NamedSharding(cfg.mesh, PartitionSpec()))
        perm = _row_reorder_permutation(x, cfg, spec)
        inv = invert_permutation(perm)
        x = jnp.take(x, perm, axis=0)
        if cfg.mesh is not None:
            shard_axes = (cfg.shard_axes if isinstance(cfg.shard_axes, str)
                          else tuple(cfg.shard_axes))
            x = jax.device_put(
                x, NamedSharding(cfg.mesh, PartitionSpec(shard_axes)))
        health_notes = tuple(health_notes) + ("row_reorder",)
    return x, health_notes, inv


def _row_reorder_permutation(x, cfg: GPICConfig, spec: AffinitySpec):
    """The canonical permutation for ``row_reorder`` (core/graph.py): the
    local path delegates wholesale; the mesh path realizes the same
    content-score + component-probe recipe with the sharded probe so the
    features never leave the mesh layout until the one global ``take``."""
    if cfg.mesh is None:
        return graph_reorder_permutation(
            x, spec, tile=cfg.tile, use_pallas=cfg.use_pallas)
    score = content_row_score(x)
    if not spec.truncated:
        return reorder_permutation(score)
    shard_axes = (cfg.shard_axes if isinstance(cfg.shard_axes, str)
                  else tuple(cfg.shard_axes))
    _, comp = distributed_component_ids(
        x, mesh=cfg.mesh, shard_axes=shard_axes, affinity=spec,
        tile=cfg.tile, use_pallas=cfg.use_pallas)
    return reorder_permutation(score, comp)


def _unpermute_result(res: PICResult, inv) -> PICResult:
    """Map every per-row output of a canonical-order run back to the
    caller's row order: labels, the column-0 embedding, the clustered
    (n, c) block, and the per-row component ids. Index-only, hence exact —
    the round-trip bitwise contract of ``row_reorder`` (DESIGN.md §15)."""
    health = res.health
    if health is not None and health.components is not None:
        health = replace(health, components=health.components[inv])
    return replace(res, labels=res.labels[inv],
                   embedding=res.embedding[inv],
                   embeddings=res.embeddings[inv],
                   health=health)


def _segment_plan(cfg: GPICConfig):
    """Resolve the loop-mode arguments of the segmented engines so the
    segment trajectory IS the monolithic one: 'ensemble' is the classic
    'pic' loop with a snapshot schedule (resolved here to the same default
    geometric schedule ``ensemble_power_iteration`` derives, with the same
    validation), the other embeddings pass through unchanged.

    Returns (mode, qr_every, snapshot_iters, residual_tol).
    """
    if cfg.embedding != "ensemble":
        return cfg.embedding, cfg.qr_every, (), cfg.residual_tol
    si = tuple(int(s) for s in (
        cfg.snapshot_iters if cfg.snapshot_iters is not None
        else default_snapshot_iters(cfg.max_iter)))
    if not si or list(si) != sorted(set(si)):
        raise ValueError(
            f"snapshot_iters must be non-empty strictly ascending ints, "
            f"got {si!r}")
    if si[0] < 1 or si[-1] > cfg.max_iter:
        raise ValueError(
            f"snapshot_iters {si!r} must lie in [1, max_iter="
            f"{cfg.max_iter}]")
    return "pic", 1, si, None


class _FallbackResume(Exception):
    """Internal control flow: a segment first tripped a kernel fallback
    under ``retry_on_fallback`` — the segment is tainted (mixed kernel /
    reference sweeps), so it is discarded unsaved and the run resumes from
    the last snapshot on the reference oracles."""

    def __init__(self, fallback_ops):
        super().__init__(f"kernel fallback mid-segment: {fallback_ops}")
        self.fallback_ops = fallback_ops


def _run_supervised(x, k, cfg: GPICConfig, *, key, spec, segment_injector):
    """The resumable-execution supervisor (DESIGN.md §14).

    Runs the power loop in bounded segments through the segmented engine
    entry points, snapshotting the convergence carry after each segment,
    and classifies failures into retry-with-resume: a typed
    :class:`~repro.core.health.GPICError` (divergence, straggler timeout,
    injected fault) restarts the attempt from the newest valid snapshot
    with exponential backoff; a first kernel fallback under
    ``retry_on_fallback`` discards the tainted segment and resumes on the
    reference oracles. Because segmentation only moves where the
    while_loop STOPS, every completed sweep is the monolithic loop's —
    resumed runs are bitwise identical to uninterrupted ones.

    Returns (result, notes): the PICResult plus the supervisor's note
    history (``resumed:<sweep>``, ``retry:<n>:<ErrorClass>``,
    ``checkpoint_skipped:<dir>``, ``straggler:<sweep>:<sec>``,
    ``kernel_fallback[_resumed]:<op>``).
    """
    # train imports core at module load; import lazily to avoid the cycle
    from ..train import checkpoint as ckpt

    n = x.shape[0]
    mode, qr_every, si, residual_tol = _segment_plan(cfg)
    ce = cfg.checkpoint_every or cfg.max_iter
    kkm, krand = jax.random.split(key)
    local = cfg.mesh is None
    shard_axes = (cfg.shard_axes if isinstance(cfg.shard_axes, str)
                  else tuple(cfg.shard_axes))
    saver = ckpt.AsyncCheckpointer() if cfg.ckpt_dir is not None else None
    notes: list[str] = []

    def seg_kwargs(use_pallas):
        kw = dict(affinity=spec, engine=cfg.engine, a_dtype=cfg.a_dtype,
                  tile=cfg.tile, use_pallas=use_pallas,
                  block_sparse=cfg.block_sparse, mode=mode,
                  qr_every=qr_every, snapshot_iters=si,
                  residual_tol=residual_tol)
        if local:
            kw["eps"] = cfg.eps_scale / n
        else:
            kw.update(mesh=cfg.mesh, shard_axes=shard_axes,
                      eps_scale=cfg.eps_scale, fold_shift=cfg.fold_shift,
                      overlap=cfg.overlap,
                      inject_ring_fault=cfg.inject_ring_fault)
        return kw

    def fin_kwargs(use_pallas):
        kw = dict(kmeans_iters=cfg.kmeans_iters, affinity=spec,
                  engine=cfg.engine, a_dtype=cfg.a_dtype, tile=cfg.tile,
                  use_pallas=use_pallas, block_sparse=cfg.block_sparse,
                  embedding=cfg.embedding, snapshot_iters=si,
                  probe_components=cfg.component_probe)
        if not local:
            kw.update(mesh=cfg.mesh, shard_axes=shard_axes,
                      fold_shift=cfg.fold_shift, overlap=cfg.overlap)
        return kw

    start_fn = gpic_segment_start if local else distributed_gpic_segment_start
    step_fn = gpic_segment if local else distributed_gpic_segment
    fin_fn = gpic_segment_finalize if local else distributed_gpic_segment_finalize

    def attempt(use_pallas):
        carry = iso = None
        if cfg.ckpt_dir is not None:
            like = power_carry_like(n, cfg.n_vectors, len(si))
            tree, step, path, skipped = ckpt.restore_latest_valid(
                cfg.ckpt_dir, like)
            for p in skipped:
                notes.append(f"checkpoint_skipped:{os.path.basename(p)}")
            if tree is not None:
                carry = tree
                iso = jnp.asarray(
                    ckpt.manifest_extra(path).get("isolated_rows", 0),
                    jnp.int32)
                notes.append(f"resumed:{step}")
        kw = seg_kwargs(use_pallas)
        while True:
            t_now = 0
            if carry is not None:
                t_now = int(jax.device_get(carry.t))
                if (t_now >= cfg.max_iter
                        or bool(jax.device_get(jnp.all(carry.done)))):
                    break
            if segment_injector is not None:
                segment_injector(t_now)
            stop = jnp.int32(min(t_now + ce, cfg.max_iter))
            before = ops.kernel_fallbacks()
            t0 = time.monotonic()
            if carry is None:
                carry, iso = start_fn(x, stop, key=krand,
                                      n_vectors=cfg.n_vectors, **kw)
            else:
                carry = step_fn(x, carry, stop, **kw)
            jax.block_until_ready(carry.v)
            sec = time.monotonic() - t0
            t_after = int(jax.device_get(carry.t))
            if (cfg.straggler_timeout is not None
                    and sec > cfg.straggler_timeout):
                notes.append(f"straggler:{t_after}:{sec:.3f}")
                raise StragglerTimeout(
                    f"segment ending at sweep {t_after} took {sec:.3f}s "
                    f"(straggler_timeout={cfg.straggler_timeout}s); "
                    "resuming from the last snapshot")
            new = tuple(sorted(o for o in ops.kernel_fallbacks()
                               if o not in before))
            if new and cfg.retry_on_fallback and use_pallas:
                raise _FallbackResume(new)   # tainted segment: NOT saved
            notes.extend(f"kernel_fallback:{o}" for o in new)
            if saver is not None:
                saver.save_async(
                    os.path.join(cfg.ckpt_dir, f"step_{t_after:06d}"),
                    carry, step=t_after,
                    extra={"isolated_rows": int(jax.device_get(iso)),
                           "sweep": t_after})
        before = ops.kernel_fallbacks()
        res = fin_fn(x, carry, iso, k, key=kkm, **fin_kwargs(use_pallas))
        jax.block_until_ready(res.labels)
        new = tuple(sorted(o for o in ops.kernel_fallbacks()
                           if o not in before))
        if new and cfg.retry_on_fallback and use_pallas:
            raise _FallbackResume(new)
        notes.extend(f"kernel_fallback:{o}" for o in new)
        return res

    use_pallas = cfg.use_pallas
    retries = 0
    try:
        while True:
            try:
                return attempt(use_pallas), tuple(notes)
            except _FallbackResume as e:
                if saver is not None:
                    saver.wait()     # land pending snapshots before restore
                notes.extend(f"kernel_fallback_resumed:{o}"
                             for o in e.fallback_ops)
                use_pallas = False   # not a retry: a consistency downgrade
            except GPICError as e:
                if saver is not None:
                    saver.wait()
                retries += 1
                if retries > cfg.max_retries:
                    raise
                notes.append(f"retry:{retries}:{type(e).__name__}")
                if cfg.backoff:
                    time.sleep(cfg.backoff * (2 ** (retries - 1)))
    finally:
        if saver is not None:
            saver.wait()
