"""Distributed GPIC via shard_map — the paper's multi-GPU future work, built
for the production mesh (DESIGN.md §3, §9).

There is no distributed power loop and no distributed affinity math in this
module: every path assembles a sharded :class:`~repro.core.power.PowerOperator`
(core/operators.py) — the SAME Pallas kernel dispatch the single-device
engines use, run on each device's row stripe inside ``shard_map`` — and
hands it to the ONE convergence engine, ``core.power.batched_power_iteration``.
The engine's ``sum``/``max``/``all_gather`` primitives are bound to
``psum``/``pmax``/``all_gather`` over the mesh axes. The explicit path
compiles the same tiled kernel program as the single-device build (tiles
keyed on the global n); the streaming ring tiles per (n/P) block and
accumulates blocks in rotated order, so its trajectories agree with the
single-device engine at the ulp level rather than bitwise (DESIGN.md §9).

Layouts:
  explicit path:      A row-stripes built by the Pallas affinity kernel
                      (bf16 A-storage O4 and fold_shift O5 supported); X and
                      V replicated via all-gather (X once, V per step —
                      O(n r) bytes/step vs O(n²/P) compute).
  streaming path:     row-striped features, NO gathered copies: each sweep
                      ring-rotates the (n/P, m) feature blocks with
                      ppermute while the streaming kernel regenerates
                      affinity stripe tiles on the fly. O(n·m/P) peak
                      memory per device and every affinity kind — the
                      production configuration.
  matrix-free path:   X̂ row-sharded; per step one psum of an (m, r) block
                      and one (r,) psum. Collectives O(m r) per step — the
                      configuration that scales to thousands of nodes.

All paths run the batched multi-vector engine state (core/power.py):
``n_vectors`` power vectors iterate as one (n_loc, r) local chunk, one
stripe sweep per iteration regardless of r, with per-column freezing so
every column reproduces its dedicated single-vector trajectory.

The final k-means runs on the (gathered, replicated) (n, r) embedding
identically on every device — deterministic, no collective needed.

Every body runs under ``jax.shard_map(..., check_vma=False)``: the loops
(power engine, k-means) seed their carries from replicated constants that
become device-varying after the first sweep, and the gathered embedding
is typed varying, so the varying-axes checker would need a ``pcast`` on
every such carry. The outputs declared replicated (``P()``) are
replicated by construction — they come out of psum/all_gather.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .affinity import AffinityKind, AffinitySpec, as_affinity_spec
from .health import HealthReport, count_bad_rows, graph_component_probe
from .kmeans import kmeans
from .operators import (
    _axis_tuple,
    mesh_reductions,
    sharded_explicit_operator,
    sharded_matrix_free_operator,
    sharded_streaming_operator,
)
from .pic import PICResult, make_pic_result
from .power import (
    PowerCarry,
    backfill_snapshots,
    ensemble_embedding,
    finalize_power_carry,
    init_power_carry,
    init_power_vectors_local,
    power_iteration_segment,
    random_start_vectors,
    run_power_embedding,
    standardize_columns,
)



def _mesh_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def _build_sharded_operator(x_loc, axes, mesh_size, engine, spec, *,
                            a_dtype=jnp.float32, fold_shift=False, tile=None,
                            use_pallas=True, block_sparse=True, overlap=True,
                            inject_ring_fault=None):
    """The ONE sharded operator construction (inside the shard_map body) —
    shared by the monolithic entry points and the segmented (resumable)
    ones so both trace the identical build (DESIGN.md §14). ``overlap``
    selects the streaming ring's collective schedule (operators.py::
    _ring_sweep) — packed double-buffered ppermutes (default) vs the
    sequential split baseline; the explicit and matrix-free engines have
    no ring and ignore it (like ``block_sparse`` on dense specs)."""
    if engine == "explicit":
        return sharded_explicit_operator(
            x_loc, axes=axes, spec=spec, a_dtype=a_dtype,
            fold_shift=fold_shift, tile=tile, use_pallas=use_pallas,
            block_sparse=block_sparse)
    if engine == "streaming":
        return sharded_streaming_operator(
            x_loc, axes=axes, mesh_size=mesh_size, spec=spec,
            tile=tile, use_pallas=use_pallas, block_sparse=block_sparse,
            overlap=overlap, inject_fault=inject_ring_fault)
    if engine == "matrix_free":
        return sharded_matrix_free_operator(x_loc, axes=axes, spec=spec,
                                            use_pallas=use_pallas)
    raise ValueError(f"unknown engine {engine!r} "
                     "(expected 'explicit' or 'streaming')")


def _local_slice(idx, n_loc, arr):
    """The (n_loc, ...) row chunk of a replicated array at device ``idx``."""
    return jax.lax.dynamic_slice_in_dim(arr, idx * n_loc, n_loc, axis=0)


def _run_sharded(op, axes, *, key, u0t, k, eps, max_iter, kmeans_iters,
                 n_total, embedding="pic", qr_every=1, snapshot_iters=None,
                 residual_tol=None, force_reference=False, probe=False):
    """Seed the local engine state from the operator's degrees, run THE
    convergence engine, gather once, and k-means the replicated embedding.

    The embedding-mode routing is the same :func:`run_power_embedding` the
    local entry points use: the QR step's Gram partials run on each
    device's chunk and are finished by the operator's ``psum`` binding, and
    ensemble snapshots are taken on the local chunk and gathered once after
    the loop — the sharded block algebra IS the single-device one. The
    health arrays (per-column status, isolated-row count, the component
    probe when ``probe`` arms) likewise finish through the operator's
    reductions, so a sharded run reports the same diagnostics as the local
    run of the same problem (DESIGN.md §12).
    Returns (labels, v_full, emb_full, t_cols, done, status, iso, n_comp,
    comp_full): the replicated final (n, r) engine state, the replicated
    (n, c) matrix that was clustered (the same array unless ensemble
    widened it to c = r·S), and the replicated health arrays.
    """
    idx = jax.lax.axis_index(_axis_tuple(axes))
    n_loc = op.degree.shape[0]
    u0t_loc = _local_slice(idx, n_loc, u0t)
    v0_loc = init_power_vectors_local(
        op.degree, u0t_loc, sum_fn=op.sum, dtype=jnp.float32)
    v_loc, t_cols, done, emb_loc, status = run_power_embedding(
        op, v0_loc, eps, max_iter, embedding=embedding, qr_every=qr_every,
        snapshot_iters=snapshot_iters, residual_tol=residual_tol)
    emb_full = op.all_gather(emb_loc)                   # once, after the loop
    v_full = emb_full if emb_loc is v_loc else op.all_gather(v_loc)
    emb = standardize_columns(emb_full)
    labels, _ = kmeans(key, emb, k, iters=kmeans_iters,
                       force_reference=force_reference)
    iso = count_bad_rows(op.degree, sum_fn=op.sum)
    if probe:
        n_comp, comp_loc = graph_component_probe(
            op, n_total, row_offset=idx * n_loc)
        comp_full = op.all_gather(comp_loc)
    else:
        n_comp = jnp.int32(-1)
        comp_full = jnp.full((n_total,), -1, jnp.int32)
    return (labels, v_full, emb_full, t_cols, done,
            status, iso, n_comp, comp_full)


@functools.partial(
    jax.jit,
    static_argnames=("k", "mesh", "shard_axes", "max_iter", "kmeans_iters",
                     "affinity_kind", "sigma", "affinity", "eps_scale",
                     "a_dtype", "fold_shift", "n_vectors", "engine", "tile",
                     "use_pallas", "embedding", "qr_every", "snapshot_iters",
                     "residual_tol", "probe_components", "block_sparse",
                     "overlap", "inject_ring_fault"),
)
def distributed_gpic(
    x: jax.Array,
    k: int,
    *,
    key: jax.Array,
    mesh: Mesh,
    shard_axes: str | Sequence[str] = "data",
    eps_scale: float = 1e-5,
    max_iter: int = 50,
    kmeans_iters: int = 25,
    affinity_kind: AffinityKind = "cosine_shifted",
    sigma: float = 1.0,
    affinity: AffinitySpec | None = None,
    a_dtype=jnp.float32,
    fold_shift: bool = False,
    n_vectors: int = 1,
    engine: str = "explicit",
    tile: int | None = None,
    use_pallas: bool = True,
    embedding: str = "pic",
    qr_every: int = 1,
    snapshot_iters: tuple | None = None,
    residual_tol: float | None = None,
    probe_components: bool = True,
    block_sparse: bool = True,
    overlap: bool = True,
    inject_ring_fault: tuple | None = None,
) -> PICResult:
    """Sharded GPIC on the Pallas kernels (paper-faithful math, row stripes).

    Engines (mirroring single-device ``gpic``):
      engine='explicit'   per-device (n/P, n) stripe of the Pallas A build;
                          V replicated per sweep. Beyond-paper options:
                          a_dtype=bf16 (O4) halves per-iteration A reads;
                          fold_shift (O5, cosine_shifted only) stores raw
                          masked cosine and folds the shift into an O(n r)
                          epilogue.
      engine='streaming'  A-free ring: feature blocks rotate around the
                          mesh with ppermute while affinity stripe tiles
                          regenerate on the fly. O(n·m/P) peak memory, all
                          affinity kinds — the production configuration.
                          ``overlap`` picks the ring schedule: packed
                          double-buffered ppermutes (default; P−1
                          collectives per sweep) vs the sequential split
                          baseline — bitwise-identical results either way
                          (ignored by the other engines, which have no
                          ring).

    ``n_vectors=r`` runs the multi-vector engine — r power vectors in one
    (n, r) state, ONE stripe sweep per iteration (DESIGN.md §4).
    ``embedding`` selects the block mode ('pic' | 'orthogonal' |
    'ensemble', DESIGN.md §10) — the QR/snapshot algebra runs through the
    operator's reduction primitives, so it is the single-device algebra.

    ``probe_components`` runs the on-device disconnected-component check
    when the spec truncates (DESIGN.md §12); ``inject_ring_fault``
    (streaming engine only) poisons one ring stage's consumed block with
    NaN — the fault-injection hook behind tests/test_robustness.py.
    """
    axes = _axis_tuple(shard_axes)
    n = x.shape[0]
    eps = eps_scale / n
    mesh_size = _mesh_size(mesh, axes)
    spec = as_affinity_spec(affinity, kind=affinity_kind, sigma=sigma)
    spec.validate_for_n(n)
    if inject_ring_fault is not None and engine != "streaming":
        raise ValueError(
            "inject_ring_fault targets the streaming ring; "
            f"engine={engine!r} has no ring stages")
    kkm, krand = jax.random.split(key)
    u0t = random_start_vectors(krand, n, n_vectors)

    def fn(x_loc, key, u0t):
        if engine not in ("explicit", "streaming"):
            raise ValueError(f"unknown engine {engine!r} "
                             "(expected 'explicit' or 'streaming')")
        op = _build_sharded_operator(
            x_loc, axes, mesh_size, engine, spec, a_dtype=a_dtype,
            fold_shift=fold_shift, tile=tile, use_pallas=use_pallas,
            block_sparse=block_sparse, overlap=overlap,
            inject_ring_fault=inject_ring_fault)
        return _run_sharded(op, axes, key=key, u0t=u0t, k=k, eps=eps,
                            max_iter=max_iter, kmeans_iters=kmeans_iters,
                            n_total=n, embedding=embedding,
                            qr_every=qr_every,
                            snapshot_iters=snapshot_iters,
                            residual_tol=residual_tol,
                            force_reference=not use_pallas,
                            probe=probe_components and spec.truncated)

    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axes), P(), P()),
        out_specs=(P(),) * 9,
        check_vma=False,
    )(x, kkm, u0t)
    labels, v, emb_full, t_cols, done, status, iso, n_comp, comp = out
    health = HealthReport(col_status=status, isolated_rows=iso,
                          n_components=n_comp, components=comp)
    return make_pic_result(labels, v, t_cols, done, embedding=embedding,
                           embeddings=emb_full, health=health)


@functools.partial(
    jax.jit,
    static_argnames=("k", "mesh", "shard_axes", "max_iter", "kmeans_iters",
                     "affinity_kind", "affinity", "eps_scale", "n_vectors",
                     "use_pallas", "embedding", "qr_every", "snapshot_iters",
                     "residual_tol"),
)
def distributed_gpic_matrix_free(
    x: jax.Array,
    k: int,
    *,
    key: jax.Array,
    mesh: Mesh,
    shard_axes: str | Sequence[str] = "data",
    eps_scale: float = 1e-5,
    max_iter: int = 50,
    kmeans_iters: int = 25,
    affinity_kind: AffinityKind = "cosine_shifted",
    affinity: AffinitySpec | None = None,
    n_vectors: int = 1,
    use_pallas: bool = True,
    embedding: str = "pic",
    qr_every: int = 1,
    snapshot_iters: tuple | None = None,
    residual_tol: float | None = None,
) -> PICResult:
    """Matrix-free distributed GPIC (O2): psum(m r) per step, scales to 1000s
    of nodes. Factorable specs only (cosine kinds, no adaptive scaling or
    truncation; DESIGN.md §2)."""
    axes = _axis_tuple(shard_axes)
    n = x.shape[0]
    eps = eps_scale / n
    spec = as_affinity_spec(affinity, kind=affinity_kind)
    if not spec.factorable:
        raise ValueError(
            f"matrix-free path needs a factorable affinity spec, got {spec}")
    kkm, krand = jax.random.split(key)
    u0t = random_start_vectors(krand, n, n_vectors)

    def fn(x_loc, key, u0t):
        op = _build_sharded_operator(x_loc, axes, None, "matrix_free", spec,
                                     use_pallas=use_pallas)
        # the sweep itself is jnp either way; the flag still governs k-means
        # (factorable specs are never truncated — the probe cannot arm)
        return _run_sharded(op, axes, key=key, u0t=u0t, k=k, eps=eps,
                            max_iter=max_iter, kmeans_iters=kmeans_iters,
                            n_total=n, embedding=embedding,
                            qr_every=qr_every,
                            snapshot_iters=snapshot_iters,
                            residual_tol=residual_tol,
                            force_reference=not use_pallas)

    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axes), P(), P()),
        out_specs=(P(),) * 9,
        check_vma=False,
    )(x, kkm, u0t)
    labels, v, emb_full, t_cols, done, status, iso, n_comp, comp = out
    health = HealthReport(col_status=status, isolated_rows=iso,
                          n_components=n_comp, components=comp)
    return make_pic_result(labels, v, t_cols, done, embedding=embedding,
                           embeddings=emb_full, health=health)


# ---------------------------------------------------------------------------
# Segmented (resumable) execution — the sharded engines in bounded pieces
# ---------------------------------------------------------------------------
#
# The convergence carry threads THROUGH shard_map: the (n_loc, r) leaves
# (v, delta, snaps) stay row-sharded on the mesh between segments, the
# per-column stats replicate, and the supervisor (core/pipeline.py) sees
# one global PowerCarry it can checkpoint. Restoring hands plain host
# arrays back in; shard_map re-lays them out without changing a bit, so
# the resumed trajectory is the uninterrupted one (DESIGN.md §14).


def _carry_specs(axes) -> PowerCarry:
    """PartitionSpecs of the carry pytree: row-block leaves sharded over
    ``axes``, per-column stats replicated."""
    row, rep = P(axes), P()
    return PowerCarry(t=rep, v=row, delta=row, done=rep, t_cols=rep,
                      snaps=row, status=rep, best=rep, since=rep)


_SEG_STATICS = ("mesh", "shard_axes", "eps_scale", "engine", "affinity",
                "a_dtype", "fold_shift", "tile", "use_pallas",
                "block_sparse", "overlap", "mode", "qr_every",
                "snapshot_iters", "residual_tol", "inject_ring_fault")


@functools.partial(jax.jit, static_argnames=_SEG_STATICS + ("n_vectors",))
def distributed_gpic_segment_start(
    x: jax.Array,
    stop: jax.Array,
    *,
    key: jax.Array,
    mesh: Mesh,
    shard_axes: str | Sequence[str] = "data",
    eps_scale: float = 1e-5,
    engine: str = "explicit",
    affinity: AffinitySpec,
    a_dtype=jnp.float32,
    fold_shift: bool = False,
    tile: int | None = None,
    use_pallas: bool = True,
    block_sparse: bool = True,
    overlap: bool = True,
    n_vectors: int = 1,
    mode: str = "pic",
    qr_every: int = 1,
    snapshot_iters: tuple = (),
    residual_tol: float | None = None,
    inject_ring_fault: tuple | None = None,
):
    """Seed the sharded sweep-0 carry (the monolithic seeding: replicated
    random starts sliced per device, degree column normalized by the
    global psum) and run the first bounded segment. ``key`` is the krand
    half of the front door's split. Returns ``(carry, isolated_rows)``."""
    axes = _axis_tuple(shard_axes)
    n = x.shape[0]
    eps = eps_scale / n
    mesh_size = _mesh_size(mesh, axes)
    u0t = random_start_vectors(key, n, n_vectors)

    def fn(x_loc, u0t, stop):
        op = _build_sharded_operator(
            x_loc, axes, mesh_size, engine, affinity, a_dtype=a_dtype,
            fold_shift=fold_shift, tile=tile, use_pallas=use_pallas,
            block_sparse=block_sparse, overlap=overlap,
            inject_ring_fault=inject_ring_fault)
        idx = jax.lax.axis_index(axes)
        n_loc = op.degree.shape[0]
        u0t_loc = _local_slice(idx, n_loc, u0t)
        v0_loc = init_power_vectors_local(
            op.degree, u0t_loc, sum_fn=op.sum, dtype=jnp.float32)
        carry = init_power_carry(v0_loc, len(snapshot_iters))
        carry = power_iteration_segment(
            op, carry, eps, stop, mode=mode, qr_every=qr_every,
            snapshot_iters=snapshot_iters, residual_tol=residual_tol)
        iso = count_bad_rows(op.degree, sum_fn=op.sum)
        return carry, iso

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axes), P(), P()),
        out_specs=(_carry_specs(axes), P()),
        check_vma=False,
    )(x, u0t, stop)


@functools.partial(jax.jit, static_argnames=_SEG_STATICS)
def distributed_gpic_segment(
    x: jax.Array,
    carry: PowerCarry,
    stop: jax.Array,
    *,
    mesh: Mesh,
    shard_axes: str | Sequence[str] = "data",
    eps_scale: float = 1e-5,
    engine: str = "explicit",
    affinity: AffinitySpec,
    a_dtype=jnp.float32,
    fold_shift: bool = False,
    tile: int | None = None,
    use_pallas: bool = True,
    block_sparse: bool = True,
    overlap: bool = True,
    mode: str = "pic",
    qr_every: int = 1,
    snapshot_iters: tuple = (),
    residual_tol: float | None = None,
    inject_ring_fault: tuple | None = None,
) -> PowerCarry:
    """Advance a (possibly restored) carry by one bounded segment on the
    mesh — the operator is rebuilt inside shard_map from the row-sharded
    features, and the carry's row blocks stay sharded throughout."""
    axes = _axis_tuple(shard_axes)
    eps = eps_scale / x.shape[0]
    mesh_size = _mesh_size(mesh, axes)

    def fn(x_loc, carry_loc, stop):
        op = _build_sharded_operator(
            x_loc, axes, mesh_size, engine, affinity, a_dtype=a_dtype,
            fold_shift=fold_shift, tile=tile, use_pallas=use_pallas,
            block_sparse=block_sparse, overlap=overlap,
            inject_ring_fault=inject_ring_fault)
        return power_iteration_segment(
            op, carry_loc, eps, stop, mode=mode, qr_every=qr_every,
            snapshot_iters=snapshot_iters, residual_tol=residual_tol)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axes), _carry_specs(axes), P()),
        out_specs=_carry_specs(axes),
        check_vma=False,
    )(x, carry, stop)


@functools.partial(jax.jit, static_argnames=(
    "k", "mesh", "shard_axes", "kmeans_iters", "engine", "affinity",
    "a_dtype", "fold_shift", "tile", "use_pallas", "block_sparse",
    "overlap", "embedding", "snapshot_iters", "probe_components"))
def distributed_gpic_segment_finalize(
    x: jax.Array,
    carry: PowerCarry,
    iso: jax.Array,
    k: int,
    *,
    key: jax.Array,
    mesh: Mesh,
    shard_axes: str | Sequence[str] = "data",
    kmeans_iters: int = 25,
    engine: str = "explicit",
    affinity: AffinitySpec,
    a_dtype=jnp.float32,
    fold_shift: bool = False,
    tile: int | None = None,
    use_pallas: bool = True,
    block_sparse: bool = True,
    overlap: bool = True,
    embedding: str = "pic",
    snapshot_iters: tuple = (),
    probe_components: bool = True,
) -> PICResult:
    """Close a finished sharded carry into the monolithic run's PICResult:
    the ``_run_sharded`` tail — gather once, standardize, replicated
    k-means (``key`` is the kkm half of the split), the component probe
    when it arms — run inside shard_map with the identical reduction
    bindings."""
    axes = _axis_tuple(shard_axes)
    n = x.shape[0]
    mesh_size = _mesh_size(mesh, axes)
    probe = probe_components and affinity.truncated

    def fn(x_loc, carry_loc, key):
        _, _, gather = mesh_reductions(axes)
        t, v_loc, t_cols, done, snaps_loc, status = finalize_power_carry(
            carry_loc)
        if embedding == "ensemble":
            snaps_loc = backfill_snapshots(snaps_loc, v_loc, t,
                                           snapshot_iters)
            emb_loc = ensemble_embedding(snaps_loc)
        else:
            emb_loc = v_loc
        emb_full = gather(emb_loc)                  # once, after the loop
        v_full = emb_full if emb_loc is v_loc else gather(v_loc)
        emb = standardize_columns(emb_full)
        labels, _ = kmeans(key, emb, k, iters=kmeans_iters,
                           force_reference=not use_pallas)
        if probe:
            op = _build_sharded_operator(
                x_loc, axes, mesh_size, engine, affinity, a_dtype=a_dtype,
                fold_shift=fold_shift, tile=tile, use_pallas=use_pallas,
                block_sparse=block_sparse, overlap=overlap)
            idx = jax.lax.axis_index(axes)
            n_loc = op.degree.shape[0]
            n_comp, comp_loc = graph_component_probe(
                op, n, row_offset=idx * n_loc)
            comp_full = gather(comp_loc)
        else:
            n_comp = jnp.int32(-1)
            comp_full = jnp.full((n,), -1, jnp.int32)
        return labels, v_full, emb_full, t_cols, done, status, n_comp, \
            comp_full

    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axes), _carry_specs(axes), P()),
        out_specs=(P(),) * 8,
        check_vma=False,
    )(x, carry, key)
    labels, v, emb_full, t_cols, done, status, n_comp, comp = out
    health = HealthReport(col_status=status,
                          isolated_rows=iso.astype(jnp.int32),
                          n_components=n_comp, components=comp)
    return make_pic_result(labels, v, t_cols, done, embedding=embedding,
                           embeddings=emb_full, health=health)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "shard_axes", "affinity", "tile", "use_pallas",
    "max_components"))
def distributed_component_ids(
    x: jax.Array,
    *,
    mesh: Mesh,
    shard_axes: str | Sequence[str] = "data",
    affinity: AffinitySpec,
    tile: int | None = None,
    use_pallas: bool = True,
    max_components: int = 16,
):
    """Replicated (n_components, (n,) ids) of the truncated affinity graph
    — the mesh realization of the row-reorder probe
    (core/graph.py::graph_reorder_permutation). Runs the dense-grid
    streaming ring (the block-sparse plan is what the reorder exists FOR,
    so the probe must not depend on it) into ``graph_component_probe``;
    the resulting PARTITION is input-order-independent whenever the probe
    resolves the graph within its budget (core/health.py), which is what
    lets the reorder permutation stay a function of row content only.
    Ids follow probe seeding order with ``-1`` for rows never reached."""
    axes = _axis_tuple(shard_axes)
    n = x.shape[0]
    mesh_size = _mesh_size(mesh, axes)

    def fn(x_loc):
        op = _build_sharded_operator(
            x_loc, axes, mesh_size, "streaming", affinity, tile=tile,
            use_pallas=use_pallas, block_sparse=False)
        idx = jax.lax.axis_index(axes)
        n_loc = op.degree.shape[0]
        n_comp, comp_loc = graph_component_probe(
            op, n, row_offset=idx * n_loc, max_components=max_components)
        return n_comp, op.all_gather(comp_loc)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axes),),
        out_specs=(P(), P()),
        check_vma=False,
    )(x)


def shard_points(x, mesh: Mesh, shard_axes="data"):
    """Places (n, m) host data row-sharded on the mesh.

    n must divide evenly over the sharded device count (shard_map and the
    streaming ring both need equal row blocks) — trim or pad the input
    first; this raises a clear error instead of an opaque sharding one.
    """
    axes = _axis_tuple(shard_axes)
    x = jnp.asarray(x)
    n_dev = _mesh_size(mesh, axes)
    if x.shape[0] % n_dev:
        raise ValueError(
            f"shard_points: n={x.shape[0]} rows do not divide evenly over "
            f"{n_dev} devices on axes {axes}; pad or trim the input first")
    sharding = NamedSharding(mesh, P(axes))
    return jax.device_put(x, sharding)
