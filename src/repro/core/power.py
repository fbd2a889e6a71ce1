"""Batched truncated power iteration — the multi-vector engine core.

One ``(n, r)`` state matrix replaces r independent while-loops: every
iteration performs ONE degree-normalized mat-mat (one sweep of A, however
it is realized — explicit Pallas tiles, streamed tiles, or the factored
matrix-free product), so the per-iteration HBM traffic is independent of
the number of power vectors (DESIGN.md §4).

The engine is parameterized by a :class:`PowerOperator` (DESIGN.md §9):
``matmat`` performs the one sweep on the caller's *local* row chunk of the
state, and the ``sum``/``max``/``all_gather`` reduction primitives finish
the cross-chunk combines. Bound to plain jnp identities the engine IS the
single-device loop; bound to ``psum``/``pmax``/``all_gather`` over mesh
axes inside ``shard_map`` the SAME loop is the sharded one — there is no
second implementation of the convergence math anywhere in the repo.

Three embedding modes share the one loop (DESIGN.md §10):

  mode='pic'         EXACTLY the paper's per-vector Algorithm 1/2 loop
                     (lines 6-15): each column carries its own delta and
                     acceleration-based stopping flag, and a converged
                     column is frozen (its value and delta stop updating)
                     while the remaining columns keep iterating. A column's
                     trajectory is identical to what a dedicated
                     single-vector loop would have produced — the batching
                     changes the cost model, not the math.
  mode='orthogonal'  block/subspace iteration: column 0 keeps the classic
                     pinned PIC trajectory (bitwise — deflation target),
                     while columns 1..r-1 are Cholesky-QR re-orthonormalized
                     against it and each other every ``qr_every`` sweeps, so
                     they converge to the successive invariant-subspace
                     directions of W instead of all collapsing onto the
                     dominant one. Block columns are NOT frozen (freezing a
                     coupled subspace breaks its convergence); their done
                     flags latch the first eps-crossing for reporting.
  ensemble           :func:`ensemble_power_iteration` snapshots the classic
                     mode='pic' block at geometrically spaced diffusion
                     times and returns the stack — a multiscale embedding.

The Gram products that price the re-orthonormalization go through
``op.gram`` (locally the Pallas tall-skinny Gram kernel or its jnp oracle)
and are finished across chunks by ``op.sum``, so the sharded engines run
the identical block algebra.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from ..kernels.tuning import f32_matmul

from .health import COL_MAXITER, COL_NONFINITE, COL_STALLED, COL_ZERO

EMBEDDINGS = ("pic", "orthogonal", "ensemble")

#: sweeps without a strict improvement of a column's acceleration statistic
#: before COL_STALLED latches (periodic/oscillating trajectories never
#: improve; slowly-converging ones improve every sweep) — diagnostic only,
#: the stall latch never stops or alters the iteration
STALL_PATIENCE = 10


def _identity(x):
    return x


def _gram_jnp(v):
    """Local-chunk Gram VᵀV in f32 — the default (oracle-math) binding;
    operator builders rebind to the Pallas tall-skinny kernel."""
    v32 = v.astype(jnp.float32)
    return f32_matmul(v32.T, v32)


@dataclass(frozen=True)
class PowerOperator:
    """One degree-normalized sweep of A plus its reduction binding.

    Attributes:
      matmat: maps the local (n_loc, r) chunk of V to the local chunk of
        (A V) / d — ONE sweep of A however realized. Any gathering the
        realization needs (e.g. replicating V across a mesh before a
        stripe mat-mat) happens inside.
      degree: the local (n_loc,) degree chunk backing the sweep (v0 seed
        and diagnostics; None for bare-callable wrapping).
      sum: finishes a cross-chunk sum of an already-locally-reduced value
        (identity locally; ``psum`` over mesh axes when sharded).
      max: same for max (identity / ``pmax``).
      all_gather: maps a local (n_loc, ...) chunk to the global (n, ...)
        array (identity locally; tiled ``all_gather`` when sharded).
      gram: maps the local (n_loc, r) chunk to its LOCAL (r, r) Gram
        VᵀV partial; ``sum`` finishes the cross-chunk combine. Defaults to
        the jnp oracle math; operator builders bind the Pallas kernel.
      matmat_t: maps the local (n_loc, r) chunk of V to the local chunk of
        Aᵀ V — UNNORMALIZED, positivity-only semantics: the symmetrized
        reachability probe (core/health.py) unions its sign pattern with
        the forward sweep's to walk the kNN graph's reverse edges. Bound
        only by builders of truncated specs (the only graphs that can be
        asymmetric); None means "A is symmetric, forward reach suffices".
    """
    matmat: Callable[[jax.Array], jax.Array]
    degree: jax.Array | None = None
    sum: Callable[[jax.Array], jax.Array] = field(default=_identity)
    max: Callable[[jax.Array], jax.Array] = field(default=_identity)
    all_gather: Callable[[jax.Array], jax.Array] = field(default=_identity)
    gram: Callable[[jax.Array], jax.Array] = field(default=_gram_jnp)
    matmat_t: Callable[[jax.Array], jax.Array] | None = None


def as_operator(op) -> PowerOperator:
    """Wrap a bare ``matmat`` callable as a local (single-chunk) operator."""
    if isinstance(op, PowerOperator):
        return op
    return PowerOperator(matmat=op)


def orthonormalize_block(op, v):
    """Cholesky-QR of the (n_loc, r) block with column 0 pinned.

    G = VᵀV (global: local Gram finished by ``op.sum``) = LLᵀ, Q = VL⁻ᵀ —
    column j of Q is column j of V orthogonalized against all earlier
    columns and L2-normalized (thin QR). Column 0 is returned UNTOUCHED
    (deflation-style pinning: the classic degree-seeded PIC trajectory is
    the block's first basis vector, bitwise), which only drops Q's column-0
    rescale — orthogonality of the later columns against it is unaffected.
    All chunks compute the same replicated (r, r) factor, so the transform
    is chunk-local after one ``op.sum``.

    A numerically singular Gram (columns momentarily aligned — possible
    with ``qr_every`` > 1 on a fast-mixing graph) makes the f32 Cholesky
    non-finite; that step's re-orthonormalization is SKIPPED (the block
    passes through unchanged) and the next one retries after the power
    sweep re-mixes the columns. The skip predicate is computed on ``ell``
    — a REPLICATED value (every chunk factors the same global G) — so all
    chunks of a sharded run make the identical apply/skip decision; a
    chunk-local test on the transformed rows could diverge per chunk and
    silently mix QR'd and raw chunks of one global state. The guard costs
    nothing on the healthy path — the selected values are bitwise the
    factored ones.
    """
    g = op.sum(op.gram(v))                                       # (r, r)
    ell = jnp.linalg.cholesky(g)
    q = jax.scipy.linalg.solve_triangular(ell, v.T, lower=True).T
    out = jnp.concatenate([v[:, :1], q[:, 1:]], axis=1)
    return jnp.where(jnp.all(jnp.isfinite(ell)), out, v)


def subspace_residual(op, v, u):
    """Relative invariant-subspace residual ||U − VΛ||_F / ||U||_F with
    U = W V (the sweep output) and Λ the least-squares Rayleigh block
    (VᵀV)⁻¹VᵀU — the ||AQ − QΛ||-style stopping statistic of the
    orthogonal embedding mode (DESIGN.md §11).

    One Gram of the (n_loc, 2r) concatenation [V | U] supplies every term
    (the existing tall-skinny Gram kernel; ``op.sum`` finishes the
    cross-chunk combine, so the sharded value is the single-device one):

        ||U − VΛ||²_F = tr(Gᵤᵤ) − tr(Gᵥᵤᵀ Λ)

    exact for any V (the pinned block is orthonormal only up to column 0's
    free scale, which the normal-equations solve absorbs).
    """
    r = v.shape[1]
    g = op.sum(op.gram(jnp.concatenate([v, u], axis=1)))       # (2r, 2r)
    gvv, gvu, guu = g[:r, :r], g[:r, r:], g[r:, r:]
    lam = jnp.linalg.solve(gvv, gvu)
    denom = jnp.trace(guu)
    res2 = denom - jnp.trace(f32_matmul(gvu.T, lam))
    rel = jnp.sqrt(jnp.maximum(res2, 0.0) / jnp.maximum(denom, 1e-30))
    # a singular Gram (columns momentarily aligned) solves to non-finite;
    # report "not converged" and let the next QR re-mix, mirroring the
    # orthonormalize_block skip guard. A zero U (all-zero columns after a
    # dead sweep) makes the statistic 0/0 -> 0 — a FALSE "converged"; the
    # denom > 0 gate reports inf instead so a dead block can never
    # satisfy the residual rule.
    return jnp.where(jnp.isfinite(rel) & (denom > 0), rel, jnp.inf)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PowerCarry:
    """The FULL convergence-loop carry — everything the engine threads
    through one sweep, as one checkpointable pytree (DESIGN.md §14).

    A run interrupted after any sweep resumes bitwise-identically from
    this value: the loop body is a pure function of (carry, operator), so
    exporting the carry (``train/checkpoint.py``), restoring it, and
    continuing with :func:`power_iteration_segment` replays EXACTLY the
    trajectory the uninterrupted loop would have produced — same
    eps-crossings, same health latches, same per-column counters.
    """
    t: jax.Array        # () int32 — completed sweeps
    v: jax.Array        # (n_loc, r) — the engine state block
    delta: jax.Array    # (n_loc, r) — |v_t − v_{t−1}| (delta_0 = v_0)
    done: jax.Array     # (r,) bool — per-column convergence latches
    t_cols: jax.Array   # (r,) int32 — per-column iteration counters
    snaps: jax.Array    # (n_loc, r, S) — ensemble snapshot stack (S = 0
    #                     outside embedding='ensemble')
    status: jax.Array   # (r,) int32 — COL_* health latches
    best: jax.Array     # (r,) f32 — best acceleration seen (stall rule)
    since: jax.Array    # (r,) int32 — sweeps since ``best`` improved


def _carry_state(carry: PowerCarry) -> tuple:
    """The raw while_loop 9-tuple (kept a plain tuple inside the loop so
    the traced jaxpr is byte-identical to the historical one)."""
    return (carry.t, carry.v, carry.delta, carry.done, carry.t_cols,
            carry.snaps, carry.status, carry.best, carry.since)


def _init_state(v0, n_snapshots: int) -> tuple:
    """The sweep-0 loop state — the ONE construction both the monolithic
    loop and :func:`init_power_carry` use, so a segmented run starts from
    exactly the uninterrupted run's initial state."""
    r = v0.shape[1]
    return (
        jnp.int32(0), v0, v0,                      # delta_0 <- v_0 (line 1)
        jnp.zeros((r,), bool), jnp.zeros((r,), jnp.int32),
        jnp.zeros(v0.shape + (n_snapshots,), v0.dtype),
        jnp.zeros((r,), jnp.int32),                # status
        jnp.full((r,), jnp.inf, jnp.float32),      # best accel (stall)
        jnp.zeros((r,), jnp.int32),                # sweeps since improved
    )


def init_power_carry(v0, n_snapshots: int = 0) -> PowerCarry:
    """The sweep-0 :class:`PowerCarry` for an (n_loc, r) start block.
    ``n_snapshots`` sizes the ensemble snapshot stack (0 = none)."""
    return PowerCarry(*_init_state(v0, n_snapshots))


def power_carry_like(n, r, n_snapshots: int = 0, dtype=jnp.float32):
    """ShapeDtypeStruct pytree of the carry for a global (n, r) state —
    the ``like`` argument checkpoint restore needs (DESIGN.md §14)."""
    sds = jax.ShapeDtypeStruct
    return PowerCarry(
        t=sds((), jnp.int32), v=sds((n, r), dtype), delta=sds((n, r), dtype),
        done=sds((r,), jnp.bool_), t_cols=sds((r,), jnp.int32),
        snaps=sds((n, r, n_snapshots), dtype), status=sds((r,), jnp.int32),
        best=sds((r,), jnp.float32), since=sds((r,), jnp.int32))


def _validate_loop_args(mode, qr_every, residual_tol, r):
    """Shared host-side argument checks of the loop and its segmented
    form. Returns (block, residual) — the static routing flags."""
    if mode not in ("pic", "orthogonal"):
        raise ValueError(
            f"unknown power-loop mode {mode!r} (expected 'pic' or "
            "'orthogonal'; 'ensemble' is ensemble_power_iteration)")
    if qr_every < 1:
        raise ValueError(f"qr_every must be >= 1, got {qr_every}")
    if residual_tol is not None and not float(residual_tol) > 0.0:
        raise ValueError(
            f"residual_tol must be > 0 (a relative residual), got "
            f"{residual_tol}")
    block = mode == "orthogonal" and r > 1
    residual = residual_tol is not None
    if residual and not block:
        raise ValueError(
            "residual_tol needs a QR-coupled block (mode='orthogonal' "
            f"with r > 1); got mode={mode!r}, r={r} — the rule could "
            "never arm")
    return block, residual


def _run_loop_state(op, state, eps, bound, mode, qr_every, snapshot_iters,
                    residual_tol=None, collect_health=True):
    """Advance a raw loop state until ``t >= bound`` or every column is
    done — the while_loop shared by the monolithic loop (bound = max_iter,
    a Python int, compiling the historical jaxpr unchanged) and the
    segmented form (bound = a traced stop sweep). The BODY is the one
    function in the repo that defines a sweep; segmentation only changes
    where the while_loop stops, never what a sweep computes — that is the
    whole bitwise-resume guarantee (DESIGN.md §14).
    """
    block, residual = _validate_loop_args(
        mode, qr_every, residual_tol, state[1].shape[1])
    op = as_operator(op)
    r = state[1].shape[1]

    def cond(state):
        t, _v, _delta, done = state[:4]
        return jnp.logical_and(t < bound, jnp.logical_not(jnp.all(done)))

    def body(state):
        t, v, delta, done, t_cols, snaps, status, best, since = state
        u = op.matmat(v)                                        # (n_loc, r)
        l1 = op.sum(jnp.sum(jnp.abs(u), axis=0))                # (r,)
        v_next = u / jnp.maximum(l1, 1e-30)[None, :]
        fault = jnp.zeros((r,), bool)
        if collect_health:
            # per-column fault latches: exact-zero L1 mass (the column has
            # no signal left — e.g. an all-zero v0 column, previously a
            # hidden 0/0 frozen forever without reporting) and NaN/Inf
            # (non-finite input or a corrupted sweep). A faulted column is
            # zeroed so the damage cannot leak into other columns through
            # a later QR, and latched done. Both tests read the ALREADY
            # computed (and already cross-chunk-summed) l1 — a NaN/Inf
            # anywhere in the column propagates into its absolute sum, so
            # no additional (n, r) reduction is introduced (adding one
            # perturbs XLA's fusion of the existing loop reductions enough
            # to shift boundary eps-crossings in interpret mode, breaking
            # the local/sharded parity discipline) and every shard latches
            # identically off the replicated value.
            zero_col = l1 <= 0.0                                # (r,)
            bad_col = jnp.logical_not(jnp.isfinite(l1))         # (r,)
            fault = (zero_col | bad_col) & ~done
            v_next = jnp.where(fault[None, :], 0.0, v_next)
            status = (status
                      | jnp.where(zero_col & fault, COL_ZERO, 0)
                      | jnp.where(bad_col & fault, COL_NONFINITE, 0)
                      ).astype(jnp.int32)
        qr_now = (t + 1) % qr_every == 0
        if block:
            if qr_every == 1:
                v_next = orthonormalize_block(op, v_next)
            else:
                v_next = jax.lax.cond(
                    qr_now,
                    lambda vv: orthonormalize_block(op, vv),
                    lambda vv: vv, v_next)
        delta_next = jnp.abs(v_next - v)
        accel = op.max(jnp.max(jnp.abs(delta_next - delta), axis=0))  # (r,)
        # columns already done are frozen: keep prior value/delta, don't
        # count the iteration; columns converging NOW keep this update
        # (the per-vector loop applies the converging step before stopping).
        # In block mode only the pinned column 0 freezes — the QR-coupled
        # columns keep iterating (done latches the first crossing).
        freeze = done & (jnp.arange(r) == 0) if block else done
        v_next = jnp.where(freeze[None, :], v, v_next)
        delta_next = jnp.where(freeze[None, :], delta, delta_next)
        t_cols = t_cols + jnp.where(done, 0, 1).astype(jnp.int32)
        done = jnp.logical_or(done, accel <= eps)
        if collect_health:
            done = jnp.logical_or(done, fault)
            # stall detector: a column whose acceleration statistic has not
            # strictly improved on its best for STALL_PATIENCE sweeps is
            # flagged (periodic trajectories — e.g. a bipartite graph's
            # oscillation — repeat their accel values forever). Diagnostic
            # only: the flag never stops or alters the iteration.
            improved = accel < best
            since = jnp.where(done | improved, 0, since + 1).astype(
                jnp.int32)
            best = jnp.minimum(best, accel)
            status = (status | jnp.where(
                ~done & (since >= STALL_PATIENCE), COL_STALLED, 0)
            ).astype(jnp.int32)
        if residual:
            # priced at QR cadence only; gating on done[0] keeps column 0's
            # classic n_iter/converged stats bitwise (the subspace never
            # stops the loop before the pinned trajectory has finished)
            rel = jax.lax.cond(
                qr_now & done[0],
                lambda: subspace_residual(op, v, u),
                lambda: jnp.float32(jnp.inf))
            done = jnp.logical_or(done, rel <= residual_tol)
        for j, s in enumerate(snapshot_iters):
            snaps = snaps.at[:, :, j].set(
                jnp.where(t + 1 == s, v_next, snaps[:, :, j]))
        return (t + 1, v_next, delta_next, done, t_cols, snaps,
                status, best, since)

    return jax.lax.while_loop(cond, body, state)


def _power_loop(op, v0, eps, max_iter, mode, qr_every, snapshot_iters,
                residual_tol=None, collect_health=True):
    """The one convergence loop behind every embedding mode. Returns
    (t, V, t_cols, done, snaps, status) with snaps (n_loc, r, S) holding
    the block at each requested iteration count (S = len(snapshot_iters))
    and status the (r,) int32 per-column COL_* health bitmask.

    ``residual_tol`` (static; block mode only) arms the subspace residual
    stopping rule: on every QR step, once the pinned column 0 has converged
    by its classic acceleration rule, a relative residual <= residual_tol
    latches ALL remaining columns done — the block stops at subspace
    convergence instead of running to max_iter. None (the default) compiles
    the exact PR-3 loop.

    ``collect_health`` (static) arms the divergence latches: a column whose
    L1 mass hits exact zero (COL_ZERO) or that produced a NaN/Inf
    (COL_NONFINITE) is zeroed and latched done — the fault can never
    propagate into other columns through a later QR — and a column whose
    acceleration statistic stops improving for STALL_PATIENCE sweeps is
    flagged COL_STALLED (diagnostic only). On a clean run every latch
    predicate is False, so the selected values are bitwise the unlatched
    ones — the health layer is a pure observer (DESIGN.md §12).
    ``collect_health=False`` compiles the loop without the latch
    computations (the benchmark baseline for pricing them).
    """
    state = _init_state(v0, len(snapshot_iters))
    (t, v, _delta, done, t_cols, snaps,
     status, _best, _since) = _run_loop_state(
        op, state, eps, max_iter, mode, qr_every, snapshot_iters,
        residual_tol=residual_tol, collect_health=collect_health)
    if collect_health:
        status = (status | jnp.where(~done, COL_MAXITER, 0)).astype(
            jnp.int32)
    return t, v, t_cols, done, snaps, status


def power_iteration_segment(op, carry: PowerCarry, eps, stop, *, mode="pic",
                            qr_every=1, snapshot_iters=(),
                            residual_tol=None,
                            collect_health=True) -> PowerCarry:
    """Advance the convergence carry by a bounded segment: run sweeps
    until ``carry.t >= stop`` or every column is done, and return the new
    carry. ``stop`` may be a traced scalar (one compiled segment program
    serves every boundary) — the loop BODY is byte-identical to the
    monolithic loop's, so a run split into segments (with the carry
    round-tripped through a checkpoint between them) reproduces the
    uninterrupted trajectory bitwise (DESIGN.md §14). Apply
    :func:`finalize_power_carry` once ``stop`` has reached max_iter or
    all columns are done.
    """
    state = _run_loop_state(
        op, _carry_state(carry), eps, stop, mode, qr_every, snapshot_iters,
        residual_tol=residual_tol, collect_health=collect_health)
    return PowerCarry(*state)


def finalize_power_carry(carry: PowerCarry, *, collect_health=True):
    """Close out a finished carry exactly as the monolithic loop does on
    exit: latch COL_MAXITER on still-unconverged columns. Returns the
    ``(t, v, t_cols, done, snaps, status)`` tuple of ``_power_loop``."""
    status = carry.status
    if collect_health:
        status = (status | jnp.where(~carry.done, COL_MAXITER, 0)).astype(
            jnp.int32)
    return (carry.t, carry.v, carry.t_cols, carry.done, carry.snaps, status)


def backfill_snapshots(snaps, v, t, snapshot_iters):
    """Fill ensemble snapshot slots the loop never reached (early exit
    before their diffusion time) with the final frozen block — the ONE
    implementation of the backfill both the monolithic ensemble loop and
    the segmented finalize use."""
    written = jnp.asarray(snapshot_iters, jnp.int32) <= t         # (S,)
    return jnp.where(written[None, None, :], snaps, v[:, :, None])


def batched_power_iteration(op, v0, eps, max_iter, *, mode="pic",
                            qr_every=1, residual_tol=None,
                            collect_health=True, return_status=False):
    """Run the truncated power iteration on batched state.

    Args:
      op: a :class:`PowerOperator`, or a bare callable mapping V (n, r) to
        (A V) / d (wrapped as a local operator).
      v0: (n_loc, r) initial vectors — the caller's local row chunk of the
        global (n, r) state (the whole state on a single device).
      eps: the paper's acceleration threshold (typically 1e-5 / n).
      max_iter: iteration cap.
      mode: 'pic' (classic per-column loop, frozen columns) or
        'orthogonal' (block iteration, column 0 pinned — see module doc).
        With r = 1 both modes are the identical classic loop, bitwise.
      qr_every: re-orthonormalization period in sweeps ('orthogonal' only).
      residual_tol: arm the subspace residual stopping rule ('orthogonal'
        with r > 1 only): once column 0 has converged classically, a
        relative ||WV − VΛ|| residual <= residual_tol on a QR step stops
        the whole block (None — the default — runs the PR-3 loop bitwise).
      collect_health: arm the per-column divergence latches (zero-mass,
        non-finite, stall — see ``_power_loop``); False compiles the loop
        without them (the guard-overhead benchmark baseline).
      return_status: also return the (r,) int32 COL_* status bitmask as a
        fourth element (kept opt-in so the historical 3-tuple unpacking
        keeps working).

    Returns:
      (V, t_cols, done): final local (n_loc, r) state, per-column iteration
      counts (r,) int32, and per-column convergence flags (r,) bool — plus
      the (r,) status mask when ``return_status``. The counts/flags are
      replicated across chunks; gather V with ``op.all_gather`` if the
      full embedding is needed.
    """
    _t, v, t_cols, done, _snaps, status = _power_loop(
        op, v0, eps, max_iter, mode, qr_every, (),
        residual_tol=residual_tol, collect_health=collect_health)
    if return_status:
        return v, t_cols, done, status
    return v, t_cols, done


def default_snapshot_iters(max_iter, n_snapshots=4):
    """Geometrically spaced diffusion times max_iter/2^(S-1-j), ascending,
    deduplicated — the default ensemble schedule."""
    iters: list[int] = []
    for j in range(n_snapshots):
        t = max(1, max_iter // (2 ** (n_snapshots - 1 - j)))
        if not iters or t > iters[-1]:
            iters.append(t)
    return tuple(iters)


def ensemble_power_iteration(op, v0, eps, max_iter, *,
                             snapshot_iters: Sequence[int] | None = None):
    """Diffusion-time ensemble: the classic mode='pic' loop, with the block
    captured at each of ``snapshot_iters`` (static, ascending; default
    geometric in ``max_iter``). Per-column freezing means the state is
    constant once every column has converged, so snapshots past an early
    exit are backfilled with the final (frozen) block — no extra sweeps.

    Returns (snaps, t_cols, done, v, status): the (n_loc, r, S) snapshot
    stack plus the loop's ACTUAL final state v (== snaps[:, :, -1] whenever
    the last snapshot time is max_iter or past the exit; later if a custom
    schedule ends before convergence) and the (r,) COL_* status mask.
    Flatten snaps to the k-means embedding with :func:`ensemble_embedding`.
    """
    snapshot_iters = tuple(
        int(s) for s in (snapshot_iters if snapshot_iters is not None
                         else default_snapshot_iters(max_iter)))
    if not snapshot_iters or list(snapshot_iters) != sorted(
            set(snapshot_iters)):
        raise ValueError(
            f"snapshot_iters must be non-empty strictly ascending ints, "
            f"got {snapshot_iters!r}")
    if snapshot_iters[0] < 1 or snapshot_iters[-1] > max_iter:
        raise ValueError(
            f"snapshot_iters {snapshot_iters!r} must lie in [1, max_iter="
            f"{max_iter}]")
    t, v, t_cols, done, snaps, status = _power_loop(
        op, v0, eps, max_iter, "pic", 1, snapshot_iters)
    snaps = backfill_snapshots(snaps, v, t, snapshot_iters)
    return snaps, t_cols, done, v, status


def run_power_embedding(op, v0, eps, max_iter, *, embedding="pic",
                        qr_every=1, snapshot_iters=None, residual_tol=None):
    """Run the engine in the requested embedding mode — the one helper every
    entry point (local, sharded, oracle) calls, so mode routing exists once.

    Returns (v, t_cols, done, emb, status): the final local (n_loc, r)
    state, the per-column stats, the LOCAL chunk of the matrix to cluster
    (the state itself for 'pic'/'orthogonal'; the (n_loc, r·S) snapshot
    concatenation for 'ensemble'), and the (r,) int32 COL_* health mask.
    """
    if embedding not in EMBEDDINGS:
        raise ValueError(
            f"unknown embedding {embedding!r} (expected one of {EMBEDDINGS})")
    if residual_tol is not None and embedding != "orthogonal":
        raise ValueError(
            "residual_tol arms the subspace residual stopping rule of "
            "embedding='orthogonal' only")
    if embedding == "ensemble":
        snaps, t_cols, done, v, status = ensemble_power_iteration(
            op, v0, eps, max_iter, snapshot_iters=snapshot_iters)
        return v, t_cols, done, ensemble_embedding(snaps), status
    v, t_cols, done, status = batched_power_iteration(
        op, v0, eps, max_iter, mode=embedding, qr_every=qr_every,
        residual_tol=residual_tol, return_status=True)
    return v, t_cols, done, v, status


def ensemble_embedding(snaps):
    """Flatten an (n, r, S) snapshot stack to the (n, r·S) k-means
    embedding (column order c·S + s — the ONE canonical layout both the
    local and sharded paths use, so their embeddings agree column-for-
    column)."""
    return snaps.reshape(snaps.shape[0], -1)


def random_start_vectors(krand, n, n_vectors, dtype=jnp.float32):
    """(n, r-1) L1-normalized uniform random starts — columns 1..r-1 of the
    engine state (Lin & Cohen's multi-vector extension, O3). The single
    source of this recipe: single-host and distributed paths must draw
    bit-identical columns for their trajectories to agree."""
    if n_vectors <= 1:
        return jnp.zeros((n, 0), dtype)
    u0 = jax.random.uniform(krand, (n_vectors - 1, n), dtype)
    u0 = u0 / jnp.sum(u0, axis=1, keepdims=True)
    return u0.T


def init_power_vectors(krand, d, n_vectors, dtype=None):
    """Build the (n, r) start state: column 0 is the paper's degree start
    v_0 = D / sum(D) (Algorithm 2 lines 4-5); the rest are random starts."""
    dtype = dtype or d.dtype
    v0 = (d / jnp.maximum(jnp.sum(d), 1e-30)).astype(dtype)
    return jnp.concatenate(
        [v0[:, None], random_start_vectors(krand, d.shape[0], n_vectors, dtype)],
        axis=1)


def init_power_vectors_local(d_loc, u0t_loc, sum_fn=_identity, dtype=None):
    """Local-chunk variant of :func:`init_power_vectors`: column 0 is the
    degree start normalized by the GLOBAL degree mass (``sum_fn`` finishes
    the cross-chunk sum — identity locally, ``psum`` when sharded) and the
    remaining columns are the caller's local slice of the replicated random
    starts, so every chunk seeds exactly the single-device state."""
    dtype = dtype or d_loc.dtype
    dsum = sum_fn(jnp.sum(d_loc))
    v0 = (d_loc / jnp.maximum(dsum, 1e-30)).astype(dtype)
    return jnp.concatenate([v0[:, None], u0t_loc.astype(dtype)], axis=1)


def standardize_columns(v):
    """Per-column zero-mean / unit-variance rescale of the (n, r) embedding."""
    mu = jnp.mean(v, axis=0, keepdims=True)
    sd = jnp.maximum(jnp.std(v, axis=0, keepdims=True), 1e-30)
    return (v - mu) / sd
