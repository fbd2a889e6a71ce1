"""Validation + diagnostics layer: typed errors, health codes, graph probes.

Every GPIC entry point either succeeds with a diagnosable result or fails
with a typed, actionable error — never silent garbage (DESIGN.md §12).
Three pieces live here:

  - The :class:`GPICError` hierarchy: the exceptions the front door
    (``run_gpic``) raises for degenerate inputs and unrecoverable runs.
    ``InvalidInputError`` doubles as a ``ValueError`` so pre-existing
    ``except ValueError`` callers keep working.
  - :class:`HealthReport` + the ``COL_*`` per-column status codes: the
    device-side diagnostics every entry point threads through
    ``PICResult.health``. The arrays are computed THROUGH the operator's
    reduction primitives, so the local and sharded engines report
    identical diagnostics (the same parity discipline as the loop itself).
  - The degenerate-graph probes: :func:`count_bad_rows` (isolated-row
    count from the degree vector — the sweep itself needs no masking, see
    :func:`degree_guard`), :func:`graph_component_probe` (on-device
    connected-component check for truncated kNN graphs, via nonnegative
    reachability sweeps), and :func:`degree_guard` (masked-reciprocal
    utility for host-side callers).

The loop-side latches (zero-column, non-finite, stall) live in
``core/power.py``; the kernel-fallback record lives in ``kernels/ops.py``;
this module only defines the vocabulary they share.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .spans import SYNC, span


# ---------------------------------------------------------------------------
# Typed errors
# ---------------------------------------------------------------------------


class GPICError(Exception):
    """Base of every typed GPIC failure (catch-all for callers)."""


class InvalidInputError(GPICError, ValueError):
    """The input can never cluster: bad shape, n < k, empty, constant."""


class NonFiniteInputError(InvalidInputError):
    """The feature matrix contains NaN/Inf (opt out via sanitize=True)."""


class DegenerateGraphError(GPICError):
    """The affinity graph carries no usable structure (e.g. every row
    isolated: all similarities underflowed to exact zero)."""


class PowerDivergenceError(GPICError):
    """Every power-iteration column went non-finite or lost all mass —
    there is no embedding left to cluster."""


class CheckpointCorruptError(GPICError):
    """A convergence-carry snapshot failed its integrity check (per-leaf
    checksum mismatch, truncated/missing leaf file, unreadable manifest).
    The supervisor skips the corrupt snapshot back to the previous valid
    step (noted ``checkpoint_skipped:<dir>``) instead of crashing."""


class StragglerTimeout(GPICError):
    """A bounded execution segment exceeded the configured wall-clock
    budget (``GPICConfig.straggler_timeout``) — the watchdog signal the
    supervisor classifies as retryable, resuming the segment from the
    last snapshot instead of re-running from sweep 0."""


# ---------------------------------------------------------------------------
# Per-column status codes (bitmask — a column can stall AND hit max_iter)
# ---------------------------------------------------------------------------

COL_OK = 0          #: converged by the acceleration (or residual) rule
COL_MAXITER = 1     #: ran to the iteration cap without converging
COL_STALLED = 2     #: acceleration stopped improving for STALL_PATIENCE
#                      sweeps (periodic/oscillating trajectory) — diagnostic
#                      only, the column keeps iterating
COL_NONFINITE = 4   #: NaN/Inf appeared in the column; it was zeroed+latched
COL_ZERO = 8        #: the column's L1 mass hit exact zero; latched

_STATUS_NAMES = (
    (COL_MAXITER, "maxiter"),
    (COL_STALLED, "stalled"),
    (COL_NONFINITE, "nonfinite"),
    (COL_ZERO, "zero"),
)

#: note prefixes that record a RECOVERY event (the supervisor resumed,
#: retried, or skipped a corrupt snapshot) rather than residual damage —
#: a run whose only notes are recovery notes and whose arrays are clean
#: classifies 'recovered', not 'degraded' (ClusteringFaultHarness)
RECOVERY_NOTE_PREFIXES = (
    "resumed:",
    "retry:",
    "straggler:",
    "checkpoint_skipped:",
    "kernel_fallback_retried:",
    "kernel_fallback_resumed:",
)


def is_recovery_note(note: str) -> bool:
    """True when ``note`` records a supervisor recovery event (resume /
    retry / corrupt-snapshot skip) rather than residual result damage."""
    return note.startswith(RECOVERY_NOTE_PREFIXES)


def describe_status(code: int) -> tuple[str, ...]:
    """Human-readable flag names for one column's status bitmask."""
    code = int(code)
    if code == COL_OK:
        return ("ok",)
    return tuple(name for bit, name in _STATUS_NAMES if code & bit)


# ---------------------------------------------------------------------------
# HealthReport
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class HealthReport:
    """Per-run diagnostics carried on ``PICResult.health``.

    All array fields are computed through the operator's reduction
    primitives inside the one convergence engine, so a sharded run reports
    bitwise the same values as the local run of the same problem.
    """
    col_status: jax.Array     # (r,) int32 COL_* bitmask per power column
    isolated_rows: jax.Array  # () int32 — rows whose degree is not > 0
    #                           (exact-zero kNN/underflow rows AND non-finite
    #                           degrees both count: neither can anchor a row)
    n_components: jax.Array   # () int32 — components found by the kNN-graph
    #                           probe; -1 = probe not run (dense spec);
    #                           max_components+1 = capped ("at least")
    components: jax.Array     # (n,) int32 per-row component id (-1 unprobed)
    #: host-side event strings (sanitization applied, kernel fallbacks...)
    #: — static metadata attached by the front door, not a traced leaf
    notes: tuple = field(metadata=dict(static=True), default=())

    def to_dict(self) -> dict:
        """Host-side dict view (concrete results only) — the per-request
        status object the serving path returns alongside labels.

        ``status`` classifies the whole run: 'ok' (clean arrays, no
        notes), 'recovered' (clean arrays, but the supervisor resumed /
        retried / skipped a corrupt snapshot on the way — the recovery
        history is in ``notes``), or 'degraded' (bad columns, isolated
        rows, or a non-recovery event such as sanitization or an
        un-retried kernel fallback).
        """
        import numpy as np
        status = np.asarray(self.col_status)
        codes = status.tolist()
        bad_columns = sum(1 for c in codes if c != COL_OK)
        iso = int(self.isolated_rows)
        recovery = [n for n in self.notes if is_recovery_note(n)]
        damage = [n for n in self.notes if not is_recovery_note(n)]
        if bad_columns or iso or damage:
            run_status = "degraded"
        elif recovery:
            run_status = "recovered"
        else:
            run_status = "ok"
        return {
            "status": run_status,
            "col_status": [describe_status(c) for c in codes],
            "bad_columns": bad_columns,
            "isolated_rows": iso,
            "n_components": int(self.n_components),
            "notes": list(self.notes),
            "recovery": recovery,
        }

    def summary(self) -> str:
        """One human-readable line of the run's health (concrete results
        only) — status class, bad-column / isolated-row counts, and the
        notes (including the supervisor's retry/resume history)."""
        d = self.to_dict()
        parts = [
            f"status={d['status']}",
            f"bad_columns={d['bad_columns']}/{len(d['col_status'])}",
            f"isolated_rows={d['isolated_rows']}",
        ]
        if d["n_components"] >= 0:
            parts.append(f"n_components={d['n_components']}")
        flagged = [f"{i}:{'+'.join(f)}" for i, f in enumerate(d["col_status"])
                   if f != ("ok",)]
        if flagged:
            parts.append("cols[" + " ".join(flagged) + "]")
        if d["notes"]:
            parts.append("notes[" + "; ".join(d["notes"]) + "]")
        return "GPIC health: " + " ".join(parts)


def empty_health(r: int, n: int) -> HealthReport:
    """An all-OK report (used by paths that compute no diagnostics)."""
    return HealthReport(
        col_status=jnp.zeros((r,), jnp.int32),
        isolated_rows=jnp.int32(0),
        n_components=jnp.int32(-1),
        components=jnp.full((n,), -1, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Zero-degree guards (DESIGN.md §12)
# ---------------------------------------------------------------------------


def degree_guard(u: jax.Array, d: jax.Array) -> jax.Array:
    """(A V) / d with rows of non-positive or non-finite degree masked to
    exact zero — a utility for host-side / out-of-band callers.

    The sweep kernels themselves keep the floored
    ``u / jnp.maximum(d, 1e-30)`` divide, which is already zero-degree
    safe: for a nonnegative A, d = 0 means the whole A row is zero, so u
    is an exact 0 and the floor returns exactly 0; a NaN degree propagates
    NaN into the iterate, where the loop's COL_NONFINITE latch catches and
    quarantines it. The kernel divide form is also PINNED: this masked
    variant is value-identical on healthy rows but perturbs interpret-mode
    XLA fusion enough to break the local/sharded trajectory-parity
    discipline (DESIGN.md §12), so it must not be substituted into the
    sweep path. ``u`` is (n, r) or (n,); ``d`` (n,).
    """
    ok = d > 0
    safe = jnp.where(ok, d, 1.0)
    if u.ndim == 2:
        return jnp.where(ok[:, None], u / safe[:, None], 0.0)
    return jnp.where(ok, u / safe, 0.0)


def count_bad_rows(d: jax.Array, sum_fn=None) -> jax.Array:
    """() int32 count of rows whose degree cannot anchor them (not > 0).
    ``sum_fn`` finishes the cross-chunk combine (identity locally)."""
    local = jnp.sum(jnp.where(d > 0, 0, 1).astype(jnp.int32))
    return local if sum_fn is None else sum_fn(local)


# ---------------------------------------------------------------------------
# Disconnected-component probe
# ---------------------------------------------------------------------------


def graph_component_probe(op, n_total: int, *, row_offset=0,
                          max_components: int = 8, max_sweeps: int = 32):
    """On-device component check of the (truncated) affinity graph.

    Repeated nonnegative reachability expansion: starting from an indicator
    on the lowest-index unvisited row, one ``op.matmat`` sweep (unioned
    with one ``op.matmat_t`` sweep when the operator binds it) adds every
    row with a nonzero affinity entry into the reached set; the expansion
    runs until a fixed point, that set becomes one component, and the next
    seed is the lowest unvisited row — up to ``max_components`` seeds.

    Exactness across engines: for a nonnegative matrix and a {0,1}
    indicator the POSITIVITY pattern of A@v (and of Aᵀ@v) is independent
    of summation order (a sum of nonnegative terms is positive iff any
    term is), so the local and sharded engines (whose sweeps differ only
    in reduction order) compute bitwise-identical probe results — unlike
    the iterates themselves, which agree only to reduction-order noise.

    Symmetrized reachability: the kNN-truncated graph is DIRECTED (per-row
    top-k), and a forward sweep alone only grows along reverse edges — a
    row nobody selects (in-degree 0) is then unreachable from its own
    neighbors and gets misreported as a separate component even though the
    weak cluster is intact. Operators over truncated specs therefore bind
    ``matmat_t`` and the expansion walks A + Aᵀ reachability — the WEAKLY
    connected components, which is the quantity that decides whether power
    iteration mass can spread (W = D⁻¹A moves mass along either direction
    of an undirected similarity). Without ``matmat_t`` (symmetric dense
    specs) the forward sweep already covers both directions. Rows are
    visited at most ``max_sweeps`` hops out; if unvisited rows remain
    after ``max_components`` seeds the count reports
    ``max_components + 1`` ("at least").

    Returns ``(n_components () int32, comp (n_local,) int32)`` with comp
    ids in discovery order and -1 for never-reached rows.
    """
    n_local = op.degree.shape[0]
    gidx = row_offset + jnp.arange(n_local, dtype=jnp.int32)

    def expand(reached):
        def cond(c):
            _reached, grew, s = c
            return grew & (s < max_sweeps)

        def body(c):
            reached, _grew, s = c
            ind = reached.astype(jnp.float32)[:, None]
            u = op.matmat(ind)[:, 0]
            new = reached | (u > 0)
            if op.matmat_t is not None:
                new = new | (op.matmat_t(ind)[:, 0] > 0)
            grew = op.sum(
                jnp.sum((new & ~reached).astype(jnp.int32))) > 0
            return new, grew, s + 1

        reached, _, _ = jax.lax.while_loop(
            cond, body, (reached, jnp.bool_(True), jnp.int32(0)))
        return reached

    def comp_cond(c):
        _comp, count, visited = c
        unvisited = op.sum(jnp.sum((~visited).astype(jnp.int32)))
        return (unvisited > 0) & (count < max_components)

    def comp_body(c):
        comp, count, visited = c
        cand = jnp.where(visited, n_total, gidx)
        seed = -op.max(-jnp.min(cand))          # global min unvisited index
        reached = expand(gidx == seed)
        comp = jnp.where(reached & (comp < 0), count, comp)
        return comp, count + 1, visited | reached

    comp, count, visited = jax.lax.while_loop(
        comp_cond, comp_body,
        (jnp.full((n_local,), -1, jnp.int32), jnp.int32(0),
         jnp.zeros((n_local,), bool)))
    leftover = op.sum(jnp.sum((~visited).astype(jnp.int32)))
    return count + jnp.where(leftover > 0, 1, 0).astype(jnp.int32), comp


# ---------------------------------------------------------------------------
# Front-door input validation (host-side; run_gpic)
# ---------------------------------------------------------------------------


def validate_features(x, k: int, *, sanitize: bool = False):
    """Front-door feature checks. Returns ``(x, notes)`` — possibly
    sanitized — or raises a typed error.

    Raises :class:`InvalidInputError` for shapes that can never cluster
    (ndim != 2, empty, n < k) and for an all-identical feature matrix
    (every pairwise similarity equal → the embedding is constant);
    :class:`NonFiniteInputError` for NaN/Inf features unless
    ``sanitize=True``, which zero-fills them and records the event in the
    returned notes. Value checks need concrete data; under a tracer
    (run_gpic called inside a caller's jit) they are skipped and the
    device-side latches carry the load.
    """
    notes: list[str] = []
    if x.ndim != 2:
        raise InvalidInputError(
            f"features must be a (n, m) matrix, got shape {x.shape}")
    n, m = x.shape
    if n == 0 or m == 0:
        raise InvalidInputError(f"empty feature matrix (shape {x.shape})")
    if n < k:
        raise InvalidInputError(
            f"cannot form k={k} clusters from n={n} points")
    if isinstance(x, jax.core.Tracer):
        return x, tuple(notes)
    x = jnp.asarray(x)
    bad = jnp.sum(~jnp.isfinite(x))
    with span(SYNC):
        n_bad = int(bad)
    if n_bad:
        if not sanitize:
            raise NonFiniteInputError(
                f"{n_bad} non-finite feature value(s); pass sanitize=True "
                "to zero-fill them (recorded in PICResult.health.notes)")
        x = jnp.nan_to_num(x, nan=0.0, posinf=0.0, neginf=0.0)
        notes.append(f"sanitized:{n_bad}_nonfinite_features")
    # per-column extrema instead of comparing against row 0: a reduction
    # needs no indexing, so it also runs on a row-sharded array whose mesh
    # axes are Explicit (the default of ``jax.make_mesh``)
    same = jnp.all(jnp.max(x, axis=0) == jnp.min(x, axis=0))
    with span(SYNC):
        constant = bool(same)
    if constant:
        raise InvalidInputError(
            "all feature rows are identical — every pairwise affinity is "
            "equal and the power embedding is constant; clustering is "
            "undefined on this input")
    return x, tuple(notes)


def raise_for_health(health: HealthReport, n: int) -> None:
    """Post-run host check: raise when the result is unusable (ALL rows
    isolated / ALL columns dead); partial damage returns with the report
    populated instead. No-op on traced values (jit'd caller)."""
    if isinstance(health.col_status, jax.core.Tracer):
        return
    import numpy as np
    with span(SYNC):
        iso = int(health.isolated_rows)
    if iso >= n:
        raise DegenerateGraphError(
            f"every one of the {n} rows is isolated (zero degree) — the "
            "affinity graph is empty; widen sigma / raise knn_k")
    with span(SYNC):
        status = np.asarray(health.col_status)
    fatal = COL_NONFINITE | COL_ZERO
    if status.size and bool(((status & fatal) != 0).all()):
        names = [describe_status(c) for c in status.tolist()]
        raise PowerDivergenceError(
            f"every power-iteration column went dead ({names}) — no "
            "embedding left to cluster; check feature scaling "
            f"({iso}/{n} rows isolated)")
