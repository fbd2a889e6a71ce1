"""Public dispatch layer over the kernel implementations.

Each op is registered in a (op, mode) table with up to three execution
modes (DESIGN.md §7):

  'pallas'     — the fused Pallas TPU kernels. On non-TPU backends (this
                 container is CPU-only) they execute in ``interpret=True``
                 mode: the kernel body runs in Python/XLA per grid step,
                 which validates correctness of the exact TPU program. On a
                 real TPU the same calls lower to Mosaic.
  'streaming'  — A-free Pallas kernels that regenerate affinity tiles on
                 the fly inside the power step (kernels/streaming.py).
  'reference'  — the pure-jnp oracles (kernels/ref.py), used by tests and
                 by benchmarks to compare fused-kernel vs unfused HLO.

The backend probe is evaluated ONCE at import (it cannot change within a
process) and can be pinned explicitly for CI / TPU runs with the
``REPRO_FORCE_INTERPRET`` env var: 1/true/interpret forces interpret mode,
0/false/compiled forces compiled Mosaic lowering.

Tile sizes default to the static autotuner in kernels/tuning.py; pass
``tm``/``tn`` to override.

Graceful degradation (DESIGN.md §12): every public wrapper guards its
kernel dispatch — a Pallas lowering/compile failure (or a fault injected
with ``forced_kernel_failure``) degrades that op to the 'reference' oracle
for the rest of the process, writes the reason to stderr and records it
in ``kernel_fallbacks()``, which the pipeline surfaces as health notes.
"""
from __future__ import annotations

import contextlib
import os
import sys
from typing import Callable

import jax
import jax.numpy as jnp

from . import ref
from .affinity import affinity_and_degree as _affinity_pallas
from .block_sparse import block_liveness as _liveness_pallas
from .block_sparse import block_sparse_matmat as _bs_matmat_pallas
from .block_sparse import (
    block_sparse_streaming_degree as _bs_degree_streaming,
)
from .block_sparse import (
    block_sparse_streaming_matmat as _bs_streaming_pallas,
)
from .gram import gram as _gram_pallas
from .kmeans_assign import kmeans_assign as _assign_pallas
from .power_step import degree_normalized_matmat as _dnmm_pallas
from .power_step import degree_normalized_matvec as _dnmv_pallas
from .power_step import power_step as _power_pallas
from .row_topk import row_topk as _row_topk_pallas
from .streaming import affinity_degree_streaming as _degree_streaming
from .streaming import affinity_matmat as _streaming_pallas
from .tuning import choose_tiles

_INTERPRET_ENV = "REPRO_FORCE_INTERPRET"


def _probe_interpret() -> bool:
    """True when kernels must run in interpret mode (once, at import)."""
    val = os.environ.get(_INTERPRET_ENV, "").strip().lower()
    if val in ("1", "true", "interpret"):
        return True
    if val in ("0", "false", "compiled"):
        return False
    return jax.default_backend() != "tpu"


_INTERPRET: bool = _probe_interpret()


def _interpret() -> bool:
    return _INTERPRET


# ---------------------------------------------------------------------------
# (op, mode) registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[tuple[str, str], Callable] = {}


def register(op: str, mode: str):
    """Decorator: register ``fn`` as the ``mode`` implementation of ``op``."""

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, mode)] = fn
        return fn

    return deco


def dispatch(op: str, mode: str) -> Callable:
    """Resolve an implementation; raises with the available modes on miss."""
    try:
        return _REGISTRY[(op, mode)]
    except KeyError:
        raise ValueError(
            f"no {mode!r} implementation of {op!r}; available: "
            f"{modes_for(op) or '(none)'}"
        ) from None


def modes_for(op: str) -> tuple[str, ...]:
    return tuple(sorted(m for (o, m) in _REGISTRY if o == op))


def _resolve_mode(mode: str | None, force_reference: bool,
                  default: str = "pallas") -> str:
    if mode is not None:
        return mode
    return "reference" if force_reference else default


# ---------------------------------------------------------------------------
# Graceful degradation: per-op kernel → reference fallback (DESIGN.md §12)
# ---------------------------------------------------------------------------

_FALLBACKS: dict[str, str] = {}
_FORCED_FAILURES: dict[str, str] = {}


def kernel_fallbacks() -> dict[str, str]:
    """Snapshot of ops that have degraded to the reference oracle in this
    process: ``{op: reason}``. The pipeline diffs this around each entry
    call to attach ``kernel_fallback:<op>`` notes to the health report."""
    return dict(_FALLBACKS)


def reset_kernel_fallbacks() -> None:
    """Forget recorded fallbacks so ops dispatch to kernels again. Pair
    with ``jax.clear_caches()``: dispatch happens at trace time, so a
    cached jit program keeps whatever path it was traced with."""
    _FALLBACKS.clear()


@contextlib.contextmanager
def forced_kernel_failure(op: str, reason: str = "forced kernel failure"):
    """Fault injection: make the next kernel dispatch of ``op`` raise, so
    the guarded wrapper exercises its reference fallback. Pair with
    ``jax.clear_caches()`` before AND after — dispatch is a trace-time
    decision, so cached programs bypass both the fault and the recovery."""
    _FORCED_FAILURES[op] = reason
    try:
        yield
    finally:
        _FORCED_FAILURES.pop(op, None)


def _guarded(op: str, kernel_thunk: Callable, ref_thunk: Callable):
    """Run the fused kernel; if it raises (Pallas lowering/compile failure
    or an injected fault), degrade to the jnp reference oracle, record the
    reason once, and keep serving the oracle for the rest of the process.
    Same math, unfused HLO — a slow correct answer instead of a crash.

    The degradation is never silent: it is written to stderr when it
    happens, recorded in ``kernel_fallbacks()``, and noted on the run's
    health report; a chip run (``chip_smoke.py``) refuses any of them."""
    if op in _FALLBACKS:
        return ref_thunk()
    try:
        if op in _FORCED_FAILURES:
            raise RuntimeError(_FORCED_FAILURES[op])
        return kernel_thunk()
    except Exception as e:  # noqa: BLE001 — any lowering failure degrades
        _FALLBACKS[op] = f"{type(e).__name__}: {e}"
        print(f"kernels.ops: {op} kernel failed and now runs its jnp "
              f"reference ({_FALLBACKS[op]})", file=sys.stderr)
        return ref_thunk()


def _tiles(n: int, tm: int | None, tn: int | None, *, r: int = 1,
           m: int = 0, a_bytes: int = 4) -> tuple[int, int]:
    """Resolve (tm, tn): explicit overrides win, else the static autotuner
    keyed on the wide dimension ``n``. Rectangular stripe sweeps
    deliberately use the SAME tile choice as the square build (not one
    shrunk to the stripe height): distributed-vs-single-device trajectory
    parity rests on the two paths compiling the same tiled program, and
    in interpret mode even a row-only tile change perturbs XLA fusion and
    hence f32 rounding. The cost — padding a short (n/P) row block up to
    the square-build tile — is a TPU-tuning follow-up (see ROADMAP).
    Exception: the streaming ring's stages are (n/P, n/P) blocks, so their
    ``n`` IS the block size — ring tiling intentionally differs from the
    single-device streaming sweep (ulp-level parity; DESIGN.md §9)."""
    if tm is not None and tn is not None:
        return tm, tn
    atm, atn = choose_tiles(n, r=r, m=m, a_bytes=a_bytes)
    return tm or atm, tn or atn


def resolve_tiles(n: int, tm: int | None = None, tn: int | None = None, *,
                  r: int = 1, m: int = 0, a_bytes: int = 4) -> tuple[int, int]:
    """Public tile resolution with the wrappers' exact policy — operators
    building a block plan call this ONCE and pass the pinned (tm, tn) into
    every sweep that consumes the plan: the autotuner's choice depends on
    the call shape (r enters the VMEM fit), so per-call resolution could
    hand the probe's r=1 matmat a different grid than the power sweep's
    and misalign the plan's block coordinates."""
    return _tiles(n, tm, tn, r=r, m=m, a_bytes=a_bytes)


# -- registrations ----------------------------------------------------------

register("affinity_and_degree", "pallas")(_affinity_pallas)
register("affinity_and_degree", "reference")(ref.affinity_and_degree_ref)
register("degree_normalized_matvec", "pallas")(_dnmv_pallas)
register("degree_normalized_matvec", "reference")(ref.degree_normalized_matvec_ref)
register("degree_normalized_matmat", "pallas")(_dnmm_pallas)
register("degree_normalized_matmat", "reference")(ref.degree_normalized_matmat_ref)
register("streaming_matmat", "streaming")(_streaming_pallas)
register("streaming_matmat", "reference")(ref.affinity_matmat_ref)
register("streaming_degree", "streaming")(_degree_streaming)
register("streaming_degree", "reference")(ref.affinity_degree_streaming_ref)
register("power_step", "pallas")(_power_pallas)
register("power_step", "reference")(ref.power_step_ref)
register("gram", "pallas")(_gram_pallas)
register("gram", "reference")(ref.gram_ref)
register("kmeans_assign", "pallas")(_assign_pallas)
register("kmeans_assign", "reference")(ref.kmeans_assign_ref)
register("row_topk", "pallas")(_row_topk_pallas)
register("row_topk", "reference")(ref.row_topk_ref)
register("block_sparse_matmat", "pallas")(_bs_matmat_pallas)
register("block_sparse_matmat", "reference")(ref.block_sparse_matmat_ref)
register("block_sparse_streaming_matmat", "streaming")(_bs_streaming_pallas)
register("block_sparse_streaming_matmat", "reference")(
    ref.block_sparse_streaming_matmat_ref)
register("block_sparse_streaming_degree", "streaming")(_bs_degree_streaming)
register("block_sparse_streaming_degree", "reference")(
    ref.block_sparse_streaming_degree_ref)
register("block_liveness", "pallas")(_liveness_pallas)
register("block_liveness", "reference")(ref.block_liveness_ref)


def _spec_kind_sigma(spec, kind: str, sigma: float) -> tuple[str, float]:
    """Resolve (kind, sigma) with an AffinitySpec taking precedence over
    the legacy loose kwargs (duck-typed: any object with .kind/.sigma)."""
    if spec is None:
        return kind, sigma
    return spec.kind, float(spec.sigma)


# ---------------------------------------------------------------------------
# Public jit-friendly wrappers (stable API; modules call these, not the
# registry directly).
# ---------------------------------------------------------------------------


def affinity_and_degree(xn, xc=None, *, kind="cosine_shifted", sigma=1.0,
                        spec=None, scale_r=None, scale_c=None, thr=None,
                        tm=None, tn=None, out_dtype=jnp.float32,
                        row_offset=0, col_offset=0,
                        force_reference=False, mode=None):
    """Fused A + D build (paper kernels 1-2). See kernels/affinity.py.

    ``xc=None`` is the square self-affinity; with ``xc`` given the result
    is the (R, C) stripe at (row_offset, col_offset) of the global matrix
    — the sharded explicit path's per-device build (DESIGN.md §9).

    ``spec`` (an AffinitySpec) supplies kind/sigma; the pass-1 statistic
    arrays ``scale_r``/``scale_c`` (adaptive local scales) and ``thr``
    (per-row truncation thresholds) realize its policies in-tile
    (DESIGN.md §11).

    A comes at its storage shape — rows and columns rounded up to the tile
    multiples, the pad entries exact zeros — in every mode, and the sweep
    kernels consume it without a copy: the engines store ONE (n, n) array.
    The logical matrix is ``a[:R, :C]``.
    """
    kind, sigma = _spec_kind_sigma(spec, kind, sigma)
    mode = _resolve_mode(mode, force_reference)
    n_rows = xn.shape[0]
    n_cols = n_rows if xc is None else xc.shape[0]
    tm_, tn_ = _tiles(max(n_rows, n_cols), tm, tn, m=xn.shape[1],
                      a_bytes=jnp.dtype(out_dtype).itemsize)

    def _ref():
        a, deg = ref.affinity_and_degree_ref(
            xn, xc, kind=kind, sigma=sigma,
            row_offset=row_offset, col_offset=col_offset,
            scale_r=scale_r, scale_c=scale_c, thr=thr)
        a = a.astype(out_dtype)           # honor O4 storage dtype here too
        return jnp.pad(a, ((0, -(-n_rows // tm_) * tm_ - n_rows),
                           (0, -(-n_cols // tn_) * tn_ - n_cols))), deg

    if mode == "reference":
        return _ref()
    return _guarded("affinity_and_degree", lambda: dispatch(
        "affinity_and_degree", mode)(
        xn, xc, kind=kind, sigma=sigma, tm=tm_, tn=tn_, out_dtype=out_dtype,
        row_offset=row_offset, col_offset=col_offset,
        scale_r=scale_r, scale_c=scale_c, thr=thr,
        interpret=_interpret(),
    ), _ref)


def degree_normalized_matvec(a, v, d, *, tm=None, tn=None,
                             force_reference=False, mode=None):
    """u = (A v)/d — fused paper kernels 3+6 (W never materialized)."""
    mode = _resolve_mode(mode, force_reference)
    if mode == "reference":
        return ref.degree_normalized_matvec_ref(a, v, d)
    tm_, tn_ = _tiles(a.shape[0], tm, tn, a_bytes=a.dtype.itemsize)
    return _guarded("degree_normalized_matvec", lambda: dispatch(
        "degree_normalized_matvec", mode)(
        a, v, d, tm=tm_, tn=tn_, interpret=_interpret()
    ), lambda: ref.degree_normalized_matvec_ref(a, v, d))


def degree_normalized_matmat(a, v, d, *, tm=None, tn=None,
                             force_reference=False, mode=None):
    """U = (A V)/d for V (C, r) — ONE HBM sweep of A for all r vectors.

    ``a`` may be a rectangular (R, C) row stripe of the global matrix (the
    sharded explicit path, DESIGN.md §9); d is the stripe's (R,) degrees.
    ``a`` may also come at the padded storage shape that
    ``affinity_and_degree`` returns; tiles are resolved from the logical
    shape (len(d), len(v)) either way.
    """
    mode = _resolve_mode(mode, force_reference)
    if mode == "reference":
        return ref.degree_normalized_matmat_ref(a, v, d)
    tm_, tn_ = _tiles(max(d.shape[0], v.shape[0]), tm, tn, r=v.shape[1],
                      a_bytes=a.dtype.itemsize)
    return _guarded("degree_normalized_matmat", lambda: dispatch(
        "degree_normalized_matmat", mode)(
        a, v, d, tm=tm_, tn=tn_, interpret=_interpret()
    ), lambda: ref.degree_normalized_matmat_ref(a, v, d))


def streaming_matmat(x, v, d=None, xc=None, *, kind="cosine_shifted",
                     sigma=1.0, spec=None, scale_r=None, scale_c=None,
                     thr=None, thr_c=None, tm=None, tn=None,
                     row_offset=0, col_offset=0,
                     force_reference=False, mode=None):
    """U = (A V)/d with A regenerated on the fly — no (n, n) allocation.

    With ``xc`` given, computes the (R, r) partial product of the stripe
    at (row_offset, col_offset) against col features xc (C, m) and V
    (C, r) — one ring stage of the sharded streaming engine. ``d=None``
    skips the degree normalization so stripe partials can accumulate.
    ``spec``/``scale_r``/``scale_c``/``thr`` as in :func:`affinity_and_degree`;
    ``thr_c`` (C,) applies each COLUMN's own threshold instead — the
    Aᵀ-stripe product of the symmetrized reachability probe.
    """
    kind, sigma = _spec_kind_sigma(spec, kind, sigma)
    mode = _resolve_mode(mode, force_reference, default="streaming")

    def _ref():
        return ref.affinity_matmat_ref(x, v, d, xc, kind=kind, sigma=sigma,
                                       row_offset=row_offset,
                                       col_offset=col_offset,
                                       scale_r=scale_r, scale_c=scale_c,
                                       thr=thr, thr_c=thr_c)

    if mode == "reference":
        return _ref()
    n = max(x.shape[0], x.shape[0] if xc is None else xc.shape[0])
    tm_, tn_ = _tiles(n, tm, tn, r=v.shape[1], m=x.shape[1])
    return _guarded("streaming_matmat", lambda: dispatch(
        "streaming_matmat", mode)(
        x, v, d, xc, kind=kind, sigma=sigma, tm=tm_, tn=tn_,
        row_offset=row_offset, col_offset=col_offset,
        scale_r=scale_r, scale_c=scale_c, thr=thr, thr_c=thr_c,
        interpret=_interpret(),
    ), _ref)


def streaming_degree(x, xc=None, *, kind="cosine_shifted", sigma=1.0,
                     spec=None, scale_r=None, scale_c=None, thr=None,
                     tm=None, tn=None, row_offset=0, col_offset=0,
                     force_reference=False, mode=None):
    """Degree vector D = A 1 in one streamed sweep (RowSum without A).

    With ``xc`` given, returns the partial row sums of the stripe at
    (row_offset, col_offset) over that column block only.
    ``spec``/``scale_r``/``scale_c``/``thr`` as in :func:`affinity_and_degree`.
    """
    kind, sigma = _spec_kind_sigma(spec, kind, sigma)
    mode = _resolve_mode(mode, force_reference, default="streaming")

    def _ref():
        return ref.affinity_degree_streaming_ref(
            x, xc, kind=kind, sigma=sigma,
            row_offset=row_offset, col_offset=col_offset,
            scale_r=scale_r, scale_c=scale_c, thr=thr)

    if mode == "reference":
        return _ref()
    n = max(x.shape[0], x.shape[0] if xc is None else xc.shape[0])
    tm_, tn_ = _tiles(n, tm, tn, m=x.shape[1])
    return _guarded("streaming_degree", lambda: dispatch(
        "streaming_degree", mode)(
        x, xc, kind=kind, sigma=sigma, tm=tm_, tn=tn_,
        row_offset=row_offset, col_offset=col_offset,
        scale_r=scale_r, scale_c=scale_c, thr=thr,
        interpret=_interpret()
    ), _ref)


def row_topk(x, xc=None, *, k, stat="similarity", kind="cosine_shifted",
             sigma=1.0, spec=None, scale_r=None, scale_c=None,
             tm=None, tn=None, row_offset=0, col_offset=0,
             force_reference=False, mode=None):
    """(R, k) per-row descending top-k scores — pass 1 of the two-pass
    affinity-graph build (kernels/row_topk.py, DESIGN.md §11).

    ``stat='neg_sqdist'`` is the k-th-nearest-neighbor pass (adaptive local
    scales); ``stat='similarity'`` the truncation-threshold pass. Streamed:
    no (R, C) allocation in any mode but 'reference'.
    """
    kind, sigma = _spec_kind_sigma(spec, kind, sigma)
    mode = _resolve_mode(mode, force_reference)

    def _ref():
        return ref.row_topk_ref(x, xc, k=k, stat=stat, kind=kind, sigma=sigma,
                                scale_r=scale_r, scale_c=scale_c,
                                row_offset=row_offset, col_offset=col_offset)

    if mode == "reference":
        return _ref()
    n = max(x.shape[0], x.shape[0] if xc is None else xc.shape[0])
    tm_, tn_ = _tiles(n, tm, tn, m=x.shape[1])
    return _guarded("row_topk", lambda: dispatch("row_topk", mode)(
        x, xc, k=k, stat=stat, kind=kind, sigma=sigma, tm=tm_, tn=tn_,
        row_offset=row_offset, col_offset=col_offset,
        scale_r=scale_r, scale_c=scale_c,
        interpret=_interpret(),
    ), _ref)


def block_sparse_matmat(a, v, d, counts, col_idx, max_b, *, tm, tn,
                        force_reference=False, mode=None):
    """U = (A V)/d visiting only the plan's live blocks (DESIGN.md §13).

    Tiles are REQUIRED here (no autotuning): the plan's block coordinates
    are only meaningful on the grid they were computed for, so the caller
    pins (tm, tn) once via :func:`resolve_tiles` and reuses them for the
    plan and every sweep. Bitwise-equal to :func:`degree_normalized_matmat`
    at the same tiles.
    """
    mode = _resolve_mode(mode, force_reference)

    def _ref():
        return ref.block_sparse_matmat_ref(a, v, d, counts, col_idx,
                                           tm=tm, tn=tn)

    if mode == "reference":
        return _ref()
    return _guarded("block_sparse_matmat", lambda: dispatch(
        "block_sparse_matmat", mode)(
        a, v, d, counts, col_idx, max_b, tm=tm, tn=tn,
        interpret=_interpret(),
    ), _ref)


def block_sparse_streaming_matmat(x, v, d=None, xc=None, *, counts, col_idx,
                                  max_b, kind="cosine_shifted", sigma=1.0,
                                  spec=None, scale_r=None, scale_c=None,
                                  thr=None, tm, tn, row_offset=0,
                                  col_offset=0, force_reference=False,
                                  mode=None):
    """Streaming U = (A V)/d regenerating only live feature tiles — the
    A-free twin of :func:`block_sparse_matmat` (same pinned-tile contract;
    ``d=None`` leaves ring-stage partials unnormalized)."""
    kind, sigma = _spec_kind_sigma(spec, kind, sigma)
    mode = _resolve_mode(mode, force_reference, default="streaming")

    def _ref():
        return ref.block_sparse_streaming_matmat_ref(
            x, v, d, xc, counts=counts, col_idx=col_idx, tm=tm, tn=tn,
            kind=kind, sigma=sigma,
            row_offset=row_offset, col_offset=col_offset,
            scale_r=scale_r, scale_c=scale_c, thr=thr)

    if mode == "reference":
        return _ref()
    return _guarded("block_sparse_streaming_matmat", lambda: dispatch(
        "block_sparse_streaming_matmat", mode)(
        x, v, d, xc, counts=counts, col_idx=col_idx, max_b=max_b,
        kind=kind, sigma=sigma, tm=tm, tn=tn,
        row_offset=row_offset, col_offset=col_offset,
        scale_r=scale_r, scale_c=scale_c, thr=thr,
        interpret=_interpret(),
    ), _ref)


def block_sparse_streaming_degree(x, xc=None, *, counts, col_idx, max_b,
                                  kind="cosine_shifted", sigma=1.0, spec=None,
                                  scale_r=None, scale_c=None, thr=None,
                                  tm, tn, row_offset=0, col_offset=0,
                                  force_reference=False, mode=None):
    """Degree vector over live blocks only (same pinned-tile contract)."""
    kind, sigma = _spec_kind_sigma(spec, kind, sigma)
    mode = _resolve_mode(mode, force_reference, default="streaming")

    def _ref():
        return ref.block_sparse_streaming_degree_ref(
            x, xc, counts=counts, col_idx=col_idx, tm=tm, tn=tn,
            kind=kind, sigma=sigma,
            row_offset=row_offset, col_offset=col_offset,
            scale_r=scale_r, scale_c=scale_c, thr=thr)

    if mode == "reference":
        return _ref()
    return _guarded("block_sparse_streaming_degree", lambda: dispatch(
        "block_sparse_streaming_degree", mode)(
        x, xc, counts=counts, col_idx=col_idx, max_b=max_b,
        kind=kind, sigma=sigma, tm=tm, tn=tn,
        row_offset=row_offset, col_offset=col_offset,
        scale_r=scale_r, scale_c=scale_c, thr=thr,
        interpret=_interpret(),
    ), _ref)


def block_liveness(x, xc=None, *, kind="cosine_shifted", sigma=1.0, spec=None,
                   scale_r=None, scale_c=None, thr=None, tm, tn,
                   row_offset=0, col_offset=0, force_reference=False,
                   mode=None):
    """(nI, nJ) int32 live-block map of the masked stripe, A-free — the
    plan source for streaming engines (explicit engines read liveness off
    the stored matrix with core.affinity.dense_block_live instead)."""
    kind, sigma = _spec_kind_sigma(spec, kind, sigma)
    mode = _resolve_mode(mode, force_reference)

    def _ref():
        return ref.block_liveness_ref(
            x, xc, tm=tm, tn=tn, kind=kind, sigma=sigma,
            row_offset=row_offset, col_offset=col_offset,
            scale_r=scale_r, scale_c=scale_c, thr=thr)

    if mode == "reference":
        return _ref()
    return _guarded("block_liveness", lambda: dispatch(
        "block_liveness", mode)(
        x, xc, kind=kind, sigma=sigma, tm=tm, tn=tn,
        row_offset=row_offset, col_offset=col_offset,
        scale_r=scale_r, scale_c=scale_c, thr=thr,
        interpret=_interpret(),
    ), _ref)


def power_step(a, v, d, *, tm=None, tn=None, force_reference=False,
               mode=None):
    """v' = W v / ||W v||_1 — one full paper iteration (kernels 6+4+5)."""
    mode = _resolve_mode(mode, force_reference)
    if mode == "reference":
        return ref.power_step_ref(a, v, d)
    r = 1 if v.ndim == 1 else v.shape[1]
    tm_, tn_ = _tiles(a.shape[0], tm, tn, r=r, a_bytes=a.dtype.itemsize)
    return _guarded("power_step", lambda: dispatch("power_step", mode)(
        a, v, d, tm=tm_, tn=tn_, interpret=_interpret()
    ), lambda: ref.power_step_ref(a, v, d))


def gram(v, *, tm=512, force_reference=False, mode=None):
    """G = VᵀV for the tall-skinny (n, r) engine state — the reduction that
    prices the block re-orthonormalization (DESIGN.md §10). One HBM sweep
    of V, f32 accumulation. Sharded callers compute the LOCAL chunk's Gram
    here and finish with the operator's ``sum`` primitive."""
    mode = _resolve_mode(mode, force_reference)
    if mode == "reference":
        return ref.gram_ref(v)
    return _guarded("gram", lambda: dispatch("gram", mode)(
        v, tm=tm, interpret=_interpret()), lambda: ref.gram_ref(v))


def kmeans_assign(x, cents, *, tm=512, force_reference=False, mode=None):
    """k-means assignment (labels, sq-dists)."""
    mode = _resolve_mode(mode, force_reference)
    if mode == "reference":
        return ref.kmeans_assign_ref(x, cents)
    return _guarded("kmeans_assign", lambda: dispatch("kmeans_assign", mode)(
        x, cents, tm=tm, interpret=_interpret()
    ), lambda: ref.kmeans_assign_ref(x, cents))


def flash_attention(q, k, v, *, causal=True, block_q=512, block_k=512,
                    force_reference=False):
    """Causal flash attention, GQA-aware (LM-substrate hot-spot kernel)."""
    from .flash_attention import flash_attention as _flash_pallas
    if force_reference:
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash_pallas(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=_interpret())
