"""Pallas TPU kernel: fused multi-vector power-iteration step.

TPU adaptation of the paper's ``Multiply`` + ``Reduction`` + ``Norm`` CUDA
kernels (DESIGN.md §2), generalized to r power vectors at once. Computes in
ONE sweep of A:

    U = (A @ V) / d          for V of shape (n, r) — the degree-normalized
                             mat-mat. W V = (D^-1 A) V = D^-1 (A V), so W is
                             never materialized: the paper's NormMatrix kernel
                             and its O(n^2) extra read+write disappear — O1b.
                             The skinny (TM, TN) x (TN, r) product runs on the
                             MXU and amortizes the single HBM read of each A
                             tile across all r vectors (DESIGN.md §4): r times
                             the flops for the same O(n^2) memory traffic.
    partial L1 mass of U     (per row-tile per column, combined on the VPU)

The final per-column division V_{t+1} = U / ||U||_1 is an O(n r) epilogue
outside the kernel (the tiny combine the paper does with its tree-Reduction
kernel; on TPU this is a trivial jnp.sum — the CUDA interleaved-addressing
pattern has no TPU analogue, see DESIGN.md §8).

A may be rectangular (R, C): the sharded explicit path (DESIGN.md §9) runs
this kernel on its local (n/P, n) row stripe against the replicated V — the
same program the single-device square sweep compiles to, just a shorter
row grid.

A may be stored in bf16 (O4): tiles are upcast to f32 on load so the MXU
accumulates in f32 while HBM traffic halves (DESIGN.md §6).

Grid: (R/TM, C/TN), accumulating the product across the col-grid dimension j
(TPU grid order is sequential, minor-to-major, so revisiting the same output
block is the idiomatic accumulation pattern). Rows pad to a TM multiple and
columns to a TN multiple independently, so any tile pair divides evenly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import MXU_PRECISION


def _power_step_kernel(a_ref, v_ref, d_ref, u_ref, *, nj: int):
    j = pl.program_id(1)

    a = a_ref[...].astype(jnp.float32)   # (TM, TN) tile of A (f32 or bf16)
    v = v_ref[...]                       # (TN, r) slice of V
    partial = jax.lax.dot_general(
        a, v, (((1,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                    # (TM, r)

    @pl.when(j == 0)
    def _init():
        u_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        u_ref[...] += partial

    # last col-step: normalize the accumulated row block by the degree.
    # The floored divide is already zero-degree safe: d = 0 means the whole
    # A row is zero (nonnegative entries), so the accumulated u row is an
    # exact 0 and 0/1e-30 stays exactly 0; a NaN degree propagates NaN into
    # the iterate, which the loop's non-finite latch catches (DESIGN.md
    # §12). The divide form itself is pinned — a masked-where variant is
    # value-identical on healthy rows but perturbs interpret-mode XLA
    # fusion enough to break local/sharded trajectory parity (the
    # kernels/ops.py::_tiles discipline). Padding rows carry d = 1.0.
    @pl.when(j == nj - 1)
    def _norm():
        d = d_ref[...]                   # (TM, 1)
        u_ref[...] = u_ref[...] / jnp.maximum(d, 1e-30)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def degree_normalized_matmat(
    a: jax.Array,
    v: jax.Array,
    d: jax.Array,
    *,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """U = (A @ V) / d[:, None], one fused HBM sweep of A for all r columns.

    Shapes: v (C, r), d (R,); a is (R, C) [f32 or bf16 storage; R == C
    on the single-device square sweep, R == n/P on a sharded row stripe]
    or that matrix at a larger, zero-filled storage shape — the padded
    array the affinity build returns, consumed without a copy. Returns
    (R, r) f32. The single-vector ``degree_normalized_matvec`` is the r=1
    case.
    """
    n_rows, n_cols = d.shape[0], v.shape[0]
    r = v.shape[1]
    rp = pl.cdiv(a.shape[0], tm) * tm
    cp = pl.cdiv(a.shape[1], tn) * tn
    if (rp, cp) != a.shape:
        a = jnp.pad(a, ((0, rp - a.shape[0]), (0, cp - a.shape[1])))
    if cp != n_cols:
        v = jnp.pad(v, ((0, cp - n_cols), (0, 0)))
    if rp != n_rows:
        d = jnp.pad(d, (0, rp - n_rows), constant_values=1.0)

    grid = (rp // tm, cp // tn)
    u = pl.pallas_call(
        functools.partial(_power_step_kernel, nj=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
            pl.BlockSpec((tn, r), lambda i, j: (j, 0)),
            pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tm, r), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, r), jnp.float32),
        interpret=interpret,
        name="degree_normalized_matmat",
    )(a, v.astype(jnp.float32), d.astype(jnp.float32)[:, None])
    return u[:n_rows]


def degree_normalized_matvec(
    a: jax.Array,
    v: jax.Array,
    d: jax.Array,
    *,
    tm: int = 256,
    tn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """u = (A @ v) / d — the r=1 column of the fused mat-mat kernel."""
    return degree_normalized_matmat(
        a, v[:, None], d, tm=tm, tn=tn, interpret=interpret
    )[:, 0]


def power_step(
    a: jax.Array, v: jax.Array, d: jax.Array, *, tm: int = 256, tn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Full paper power step: V_{t+1} = (W V) / ||W V||_1 with W = D^-1 A.

    Accepts v of shape (n,) or (n, r); the L1 normalization is per column.
    """
    if v.ndim == 1:
        u = degree_normalized_matvec(a, v, d, tm=tm, tn=tn, interpret=interpret)
        return u / jnp.maximum(jnp.sum(jnp.abs(u)), 1e-30)
    u = degree_normalized_matmat(a, v, d, tm=tm, tn=tn, interpret=interpret)
    return u / jnp.maximum(jnp.sum(jnp.abs(u), axis=0, keepdims=True), 1e-30)
