"""Pallas TPU kernel: tall-skinny Gram matrix G = VᵀV (DESIGN.md §10).

The block re-orthonormalization of the orthogonal embedding mode needs
(n, r)ᵀ(n, r) products every ``qr_every`` sweeps — an O(n r²) reduction
whose input is the tall-skinny engine state. The kernel sweeps V once in
(TM, r) row tiles, runs the (r, TM) × (TM, r) outer contraction on the MXU
in f32, and accumulates the (r, r) result in VMEM across the row grid —
one HBM read of V, no (n, r) temporary, f32 accumulation regardless of the
state dtype.

Grid: (n/TM,). r pads to the 8-sublane boundary with zero columns (zeros
contribute zero Gram entries, so no masking epilogue is needed); rows pad
to a TM multiple the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import MXU_PRECISION


def _gram_kernel(v_ref, g_ref):
    i = pl.program_id(0)
    v = v_ref[...].astype(jnp.float32)                   # (TM, rp)
    partial = jax.lax.dot_general(
        v, v, (((0,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                                    # (rp, rp)

    @pl.when(i == 0)
    def _init():
        g_ref[...] = partial

    @pl.when(i != 0)
    def _acc():
        g_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def gram(v: jax.Array, *, tm: int = 512, interpret: bool = False) -> jax.Array:
    """G = VᵀV for tall-skinny V (n, r); returns (r, r) f32."""
    n, r = v.shape
    rp = max(8, pl.cdiv(r, 8) * 8)
    n_pad = pl.cdiv(n, tm) * tm
    # pad in the NATIVE dtype — the kernel casts each tile on load, so a
    # bf16 state is read from HBM at bf16 width (a host-side f32 cast
    # would materialize an (n, r) temporary and double the read traffic)
    vp = jnp.pad(v, ((0, n_pad - n), (0, rp - r)))

    g = pl.pallas_call(
        _gram_kernel,
        grid=(n_pad // tm,),
        in_specs=[pl.BlockSpec((tm, rp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rp, rp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, rp), jnp.float32),
        interpret=interpret,
    )(vp)
    return g[:r, :r]
