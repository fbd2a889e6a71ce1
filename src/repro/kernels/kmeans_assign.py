"""Pallas TPU kernel: k-means assignment step.

Computes, for a (TM, d) tile of points against the full (k, d) centroid set
held in VMEM, the squared distances on the MXU (expansion form) and the
argmin on the VPU — one read of the points, no (n, k) distance matrix in HBM.

Grid: (n/TM,). Centroids are small (k ≤ a few hundred), so they live in VMEM
for every grid step. k is padded to the 128-lane boundary with +inf distances.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import MXU_PRECISION


def _assign_kernel(x_ref, c_ref, csq_ref, lab_ref, dist_ref, *, k: int):
    x = x_ref[...]                              # (TM, d)
    c = c_ref[...]                              # (Kp, d)
    csq = csq_ref[...]                          # (1, Kp)

    xx = jnp.sum(x * x, axis=1, keepdims=True)  # (TM, 1)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=jnp.float32
    )                                           # (TM, Kp)
    d2 = xx + csq - 2.0 * xc

    kp = c.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2 = jnp.where(col < k, d2, jnp.inf)        # mask centroid padding

    lab_ref[...] = jnp.argmin(d2, axis=1, keepdims=True).astype(jnp.int32)
    dist_ref[...] = jnp.min(d2, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def kmeans_assign(
    x: jax.Array,
    cents: jax.Array,
    *,
    tm: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (labels (n,) int32, sq-dists (n,) f32) for points x (n, d)."""
    n, dim = x.shape
    k = cents.shape[0]
    kp = max(8, pl.cdiv(k, 8) * 8)
    n_pad = pl.cdiv(n, tm) * tm

    xp = jnp.pad(x.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    cp = jnp.pad(cents.astype(jnp.float32), ((0, kp - k), (0, 0)))
    csq = jnp.sum(cp * cp, axis=1)[None, :]     # (1, Kp)

    labels, dists = pl.pallas_call(
        functools.partial(_assign_kernel, k=k),
        grid=(n_pad // tm,),
        in_specs=[
            pl.BlockSpec((tm, dim), lambda i: (i, 0)),
            pl.BlockSpec((kp, dim), lambda i: (0, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tm, 1), lambda i: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_assign",
    )(xp, cp, csq)
    return labels[:n, 0], dists[:n, 0]
