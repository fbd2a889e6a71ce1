"""k-means (kmeans++ init + Lloyd iterations) on the (op, mode) kernel registry.

Used as the final step of PIC/GPIC (cluster the power-iteration embedding)
and, more generally, on (n, d) embeddings (e.g. LM token-embedding
clustering). The Lloyd assignment step — the O(n·k·d) hot loop — dispatches
through ``kernels.ops.kmeans_assign``: the fused Pallas kernel computes the
squared distances on the MXU and the argmin on the VPU with no (n, k)
distance matrix in HBM; ``force_reference=True`` routes it to the pure-jnp
oracle (same math, unfused HLO), mirroring every other op in the registry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..kernels.tuning import f32_matmul


def kmeans_plus_plus_init(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """kmeans++ seeding: iteratively sample points proportional to D^2."""
    n = x.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    cents0 = jnp.tile(x[first][None, :], (k, 1))

    def body(i, carry):
        cents, key, mind2 = carry
        d2_new = jnp.sum((x - cents[i - 1]) ** 2, axis=1)
        mind2 = jnp.minimum(mind2, d2_new)
        key, sub = jax.random.split(key)
        p = mind2 / jnp.maximum(jnp.sum(mind2), 1e-30)
        idx = jax.random.choice(sub, n, p=p)
        cents = cents.at[i].set(x[idx])
        return cents, key, mind2

    mind2 = jnp.full((n,), jnp.inf, x.dtype)
    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents0, key, mind2))
    return cents


def _canonicalize(labels: jax.Array, cents: jax.Array, k: int):
    """Relabel clusters in order of first appearance (point 0's cluster
    becomes id 0, the next unseen cluster id 1, ...). Cluster ids then
    depend only on the PARTITION, not on the kmeans++ sampling order — so
    two runs whose embeddings differ by reduction-order noise (e.g. the
    sharded vs single-device engines) produce bitwise-identical labels
    whenever they produce the same clustering. Centroids are permuted to
    match. Empty clusters sort last (stable)."""
    n = labels.shape[0]
    first = jnp.min(
        jnp.where(labels[None, :] == jnp.arange(k)[:, None],
                  jnp.arange(n)[None, :], n),
        axis=1)                                   # (k,) first index per id
    order = jnp.argsort(first)                    # old ids by first appearance
    rank = jnp.argsort(order)                     # old id -> canonical id
    return rank[labels].astype(jnp.int32), cents[order]


@functools.partial(jax.jit, static_argnames=("k", "iters", "force_reference"))
def kmeans(
    key: jax.Array, x: jax.Array, k: int, iters: int = 25,
    force_reference: bool = False, *, init: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Lloyd's algorithm. Returns (labels (n,), centroids (k, d)).

    An emptied cluster is reseeded to the point farthest from its assigned
    centroid (the i-th emptied cluster takes the i-th farthest point, so
    multiple empties land on distinct points) — deterministic given the
    seeded init, and it keeps all k clusters populated instead of letting
    two centroids collapse onto one blob (the old keep-previous-centroid
    fix could return fewer than k distinct labels under adversarial init).
    The assignment step runs the fused Pallas kernel unless
    ``force_reference`` routes it to the jnp oracle. Labels are
    canonicalized by first appearance (see ``_canonicalize``).
    ``init`` overrides the kmeans++ seeding with explicit (k, d) starting
    centroids (robustness tests drive the empty-cluster reseed with it).
    """
    x = x.astype(jnp.float32)
    n = x.shape[0]
    cents = (kmeans_plus_plus_init(key, x, k) if init is None
             else jnp.asarray(init, jnp.float32))

    def step(cents, _):
        assign, d2 = ops.kmeans_assign(x, cents,
                                       force_reference=force_reference)
        onehot = jax.nn.one_hot(assign, k, dtype=x.dtype)      # (n, k)
        counts = jnp.sum(onehot, axis=0)                        # (k,)
        sums = f32_matmul(onehot.T, x)                          # (k, d)
        empty = counts == 0
        # farthest-point reseed: i-th empty slot takes the i-th farthest
        # point (argsort is stable — deterministic under ties)
        order = jnp.argsort(-d2)                                # (n,) desc
        slot = jnp.clip(jnp.cumsum(empty) - 1, 0, n - 1)        # (k,)
        new = jnp.where(empty[:, None], x[order[slot]],
                        sums / jnp.maximum(counts, 1.0)[:, None])
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    labels, _ = ops.kmeans_assign(x, cents, force_reference=force_reference)
    return _canonicalize(labels, cents, k)


def kmeans_objective(x: jax.Array, labels: jax.Array, cents: jax.Array) -> jax.Array:
    """Sum of squared distances to assigned centroids (inertia)."""
    return jnp.sum(jnp.sum((x - cents[labels]) ** 2, axis=1))
