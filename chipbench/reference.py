"""The plain reference: power iteration clustering as the GPIC paper's
Algorithm 1 states it, written from the paper and imported from nowhere
in the program.

- Gaussian affinity A_ij = exp(-|x_i - x_j|^2 / (2 sigma^2)), A_ii = 0,
  with |x_i - x_j|^2 = |x_i|^2 + |x_j|^2 - 2 x_i.x_j from one matmul;
- degrees d = A 1, start v_0 = d / sum(d), delta_0 = v_0;
- sweeps v_{t+1} = D^-1 A v_t / |D^-1 A v_t|_1, delta_{t+1} = |v_{t+1} - v_t|,
  stopping after the sweep where max |delta_{t+1} - delta_t| <= eps;
- k-means (k-means++ seeding, Lloyd, best of several restarts) on the
  standardized embedding, in float64 on the host.

A is never stored: every sweep regenerates it in row blocks, so the
reference fits on one chip next to nothing else, at any n the cells use.
On several chips each chip owns a stripe of rows (``shard_map``) and the
new vector is all-gathered. ``precision`` is the precision of both
matmuls: ``"highest"`` for the reference, a lower one for the control.
"""
from __future__ import annotations

import functools

import numpy as np

#: largest row block of a regenerated affinity stripe, in entries: 2^27
#: f32 entries are 512 MiB, a few of which (dot, exp, mask) fit any chip
BLOCK_ENTRIES = 1 << 27


def matmul(a, b, precision: str):
    """``a @ b`` with f32 accumulation at ``precision``, written out so that
    it computes the same on every backend: ``"highest"`` as the backend
    gives f32 products; ``"high"`` as three bf16 passes (each operand split
    into a bf16 head and tail, the tail-by-tail product left out), the
    algorithm of ``Precision.HIGH`` on a TPU; ``"default"`` as one bf16
    pass, that of ``Precision.DEFAULT``."""
    import jax.numpy as jnp

    def dot(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32)

    if precision == "highest":
        return jnp.matmul(a, b, precision="highest")
    if precision == "default":
        return dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(t):
        head = t.astype(jnp.bfloat16)
        return head, (t - head.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _row_block(n_loc: int, n: int) -> int:
    """Largest divisor of n_loc whose (block, n) stripe fits BLOCK_ENTRIES."""
    cap = max(1, BLOCK_ENTRIES // n)
    return max(b for b in range(1, min(n_loc, cap) + 1) if n_loc % b == 0)


@functools.lru_cache(maxsize=None)
def _program(n: int, m: int, chips: int, precision: str, max_iter: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((chips,), ("rows",),
                         devices=jax.devices()[:chips])
    n_loc = n // chips
    block = _row_block(n_loc, n)

    def stripe(x_loc, x, row0, inv2s2, v=None):
        """Row sums of this chip's stripe of A (v None) or its A v."""
        sq = jnp.sum(x * x, axis=1)

        def one(b):
            xb = jax.lax.dynamic_slice_in_dim(x_loc, b * block, block)
            d2 = (jnp.sum(xb * xb, axis=1)[:, None] + sq[None, :]
                  - 2.0 * matmul(xb, x.T, precision))
            a = jnp.exp(-jnp.maximum(d2, 0.0) * inv2s2)
            rows = row0 + b * block + jnp.arange(block)
            a = jnp.where(rows[:, None] == jnp.arange(n)[None, :], 0.0, a)
            if v is None:
                return jnp.sum(a, axis=1)
            return matmul(a, v, precision)

        out = jax.lax.map(one, jnp.arange(n_loc // block))
        return out.reshape(n_loc)

    def body_fn(x_loc, x, inv2s2, eps, stop_at):
        row0 = jax.lax.axis_index("rows") * n_loc
        gather = functools.partial(jax.lax.all_gather, axis_name="rows",
                                   tiled=True)
        d = gather(stripe(x_loc, x, row0, inv2s2))
        v0 = d / jnp.sum(d)

        def cond(s):
            t, _v, _delta, done = s[:4]
            return (t < max_iter) & (~done | (t < stop_at))

        def body(s):
            t, v, delta, done, n_own, snap, own = s
            u = gather(stripe(x_loc, x, row0, inv2s2, v)) / d
            v_next = u / jnp.sum(jnp.abs(u))
            delta_next = jnp.abs(v_next - v)
            accel = jnp.max(jnp.abs(delta_next - delta))
            t = t + 1
            snap = jnp.where(t == stop_at, v_next, snap)
            n_own = jnp.where(done, n_own, t)
            own = jnp.where(done, own, v_next)
            return (t, v_next, delta_next, done | (accel <= eps), n_own,
                    snap, own)

        s0 = (jnp.int32(0), v0, v0, jnp.bool_(False), jnp.int32(0), v0, v0)
        _t, _v, _delta, _done, n_own, snap, own = jax.lax.while_loop(
            cond, body, s0)
        return d, snap, own, n_own

    rows = NamedSharding(mesh, P("rows"))
    rep = NamedSharding(mesh, P())
    fn = jax.jit(jax.shard_map(
        body_fn, mesh=mesh, in_specs=(P("rows"), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P()), check_vma=False))
    return fn, rows, rep


def power_embedding(x, *, sigma: float, eps: float, max_iter: int,
                    stop_at: int, chips: int = 1,
                    precision: str = "highest"):
    """Run the reference power iteration on (n, m) points ``x``.

    Returns (degrees (n,), v after ``stop_at`` sweeps (n,), v after the
    sweep at which the reference's own stop rule fired (n,), that sweep),
    all as numpy. The loop runs until both its own stop and ``stop_at``
    are reached (or ``max_iter``), so the embedding of a run that stopped
    after ``stop_at`` sweeps is compared at the same number of sweeps.
    """
    import jax
    import jax.numpy as jnp
    x = np.asarray(x, np.float32)
    n, m = x.shape
    fn, rows, rep = _program(n, m, chips, precision, int(max_iter))
    put = jax.device_put
    d, snap, v, n_own = fn(put(x, rows), put(x, rep),
                        put(jnp.float32(1.0 / (2.0 * sigma * sigma)), rep),
                        put(jnp.float32(eps), rep),
                        put(jnp.int32(stop_at), rep))
    return np.asarray(d), np.asarray(snap), np.asarray(v), int(n_own)


def kmeans(z, k: int, *, restarts: int = 10, iters: int = 100,
           seed: int = 0):
    """Lloyd's k-means with k-means++ seeding on (n,) or (n, c) ``z``, in
    float64; the labels of the restart with the least inertia."""
    z = np.asarray(z, np.float64).reshape(len(z), -1)
    rng = np.random.default_rng(seed)
    best, best_inertia = None, np.inf
    for _ in range(restarts):
        cents = z[[rng.integers(len(z))]]
        for _ in range(1, k):
            d2 = ((z[:, None, :] - cents[None]) ** 2).sum(-1).min(1)
            cents = np.vstack([cents, z[rng.choice(len(z), p=d2 / d2.sum())]])
        labels = None
        for _ in range(iters):
            new = ((z[:, None, :] - cents[None]) ** 2).sum(-1).argmin(1)
            if labels is not None and np.array_equal(new, labels):
                break
            labels = new
            cents = np.vstack([z[labels == c].mean(0) if np.any(labels == c)
                               else cents[c] for c in range(k)])
        inertia = ((z - cents[labels]) ** 2).sum()
        if inertia < best_inertia:
            best, best_inertia = labels, inertia
    return best


def standardize(v):
    v = np.asarray(v, np.float64)
    return (v - v.mean()) / max(v.std(), 1e-300)


def cluster(x, k: int, *, sigma: float, eps: float, max_iter: int,
            chips: int = 1, precision: str = "highest"):
    """The whole reference run with its own stop rule: (labels, v, n_iter)
    — what the control puts in the program's place."""
    _d, _snap, v, n_own = power_embedding(x, sigma=sigma, eps=eps,
                                   max_iter=max_iter, stop_at=0,
                                   chips=chips, precision=precision)
    return kmeans(standardize(v), k), v, n_own
