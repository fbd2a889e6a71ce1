"""Distributed GPIC on a multi-device mesh (the paper's multi-GPU future
work, realized with shard_map over the operator pipeline — DESIGN.md §9).

The mesh spans every device JAX finds: the chips of an accelerator host,
or — with ``JAX_PLATFORMS=cpu`` — 8 virtual CPU devices. All three
sharded paths run the SAME convergence engine as the single-device
entry points — only the PowerOperator binding changes.

    PYTHONPATH=src python examples/distributed_clustering.py
    PYTHONPATH=src JAX_PLATFORMS=cpu python examples/distributed_clustering.py
"""
import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # virtual host devices only where the run is pinned to the CPU; on an
    # accelerator host the mesh is made of its chips
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import configure_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    GPICConfig, adjusted_rand_index, pic_reference, run_gpic)
from repro.core.distributed import shard_points  # noqa: E402
from repro.data import dataset_by_name  # noqa: E402


def main():
    configure_compile_cache()
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    print(f"mesh: {mesh.shape} on {jax.devices()[0].device_kind}")

    # explicit path: row-striped Pallas A build, O(n r) collectives per step
    x, y, k = dataset_by_name("three_circles", 1600, seed=0)
    xs = shard_points(x, mesh, "data")
    cfg = GPICConfig(mesh=mesh, shard_axes="data", affinity_kind="rbf",
                     sigma=0.3, max_iter=300)
    res = run_gpic(xs, k, cfg, key=jax.random.key(1))
    ari = adjusted_rand_index(y, np.asarray(res.labels))
    ref = pic_reference(jnp.asarray(x), k, key=jax.random.key(1),
                        affinity_kind="rbf", sigma=0.3, max_iter=300)
    err = float(jnp.max(jnp.abs(ref.embedding - res.embedding)))
    print(f"explicit-A : ARI={ari:.3f} iters={int(res.n_iter)} "
          f"| single-device parity err={err:.2e}")

    # streaming ring: A-free AND gather-free — O(n·m/P) per device, every
    # affinity kind. The production configuration.
    res_s = run_gpic(xs, k, cfg.with_(engine="streaming"),
                     key=jax.random.key(1))
    sd = run_gpic(jnp.asarray(x), k, cfg.with_(mesh=None, engine="streaming"),
                  key=jax.random.key(1))
    same = bool((np.asarray(res_s.labels) == np.asarray(sd.labels)).all())
    print(f"streaming  : iters={int(res_s.n_iter)} "
          f"| labels identical to single-device engine: {same}")

    # orthogonal embedding on the mesh: the QR's Gram partials psum through
    # the operator binding, so the sharded block clusters identically to
    # the single-device engine (DESIGN.md §10)
    cfg_o = cfg.with_(n_vectors=2, embedding="orthogonal", max_iter=400)
    res_o = run_gpic(xs, k, cfg_o, key=jax.random.key(1))
    sd_o = run_gpic(jnp.asarray(x), k, cfg_o.with_(mesh=None),
                    key=jax.random.key(1))
    same_o = bool((np.asarray(res_o.labels) == np.asarray(sd_o.labels)).all())
    ari_o = adjusted_rand_index(y, np.asarray(res_o.labels))
    print(f"orthogonal : ARI={ari_o:.3f} (2-col block separates the rings "
          f"the 1-D embedding collapses) | labels identical to "
          f"single-device: {same_o}")

    # matrix-free path: O(m) collectives per step — the 1000-node layout
    x, y, k = dataset_by_name("gaussians", 80_000, seed=0)
    xs = shard_points(x, mesh, "data")
    cfg = GPICConfig(engine="matrix_free", mesh=mesh, shard_axes="data",
                     affinity_kind="cosine_shifted", max_iter=50)
    res = run_gpic(xs, 3, cfg, key=jax.random.key(1))
    print(f"matrix-free: n=80k iters={int(res.n_iter)} "
          f"labels on host: {np.bincount(np.asarray(res.labels))}")


if __name__ == "__main__":
    main()
