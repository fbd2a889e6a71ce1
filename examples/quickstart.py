"""Quickstart: cluster the paper's synthetic datasets with GPIC.

One config object, one entry point — ``run_gpic(x, k, GPICConfig(...))``
routes to the right operator-backed engine (see DESIGN.md §9).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import (
    AffinitySpec,
    GPICConfig,
    adjusted_rand_index,
    jaccard_index,
    run_gpic,
)
from repro.data import dataset_by_name


def main():
    configure_compile_cache()
    print("GPIC quickstart — explicit-A (paper-faithful) pipeline")
    for name, sigma, nv in (("three_circles", 0.3, 1), ("cassini", 0.3, 2),
                            ("gaussians", 0.3, 1), ("smiley", 0.15, 1)):
        x, y, k = dataset_by_name(name, 1200, seed=0)
        cfg = GPICConfig(affinity_kind="rbf", sigma=sigma, max_iter=400,
                         n_vectors=nv)
        res = run_gpic(jnp.asarray(x), k, cfg, key=jax.random.key(1))
        ari = adjusted_rand_index(y, np.asarray(res.labels))
        jac = jaccard_index(y, np.asarray(res.labels))
        print(f"  {name:15s} k={k}  iters={int(res.n_iter):3d} "
              f"ARI={ari:.3f} Jaccard={jac:.3f}")

    print("\nembedding modes on nested structure (three_circles, "
          "DESIGN.md §10):")
    x, y, k = dataset_by_name("three_circles", 1200, seed=0)
    for emb, nv in (("pic", 1), ("orthogonal", 2), ("ensemble", 1)):
        cfg = GPICConfig(affinity_kind="rbf", sigma=0.3, max_iter=400,
                         n_vectors=nv, embedding=emb)
        res = run_gpic(jnp.asarray(x), k, cfg, key=jax.random.key(1))
        ari = adjusted_rand_index(y, np.asarray(res.labels))
        print(f"  embedding={res.embedding_mode:10s} r={nv} "
              f"embeddings{tuple(res.embeddings.shape)} ARI={ari:.3f}"
              + ("   <- separates all three rings" if emb == "orthogonal"
                 else ""))

    print("\nstreaming (A-free) engine on the same data — identical labels,"
          " no (n, n) allocation:")
    x, y, k = dataset_by_name("three_circles", 1200, seed=0)
    cfg = GPICConfig(affinity_kind="rbf", sigma=0.3, max_iter=400)
    res_e = run_gpic(jnp.asarray(x), k, cfg, key=jax.random.key(1))
    res_s = run_gpic(jnp.asarray(x), k, cfg.with_(engine="streaming"),
                     key=jax.random.key(1))
    same = bool((np.asarray(res_e.labels) == np.asarray(res_s.labels)).all())
    print(f"  three_circles explicit vs streaming: labels identical={same}")

    print("\naffinity-graph specs (DESIGN.md §11) — two_moons at sigma "
          "0.25, the dataset every dense mode leaves marginal (~0.5):")
    x, y, k = dataset_by_name("two_moons", 1200, seed=0)
    for tag, spec, rt in (
            ("dense rbf", AffinitySpec(kind="rbf", sigma=0.25), None),
            # knn_k ~ n/16 tracks the arc density (30 at n=480, 75 here);
            # residual_tol stops the block at subspace convergence instead
            # of max_iter
            ("kNN-truncated (k=n/16)",
             AffinitySpec(kind="rbf", sigma=0.25, knn_k=75), 1e-3)):
        cfg = GPICConfig(affinity=spec, max_iter=400, n_vectors=2,
                         embedding="orthogonal", residual_tol=rt)
        res = run_gpic(jnp.asarray(x), k, cfg, key=jax.random.key(1))
        ari = adjusted_rand_index(y, np.asarray(res.labels))
        print(f"  {tag:24s} ARI={ari:.3f} "
              f"iters={np.asarray(res.n_iter_cols).tolist()}")

    print("\nadaptive local scaling — self-tuning bandwidths, NO sigma "
          "to choose (exp(-d^2/(s_i s_j)) from each point's scale_k-th "
          "neighbor):")
    spec = AffinitySpec(kind="rbf", bandwidth="adaptive", scale_k=25,
                        knn_k=75)
    for name in ("gaussians", "cassini"):
        x, y, k = dataset_by_name(name, 1200, seed=0)
        cfg = GPICConfig(affinity=spec, max_iter=400, n_vectors=2,
                         embedding="orthogonal")
        res = run_gpic(jnp.asarray(x), k, cfg, key=jax.random.key(1))
        ari = adjusted_rand_index(y, np.asarray(res.labels))
        print(f"  {name:15s} adaptive+kNN ARI={ari:.3f}")

    print("\nmatrix-free GPIC (beyond-paper O2) at n=100,000:")
    x, y, k = dataset_by_name("gaussians", 100_000, seed=0)
    cfg = GPICConfig(engine="matrix_free", affinity_kind="cosine_shifted",
                     max_iter=50)
    res = run_gpic(jnp.asarray(x), 3, cfg, key=jax.random.key(1))
    print(f"  n=100k clustered in {int(res.n_iter)} power iterations "
          f"(A would be 40 GB; matrix-free uses ~1.6 MB)")


if __name__ == "__main__":
    main()
