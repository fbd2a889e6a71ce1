"""The pluggable affinity-graph subsystem (DESIGN.md §11).

Covers: AffinitySpec validation, the strided bandwidth-heuristic fix, the
row-top-k kernel vs its oracle (both statistics, stripes, ties), the
two-pass masked build (adaptive local scaling + kNN truncation) against
the dense jnp reference for BOTH the explicit and streaming kernels, the
bitwise explicit==streaming discipline under the new specs, matrix-free
spec rejection, and the subspace residual stopping rule (sweep reduction +
bitwise-pinned column 0).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AffinitySpec,
    GPICConfig,
    adjusted_rand_index,
    affinity_matrix,
    as_affinity_spec,
    gpic_matrix_free,
    knn_thresholds,
    local_scales,
    pic_reference,
    rbf_bandwidth_heuristic,
    run_gpic,
)
from repro.core.affinity import SCALE_FLOOR, matmat_matrix_free, row_normalize_features
from repro.core.graph import affinity_stats, scales_from_topk
from repro.data import gaussians, shuffle_points, three_circles
from repro.kernels import ops, ref
from repro.kernels.row_topk import row_topk_merge


def _points(n, m=3, seed=0):
    return jax.random.normal(jax.random.key(seed), (n, m))


class TestAffinitySpec:
    def test_defaults_are_dense_fixed(self):
        spec = AffinitySpec()
        assert spec.dense_fixed and not spec.adaptive and not spec.truncated
        assert spec.factorable

    def test_coercion(self):
        assert as_affinity_spec("rbf", sigma=0.4) == AffinitySpec(
            kind="rbf", sigma=0.4)
        spec = AffinitySpec(kind="rbf", knn_k=5)
        assert as_affinity_spec(spec, kind="cosine") is spec
        assert as_affinity_spec(None, kind="cosine") == AffinitySpec(
            kind="cosine")
        with pytest.raises(TypeError, match="AffinitySpec"):
            as_affinity_spec(42)

    @pytest.mark.parametrize("bad,match", [
        (dict(kind="warp"), "kind"),
        (dict(sigma=0.0), "sigma"),
        (dict(sigma=-2.0), "sigma"),
        (dict(bandwidth="auto"), "bandwidth"),
        (dict(kind="cosine_shifted", bandwidth="adaptive"), "rbf"),
        (dict(kind="rbf", bandwidth="adaptive", scale_k=0), "scale_k"),
        (dict(knn_k=0), "knn_k"),
    ])
    def test_constructor_rejections(self, bad, match):
        with pytest.raises(ValueError, match=match):
            AffinitySpec(**bad)

    def test_neighbor_rank_bounds_need_n(self):
        AffinitySpec(kind="rbf", knn_k=63).validate_for_n(64)
        with pytest.raises(ValueError, match="knn_k"):
            AffinitySpec(kind="rbf", knn_k=64).validate_for_n(64)
        with pytest.raises(ValueError, match="scale_k"):
            AffinitySpec(kind="rbf", bandwidth="adaptive",
                         scale_k=80).validate_for_n(64)

    def test_factorable_flags(self):
        assert not AffinitySpec(kind="rbf").factorable
        assert not AffinitySpec(knn_k=3).factorable
        assert AffinitySpec(kind="cosine").factorable


class TestFrontDoorValidation:
    """GPICConfig-level rejections (the PR 3 validation style)."""

    def _run(self, **cfg):
        x = jnp.asarray(_points(64, 2))
        return run_gpic(x, 2, GPICConfig(**cfg), key=jax.random.key(0))

    def test_matrix_free_rejects_truncation(self):
        with pytest.raises(ValueError, match="factorable"):
            self._run(engine="matrix_free", affinity=AffinitySpec(knn_k=5))

    def test_matrix_free_rejects_adaptive(self):
        with pytest.raises(ValueError, match="factorable"):
            self._run(engine="matrix_free", affinity=AffinitySpec(
                kind="rbf", bandwidth="adaptive"))

    def test_knn_k_bounds_at_n(self):
        with pytest.raises(ValueError, match=r"outside \[1, n\)"):
            self._run(affinity=AffinitySpec(kind="rbf", knn_k=64))

    def test_spec_and_legacy_shorthand_conflict(self):
        with pytest.raises(ValueError, match="not both"):
            self._run(affinity=AffinitySpec(kind="rbf", sigma=0.3),
                      affinity_kind="rbf", sigma=0.3)

    def test_fold_shift_rejects_truncation(self):
        # mesh-independent rejection: fold_shift needs a dense fixed spec
        with pytest.raises(ValueError, match="fold_shift"):
            self._run(affinity=AffinitySpec(knn_k=5), fold_shift=True)

    def test_residual_tol_needs_orthogonal(self):
        with pytest.raises(ValueError, match="residual_tol"):
            self._run(residual_tol=1e-3)
        with pytest.raises(ValueError, match="residual_tol"):
            self._run(embedding="orthogonal", n_vectors=2, residual_tol=-1.0)

    def test_residual_tol_needs_a_block(self):
        """r=1 orthogonal IS the classic loop — the rule could never arm,
        so silently ignoring it would fake early stopping. Rejected at the
        front door AND the engine."""
        from repro.core import batched_power_iteration
        with pytest.raises(ValueError, match="n_vectors"):
            self._run(embedding="orthogonal", n_vectors=1, residual_tol=1e-3)
        with pytest.raises(ValueError, match="never arm"):
            batched_power_iteration(lambda v: v, jnp.ones((8, 1)), 1e-5, 5,
                                    mode="orthogonal", residual_tol=1e-3)

    def test_direct_matrix_free_rejects_spec(self):
        x = jnp.asarray(_points(64, 2))
        with pytest.raises(ValueError, match="factorable"):
            gpic_matrix_free(x, 2, key=jax.random.key(0),
                             affinity=AffinitySpec(knn_k=5))
        with pytest.raises(ValueError, match="factorable"):
            matmat_matrix_free(row_normalize_features(x), jnp.ones((64, 1)),
                               AffinitySpec(kind="rbf"))


class TestBandwidthHeuristicSampling:
    def test_strided_sample_sees_every_cluster(self):
        """Regression (sampling bias): on cluster-SORTED data the first 512
        rows may all lie in one cluster, collapsing the median to the
        intra-cluster distance. The generators emit points class-by-class,
        so gaussians(2048) IS cluster-sorted: with 4 blobs of 512 the old
        leading slice saw exactly one blob. The strided sample must
        recover a bandwidth near the all-pairs median (inter-cluster
        scale), several times the intra-cluster one."""
        x, y = gaussians(2048, k=4, seed=0)
        assert (np.sort(y) == y).all()          # cluster-sorted, by design
        xj = jnp.asarray(x)
        sig = float(rbf_bandwidth_heuristic(xj))
        # ground truth from an unbiased random sample
        rng = np.random.default_rng(0)
        s = x[rng.choice(2048, 512, replace=False)]
        d = np.sqrt(np.maximum(
            np.sum(s * s, 1)[:, None] + np.sum(s * s, 1)[None, :]
            - 2 * s @ s.T, 0) + np.eye(512) * 1e9)
        sig_true = float(np.median(d))
        # the old leading-slice estimate: one blob's internal spread
        lead = x[:512]
        d0 = np.sqrt(np.maximum(
            np.sum(lead * lead, 1)[:, None] + np.sum(lead * lead, 1)[None, :]
            - 2 * lead @ lead.T, 0) + np.eye(512) * 1e9)
        sig_lead = float(np.median(d0))
        assert sig_lead < 0.25 * sig_true       # the bias being fixed
        assert abs(sig - sig_true) < 0.25 * sig_true

    @pytest.mark.parametrize("n", [1000, 1500])
    def test_ceil_stride_covers_tail_sizes(self, n):
        """Regression (stride rounding): floor division degenerates to the
        leading slice for sample < n < 2*sample (n=1000 → stride 1) and
        drops the tail class when n/sample is non-integral (n=1500 →
        floor-stride 2 never samples rows past 1022). The ceil stride
        must keep the estimate near the unbiased median at these sizes."""
        x, y = gaussians(n, k=4, seed=0)
        sig = float(rbf_bandwidth_heuristic(jnp.asarray(x)))
        rng = np.random.default_rng(0)
        s = x[rng.choice(n, 512, replace=False)]
        d = np.sqrt(np.maximum(
            np.sum(s * s, 1)[:, None] + np.sum(s * s, 1)[None, :]
            - 2 * s @ s.T, 0) + np.eye(512) * 1e9)
        sig_true = float(np.median(d))
        assert abs(sig - sig_true) < 0.25 * sig_true

    def test_order_robust(self):
        """The strided estimate on cluster-sorted input must agree with
        the estimate on the SAME data shuffled — the property the old
        leading slice violated by construction."""
        x, y = gaussians(2048, k=4, seed=1)
        xs, _ = shuffle_points(x, y, seed=3)
        a = float(rbf_bandwidth_heuristic(jnp.asarray(x)))
        b = float(rbf_bandwidth_heuristic(jnp.asarray(xs)))
        assert abs(a - b) < 0.2 * max(a, b)

    def test_small_n_unchanged(self):
        """n <= sample keeps the full-population median (stride 1)."""
        x = jnp.asarray(_points(100, 2))
        assert float(rbf_bandwidth_heuristic(x)) > 0


class TestRowTopkKernel:
    @pytest.mark.parametrize("n,m", [(64, 2), (129, 3), (300, 5), (517, 2)])
    @pytest.mark.parametrize("stat,kind", [("neg_sqdist", "rbf"),
                                           ("similarity", "rbf"),
                                           ("similarity", "cosine_shifted"),
                                           ("similarity", "cosine")])
    def test_shape_sweep(self, n, m, stat, kind):
        x = _points(n, m, seed=n + m)
        inp = x if kind == "rbf" else row_normalize_features(x)
        tk = ops.row_topk(inp, k=7, stat=stat, kind=kind, sigma=0.8)
        tr = ref.row_topk_ref(inp, k=7, stat=stat, kind=kind, sigma=0.8)
        assert tk.shape == (n, 7)
        np.testing.assert_allclose(tk, tr, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("k", [1, 2, 16, 63])
    def test_k_sweep_descending(self, k):
        x = _points(200, 3, seed=k)
        tk = np.asarray(ops.row_topk(x, k=k, stat="neg_sqdist", kind="rbf"))
        assert (np.diff(tk, axis=1) <= 0).all()  # descending rows
        np.testing.assert_allclose(
            tk, ref.row_topk_ref(x, k=k, stat="neg_sqdist", kind="rbf"),
            atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("tm,tn", [(128, 128), (128, 256), (256, 128)])
    def test_tile_sweep(self, tm, tn):
        x = _points(300, 4, seed=1)
        np.testing.assert_allclose(
            ops.row_topk(x, k=5, stat="neg_sqdist", kind="rbf", tm=tm, tn=tn),
            ref.row_topk_ref(x, k=5, stat="neg_sqdist", kind="rbf"),
            atol=1e-5, rtol=1e-5)

    def test_stripe_offsets_mask_global_diagonal(self):
        """The ring contract: per-stage stripes with offsets, merged, equal
        the square self-pass — and k > block width pads with -inf."""
        x = _points(256, 3, seed=2)
        k = 40
        full = np.asarray(ops.row_topk(x, k=k, stat="neg_sqdist", kind="rbf"))
        rows = x[:64]
        buf = jnp.full((64, k), -jnp.inf)
        for s in range(4):
            part = ops.row_topk(rows, x[s * 64:(s + 1) * 64], k=k,
                                stat="neg_sqdist", kind="rbf",
                                row_offset=0, col_offset=s * 64)
            buf = row_topk_merge(buf, part, k)
        np.testing.assert_allclose(np.asarray(buf), full[:64],
                                   atol=1e-5, rtol=1e-5)

    def test_ties_consumed_once(self):
        """Duplicate points create exactly-tied scores; each occurrence
        must be counted once (index tie-break, not suppress-all)."""
        base = np.asarray(_points(8, 2, seed=3))
        x = jnp.asarray(np.concatenate([base, base, base], axis=0))  # 24 pts
        tk = np.asarray(ops.row_topk(x, k=3, stat="neg_sqdist", kind="rbf"))
        # every point has exactly 2 duplicates: top-2 neg-sq-dists are 0,
        # the 3rd is strictly negative
        np.testing.assert_allclose(tk[:, :2], 0.0, atol=1e-6)
        assert (tk[:, 2] < -1e-6).all()

    def test_adaptive_scaled_similarity(self):
        x = _points(150, 3, seed=4)
        scl = local_scales(x, 7)
        tk = ops.row_topk(x, k=9, stat="similarity", kind="rbf",
                          scale_r=scl, scale_c=scl)
        tr = ref.row_topk_ref(x, k=9, stat="similarity", kind="rbf",
                              scale_r=scl, scale_c=scl)
        np.testing.assert_allclose(tk, tr, atol=1e-5, rtol=1e-5)

    def test_registry_modes(self):
        assert set(ops.modes_for("row_topk")) == {"pallas", "reference"}


class TestTwoPassMaskedBuild:
    """Pass 1 (row_topk) + pass 2 (masked affinity kernels) against the
    dense jnp reference (affinity_matrix(spec=...))."""

    SPECS = [
        AffinitySpec(kind="rbf", sigma=0.5, knn_k=10),
        AffinitySpec(kind="rbf", bandwidth="adaptive", scale_k=7),
        AffinitySpec(kind="rbf", bandwidth="adaptive", scale_k=5, knn_k=12),
        AffinitySpec(kind="cosine_shifted", knn_k=15),
        AffinitySpec(kind="cosine", knn_k=8),
    ]

    @pytest.mark.parametrize("spec", SPECS, ids=str)
    @pytest.mark.parametrize("n", [128, 300])
    def test_explicit_build_matches_dense_reference(self, spec, n):
        x = _points(n, 3, seed=n)
        inp = x if spec.kind == "rbf" else row_normalize_features(x)
        scale, thr = affinity_stats(inp, spec)
        a_k, d_k = ops.affinity_and_degree(inp, spec=spec, scale_r=scale,
                                           scale_c=scale, thr=thr)
        a_ref = affinity_matrix(inp, spec=spec)
        np.testing.assert_allclose(a_k[:n, :n], a_ref, atol=1e-5)
        np.testing.assert_allclose(d_k, jnp.sum(a_ref, axis=1),
                                   atol=1e-3, rtol=1e-5)

    @pytest.mark.parametrize("spec", SPECS, ids=str)
    def test_streaming_bitwise_equals_explicit(self, spec):
        """The §5 discipline extends to every spec: streamed degrees and
        sweeps equal the explicit masked build bitwise at matching tiles."""
        x = _points(300, 4, seed=9)
        inp = x if spec.kind == "rbf" else row_normalize_features(x)
        scale, thr = affinity_stats(inp, spec, tile=128)
        kw = dict(spec=spec, scale_r=scale, scale_c=scale, thr=thr,
                  tm=128, tn=128)
        a_k, d_e = ops.affinity_and_degree(inp, **kw)
        d_s = ops.streaming_degree(inp, **kw)
        np.testing.assert_array_equal(d_s, d_e)
        v = jax.random.uniform(jax.random.key(1), (300, 3))
        u_s = ops.streaming_matmat(inp, v, d_e, **kw)
        u_e = ops.degree_normalized_matmat(a_k, v, d_e, tm=128, tn=128)
        np.testing.assert_allclose(u_s, u_e, atol=1e-6)

    def test_truncated_rows_keep_knn_k_entries(self):
        """Each row keeps >= knn_k entries (ties may keep more), every
        kept entry >= the row's threshold, and the diagonal stays zero."""
        x = _points(200, 2, seed=5)
        spec = AffinitySpec(kind="rbf", sigma=0.5, knn_k=10)
        a = np.asarray(affinity_matrix(x, spec=spec))
        nnz = (a > 0).sum(axis=1)
        assert (nnz >= 10).all()
        assert (nnz <= 12).all()                 # no wholesale densification
        np.testing.assert_allclose(np.diag(a), 0.0, atol=0.0)

    def test_dense_spec_is_bitwise_the_legacy_build(self):
        """The bitwise-pinned baseline: the dense fixed spec and the legacy
        kind/sigma route compile to identical results."""
        x = _points(300, 3, seed=6)
        a0, d0 = ops.affinity_and_degree(x, kind="rbf", sigma=0.5)
        a1, d1 = ops.affinity_and_degree(
            x, spec=AffinitySpec(kind="rbf", sigma=0.5))
        np.testing.assert_array_equal(a0, a1)
        np.testing.assert_array_equal(d0, d1)

    def test_local_scales_floor_on_duplicates(self):
        base = np.asarray(_points(16, 2, seed=7))
        x = jnp.asarray(np.concatenate([base] * 8, axis=0))   # 8 copies
        scl = np.asarray(local_scales(x, 3))   # 3rd NN of any point: itself
        np.testing.assert_allclose(scl, SCALE_FLOOR, atol=0.0)

    def test_scales_from_topk_matches_dense_oracle(self):
        x = _points(200, 3, seed=8)
        nk = ops.row_topk(x, k=7, stat="neg_sqdist", kind="rbf")
        np.testing.assert_allclose(scales_from_topk(nk), local_scales(x, 7),
                                   atol=1e-5, rtol=1e-5)

    def test_knn_thresholds_oracle(self):
        x = _points(150, 2, seed=10)
        a = affinity_matrix(x, "rbf", sigma=0.5)
        thr = np.asarray(knn_thresholds(a, 5))
        a_np = np.where(np.eye(150, dtype=bool), -np.inf, np.asarray(a))
        expect = np.sort(a_np, axis=1)[:, -5]
        np.testing.assert_allclose(thr, expect, atol=1e-6)


class TestSpecPipeline:
    """End-to-end run_gpic under the new specs (single device)."""

    def test_engines_agree_on_knn_spec(self):
        x, _ = three_circles(400, seed=0)
        cfg = GPICConfig(affinity=AffinitySpec(kind="rbf", sigma=0.3,
                                               knn_k=30),
                         max_iter=300)
        r_e = run_gpic(jnp.asarray(x), 3, cfg, key=jax.random.key(1))
        r_s = run_gpic(jnp.asarray(x), 3, cfg.with_(engine="streaming"),
                       key=jax.random.key(1))
        np.testing.assert_array_equal(np.asarray(r_e.labels),
                                      np.asarray(r_s.labels))
        np.testing.assert_array_equal(np.asarray(r_e.embedding),
                                      np.asarray(r_s.embedding))

    def test_pic_reference_oracle_matches_gpic_on_spec(self):
        """The dense jnp oracle and the two-pass Pallas build agree on the
        full pipeline (labels + iteration count) for an adaptive+kNN spec."""
        x, _ = gaussians(256, seed=1)
        spec = AffinitySpec(kind="rbf", bandwidth="adaptive", scale_k=7,
                            knn_k=12)
        ref_res = pic_reference(jnp.asarray(x), 4, key=jax.random.key(2),
                                affinity=spec, max_iter=200)
        acc = run_gpic(jnp.asarray(x), 4, GPICConfig(affinity=spec,
                                                     max_iter=200),
                       key=jax.random.key(2))
        assert int(ref_res.n_iter) == int(acc.n_iter)
        np.testing.assert_allclose(ref_res.embedding, acc.embedding,
                                   atol=1e-6, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(ref_res.labels),
                                      np.asarray(acc.labels))


class TestSubspaceResidualStopping:
    """The ROADMAP open item: orthogonal-mode block columns stop on the
    ||WV − VΛ|| residual instead of running to max_iter."""

    def _cfg(self, **kw):
        return GPICConfig(affinity_kind="rbf", sigma=0.3, max_iter=400,
                          n_vectors=2, embedding="orthogonal", **kw)

    def test_sweep_count_reduction_and_pinned_column0(self):
        x, y = three_circles(480, seed=0)
        xj = jnp.asarray(x)
        full = run_gpic(xj, 3, self._cfg(), key=jax.random.key(1))
        res = run_gpic(xj, 3, self._cfg(residual_tol=1e-3),
                       key=jax.random.key(1))
        # the block column ran to max_iter without the rule; with it the
        # loop stops at subspace convergence
        assert int(full.n_iter_cols[1]) == 400
        assert int(res.n_iter_cols[1]) < 200
        assert bool(res.converged_cols.all())
        # column 0 (the paper's trajectory) is untouched: same count AND
        # bitwise-identical embedding
        assert int(res.n_iter_cols[0]) == int(full.n_iter_cols[0])
        np.testing.assert_array_equal(np.asarray(res.embedding),
                                      np.asarray(full.embedding))

    def test_quality_preserved(self):
        x, y = three_circles(480, seed=0)
        res = run_gpic(jnp.asarray(x), 3, self._cfg(residual_tol=1e-3),
                       key=jax.random.key(1))
        assert adjusted_rand_index(y, np.asarray(res.labels)) >= 0.9

    def test_default_off_is_bitwise_pr3(self):
        """residual_tol=None compiles the exact prior loop: same per-column
        counts and bitwise state as a run that never heard of the rule."""
        x, _ = gaussians(256, seed=0)
        cfg = GPICConfig(affinity_kind="rbf", sigma=0.3, max_iter=100,
                         n_vectors=2, embedding="orthogonal")
        a = run_gpic(jnp.asarray(x), 3, cfg, key=jax.random.key(1))
        b = run_gpic(jnp.asarray(x), 3, cfg.with_(residual_tol=None),
                     key=jax.random.key(1))
        np.testing.assert_array_equal(np.asarray(a.embeddings),
                                      np.asarray(b.embeddings))
        np.testing.assert_array_equal(np.asarray(a.n_iter_cols),
                                      np.asarray(b.n_iter_cols))
