"""Tests for the multi-vector power engine (ISSUE 1).

Covers: the batched degree-normalized mat-mat kernel vs vmapped matvec, the
streaming (A-free) kernel vs the explicit-A path for all affinity kinds and
non-divisible n, the lcm tile-padding regression, the interpret-probe env
override, the tile autotuner, bf16 A storage, and the engine-level
guarantees (frozen-column parity, streaming == explicit clustering).
"""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import gpic, gpic_matrix_free, matmat_matrix_free, pic_from_affinity
from repro.core.affinity import affinity_matrix, row_normalize_features
from repro.core.power import batched_power_iteration, init_power_vectors
from repro.kernels import ops, ref
from repro.kernels.tuning import choose_tiles, round_up_to_lcm

KINDS = ["cosine", "cosine_shifted", "rbf"]


def _problem(n, m, seed, kind):
    x = jax.random.normal(jax.random.key(seed), (n, m))
    return x if kind == "rbf" else row_normalize_features(x)


class TestDegreeNormalizedMatmat:
    @pytest.mark.parametrize("n", [64, 129, 300, 517])
    @pytest.mark.parametrize("r", [1, 2, 3, 4])
    def test_matches_vmapped_matvec(self, n, r):
        inp = _problem(n, 3, n + r, "cosine_shifted")
        a, d = ref.affinity_and_degree_ref(inp, kind="cosine_shifted")
        v = jax.random.uniform(jax.random.key(r), (n, r))
        batched = ops.degree_normalized_matmat(a, v, d)
        vmapped = jax.vmap(
            lambda col: ops.degree_normalized_matvec(a, col, d),
            in_axes=1, out_axes=1,
        )(v)
        np.testing.assert_allclose(batched, vmapped, atol=1e-5, rtol=1e-5)

    def test_r1_equals_matvec_exactly(self):
        inp = _problem(200, 2, 0, "cosine_shifted")
        a, d = ref.affinity_and_degree_ref(inp, kind="cosine_shifted")
        v = jax.random.uniform(jax.random.key(1), (200,))
        np.testing.assert_array_equal(
            ops.degree_normalized_matmat(a, v[:, None], d)[:, 0],
            ops.degree_normalized_matvec(a, v, d),
        )

    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(16, 384), r=st.integers(1, 4))
    def test_matches_reference_property(self, n, r):
        inp = _problem(n, 2, n * 7 + r, "cosine_shifted")
        a, d = ref.affinity_and_degree_ref(inp, kind="cosine_shifted")
        v = jax.random.uniform(jax.random.key(n + r), (n, r))
        np.testing.assert_allclose(
            ops.degree_normalized_matmat(a, v, d),
            ref.degree_normalized_matmat_ref(a, v, d),
            atol=1e-5, rtol=1e-5,
        )

    def test_bf16_storage_f32_accumulation(self):
        inp = _problem(300, 4, 2, "cosine_shifted")
        a, d = ops.affinity_and_degree(inp, kind="cosine_shifted",
                                       out_dtype=jnp.bfloat16)
        assert a.dtype == jnp.bfloat16
        v = jax.random.uniform(jax.random.key(3), (300, 2))
        u16 = ops.degree_normalized_matmat(a, v, d)
        assert u16.dtype == jnp.float32
        a32, d32 = ops.affinity_and_degree(inp, kind="cosine_shifted")
        u32 = ops.degree_normalized_matmat(a32, v, d32)
        np.testing.assert_allclose(u16, u32, atol=2e-2, rtol=2e-2)


class TestStreamingKernel:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("n", [129, 300])
    @pytest.mark.parametrize("r", [1, 2, 3, 4])
    def test_matches_explicit_path(self, kind, n, r):
        """The A-free kernel must reproduce build-A-then-multiply."""
        inp = _problem(n, 3, n, kind)
        a, d = ref.affinity_and_degree_ref(inp, kind=kind, sigma=0.8)
        v = jax.random.uniform(jax.random.key(n + r), (n, r))
        streamed = ops.streaming_matmat(inp, v, d, kind=kind, sigma=0.8)
        explicit = ref.degree_normalized_matmat_ref(a, v, d)
        # raw-cosine degrees can be ~0, so (A V)/d amplifies magnitudes
        # enormously; relative tolerance is the meaningful check there
        np.testing.assert_allclose(streamed, explicit, atol=1e-4, rtol=2e-3)

    @pytest.mark.parametrize("kind", KINDS)
    def test_degree_matches_fused_affinity_kernel(self, kind):
        """Streamed degrees equal the affinity kernel's fused RowSum (the
        reduction orders are matched for bitwise engine parity)."""
        inp = _problem(300, 5, 9, kind)
        _, d_explicit = ops.affinity_and_degree(inp, kind=kind, sigma=0.8,
                                                tm=128, tn=128)
        d_streamed = ops.streaming_degree(inp, kind=kind, sigma=0.8,
                                          tm=128, tn=128)
        np.testing.assert_array_equal(d_streamed, d_explicit)

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(16, 300), r=st.integers(1, 4),
           kind=st.sampled_from(KINDS))
    def test_streaming_property(self, n, r, kind):
        inp = _problem(n, 2, n * 3 + r, kind)
        v = jax.random.uniform(jax.random.key(n), (n, r))
        np.testing.assert_allclose(
            ops.streaming_matmat(inp, v, None, kind=kind, sigma=1.1),
            ref.affinity_matmat_ref(inp, v, None, kind=kind, sigma=1.1),
            atol=1e-4, rtol=1e-4,
        )


class TestLcmPadding:
    """Regression: n_pad must round to lcm(tm, tn), not max(tm, tn) —
    max() breaks whenever tm/tn are not mutually divisible."""

    @pytest.mark.parametrize("tm,tn", [(256, 160), (256, 192), (128, 96)])
    def test_matmat_non_divisible_tiles(self, tm, tn):
        n = 300
        inp = _problem(n, 3, 1, "cosine_shifted")
        a, d = ref.affinity_and_degree_ref(inp, kind="cosine_shifted")
        v = jax.random.uniform(jax.random.key(2), (n, 2))
        np.testing.assert_allclose(
            ops.degree_normalized_matmat(a, v, d, tm=tm, tn=tn),
            ref.degree_normalized_matmat_ref(a, v, d),
            atol=1e-5, rtol=1e-5,
        )

    @pytest.mark.parametrize("tm,tn", [(256, 160), (128, 96)])
    def test_affinity_non_divisible_tiles(self, tm, tn):
        inp = _problem(300, 3, 4, "cosine_shifted")
        a_k, d_k = ops.affinity_and_degree(inp, kind="cosine_shifted",
                                           tm=tm, tn=tn)
        a_r, d_r = ref.affinity_and_degree_ref(inp, kind="cosine_shifted")
        np.testing.assert_allclose(a_k[:300, :300], a_r, atol=1e-5)
        np.testing.assert_allclose(d_k, d_r, atol=1e-3, rtol=1e-5)

    def test_round_up_to_lcm(self):
        assert round_up_to_lcm(300, 256, 256) == 512
        assert round_up_to_lcm(300, 256, 160) == 1280
        assert round_up_to_lcm(1280, 256, 160) == 1280


class TestInterpretProbe:
    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        assert ops._probe_interpret() is True
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "compiled")
        assert ops._probe_interpret() is False
        monkeypatch.delenv("REPRO_FORCE_INTERPRET")
        assert ops._probe_interpret() == (jax.default_backend() != "tpu")

    def test_probe_cached_at_import(self):
        # the module-level constant is what every op consults — no
        # per-call backend probing
        assert isinstance(ops._INTERPRET, bool)
        assert ops._interpret() is ops._INTERPRET


class TestTileAutotuner:
    def test_small_problem_gets_small_tiles(self):
        # 100 -> padding to 256 would be >60% phantom rows; 128 wastes 28
        tm, tn = choose_tiles(100)
        assert (tm, tn) == (128, 128)

    def test_large_problem_gets_large_tiles(self):
        tm, tn = choose_tiles(8192)
        assert tm >= 256 and tn >= 256

    def test_fits_and_divides(self):
        for n in (100, 300, 1024, 5000):
            tm, tn = choose_tiles(n, r=4, m=64)
            n_pad = round_up_to_lcm(n, tm, tn)
            assert n_pad % tm == 0 and n_pad % tn == 0

    def test_default_tiles_used_by_ops(self):
        # ops must accept tm=tn=None and autotune (no crash, right result)
        inp = _problem(150, 2, 5, "cosine_shifted")
        a, d = ops.affinity_and_degree(inp, kind="cosine_shifted",
                                       tm=None, tn=None)
        a_r, d_r = ref.affinity_and_degree_ref(inp, kind="cosine_shifted")
        np.testing.assert_allclose(a[:150, :150], a_r, atol=1e-5)


class TestDispatchRegistry:
    def test_modes_registered(self):
        assert set(ops.modes_for("degree_normalized_matmat")) == {
            "pallas", "reference"}
        assert set(ops.modes_for("streaming_matmat")) == {
            "streaming", "reference"}

    def test_unknown_mode_raises_with_choices(self):
        with pytest.raises(ValueError, match="available"):
            ops.dispatch("degree_normalized_matmat", "nope")


class TestEngine:
    def test_frozen_columns_reproduce_solo_loops_exactly(self):
        """The batched loop with per-column freezing must give every column
        the EXACT trajectory of a dedicated single-vector loop. Tested with
        a columnwise-identical matmat so the only variable is the loop
        logic itself (core/power.py owns exactly that)."""
        x = jax.random.normal(jax.random.key(0), (128, 2))
        a = affinity_matrix(x, "cosine_shifted")
        d = jnp.sum(a, axis=1)
        w = a / jnp.maximum(d, 1e-30)[:, None]

        def mm(vv):  # per-column products: r cannot change the float ops
            return jnp.stack([w @ vv[:, c] for c in range(vv.shape[1])],
                             axis=1)

        v0 = init_power_vectors(jax.random.key(1), d, 3)
        v_b, t_b, done_b = batched_power_iteration(mm, v0, 1e-5 / 128, 60)
        for c in range(3):
            v_s, t_s, done_s = batched_power_iteration(
                mm, v0[:, c:c + 1], 1e-5 / 128, 60)
            # values agree to XLA fusion noise (~2 ulp at 1/n magnitude);
            # the loop SEMANTICS — per-column counts and flags — are exact
            np.testing.assert_allclose(v_b[:, c], v_s[:, 0], atol=1e-8,
                                       rtol=0)
            assert int(t_b[c]) == int(t_s[0])
            assert bool(done_b[c]) == bool(done_s[0])

    def test_primary_column_independent_of_r(self):
        """Adding random extra vectors must not perturb the paper's primary
        (degree-start) trajectory beyond dot-reduction float noise."""
        x = jnp.asarray(jax.random.normal(jax.random.key(0), (256, 2)))
        r1 = gpic(x, 3, key=jax.random.key(1), max_iter=40)
        r4 = gpic(x, 3, key=jax.random.key(1), max_iter=40, n_vectors=4)
        np.testing.assert_allclose(r1.embedding, r4.embedding, atol=1e-6)

    @pytest.mark.parametrize("kind,sigma", [("cosine_shifted", 1.0),
                                            ("rbf", 0.4)])
    def test_streaming_engine_clusters_identically(self, kind, sigma):
        x = jnp.asarray(jax.random.normal(jax.random.key(2), (300, 2)))
        e = gpic(x, 3, key=jax.random.key(3), affinity_kind=kind, sigma=sigma,
                 max_iter=50, engine="explicit")
        s = gpic(x, 3, key=jax.random.key(3), affinity_kind=kind, sigma=sigma,
                 max_iter=50, engine="streaming")
        np.testing.assert_array_equal(np.asarray(e.labels),
                                      np.asarray(s.labels))
        np.testing.assert_array_equal(np.asarray(e.embedding),
                                      np.asarray(s.embedding))

    def test_unknown_engine_raises(self):
        x = jnp.ones((64, 2))
        with pytest.raises(ValueError, match="engine"):
            gpic(x, 2, key=jax.random.key(0), engine="warp")

    def test_matrix_free_multivector_batched(self):
        x = jnp.asarray(jax.random.normal(jax.random.key(4), (200, 3)))
        res = gpic_matrix_free(x, 3, key=jax.random.key(5), max_iter=30,
                               n_vectors=3)
        assert res.labels.shape == (200,)
        assert np.isfinite(np.asarray(res.embedding)).all()

    def test_pic_from_affinity_multivector(self):
        x = jax.random.normal(jax.random.key(6), (150, 2))
        a = affinity_matrix(x, "cosine_shifted")
        res = pic_from_affinity(a, 3, key=jax.random.key(7), max_iter=30,
                                n_vectors=3)
        assert res.labels.shape == (150,)

    def test_batched_iteration_counts_per_column(self):
        """Columns converge independently; t_cols tracks each one."""
        x = jax.random.normal(jax.random.key(8), (128, 2))
        a = affinity_matrix(x, "cosine_shifted")
        d = jnp.sum(a, axis=1)
        w = a / jnp.maximum(d, 1e-30)[:, None]
        v0 = init_power_vectors(jax.random.key(9), d, 3)
        v, t_cols, done = batched_power_iteration(
            lambda vv: w @ vv, v0, 1e-5 / 128, 100)
        assert v.shape == (128, 3)
        assert t_cols.shape == (3,) and done.shape == (3,)
        assert (np.asarray(t_cols) >= 1).all()

    def test_matmat_matrix_free_batched_matches_loop(self):
        xn = row_normalize_features(
            jax.random.normal(jax.random.key(10), (120, 4)))
        v = jax.random.uniform(jax.random.key(11), (120, 3))
        batched = matmat_matrix_free(xn, v, "cosine_shifted")
        for c in range(3):
            np.testing.assert_allclose(
                batched[:, c],
                matmat_matrix_free(xn, v[:, c], "cosine_shifted"),
                atol=1e-5, rtol=1e-5,
            )
