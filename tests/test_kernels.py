"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.affinity import row_normalize_features
from repro.kernels import ops, ref

SHAPES_N_M = [(64, 2), (100, 3), (256, 2), (300, 7), (517, 16), (1024, 2)]
TILES = [(128, 128), (256, 256), (128, 256)]


class TestAffinityKernel:
    @pytest.mark.parametrize("n,m", SHAPES_N_M)
    @pytest.mark.parametrize("kind", ["cosine", "cosine_shifted", "rbf"])
    def test_shape_sweep(self, n, m, kind):
        x = jax.random.normal(jax.random.key(n * m), (n, m))
        inp = x if kind == "rbf" else row_normalize_features(x)
        a_k, d_k = ops.affinity_and_degree(inp, kind=kind, sigma=0.8)
        a_r, d_r = ref.affinity_and_degree_ref(inp, kind=kind, sigma=0.8)
        # A comes at its tile-padded storage shape; pad entries are zeros
        assert a_k.shape[0] >= n and a_k.shape[1] >= n and d_k.shape == (n,)
        assert not np.asarray(a_k[n:]).any()
        assert not np.asarray(a_k[:, n:]).any()
        np.testing.assert_allclose(a_k[:n, :n], a_r, atol=1e-5)
        np.testing.assert_allclose(d_k, d_r, atol=1e-3, rtol=1e-5)

    @pytest.mark.parametrize("tm,tn", TILES)
    def test_tile_sweep(self, tm, tn):
        x = row_normalize_features(jax.random.normal(jax.random.key(0), (400, 4)))
        a_k, d_k = ops.affinity_and_degree(x, kind="cosine_shifted", tm=tm, tn=tn)
        a_r, d_r = ref.affinity_and_degree_ref(x, kind="cosine_shifted")
        np.testing.assert_allclose(a_k[:400, :400], a_r, atol=1e-5)
        np.testing.assert_allclose(d_k, d_r, atol=1e-3, rtol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        x = row_normalize_features(
            jax.random.normal(jax.random.key(1), (200, 5))
        ).astype(dtype)
        a_k, d_k = ops.affinity_and_degree(x, kind="cosine_shifted")
        a_r, d_r = ref.affinity_and_degree_ref(x, kind="cosine_shifted")
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(a_k[:200, :200], np.float32),
                                   a_r, atol=tol)
        np.testing.assert_allclose(d_k, d_r, atol=max(tol * 200, 1e-3), rtol=tol)

    def test_diagonal_is_zero(self):
        x = row_normalize_features(jax.random.normal(jax.random.key(2), (130, 3)))
        a_k, _ = ops.affinity_and_degree(x, kind="cosine_shifted")
        np.testing.assert_allclose(np.diag(np.asarray(a_k)), 0.0, atol=0.0)

    def test_padding_region_not_leaked(self):
        """n far from the tile boundary: degrees must ignore padded cols."""
        x = row_normalize_features(jax.random.normal(jax.random.key(3), (129, 2)))
        _, d_k = ops.affinity_and_degree(x, kind="cosine_shifted")
        _, d_r = ref.affinity_and_degree_ref(x, kind="cosine_shifted")
        np.testing.assert_allclose(d_k, d_r, atol=1e-3, rtol=1e-5)


class TestPowerStepKernel:
    @pytest.mark.parametrize("n", [64, 129, 300, 512, 1000])
    def test_shape_sweep(self, n):
        key = jax.random.key(n)
        x = row_normalize_features(jax.random.normal(key, (n, 3)))
        a, d = ref.affinity_and_degree_ref(x, kind="cosine_shifted")
        v = jax.random.uniform(jax.random.key(n + 1), (n,))
        np.testing.assert_allclose(
            ops.degree_normalized_matvec(a, v, d),
            ref.degree_normalized_matvec_ref(a, v, d),
            atol=1e-5, rtol=1e-5,
        )

    @pytest.mark.parametrize("tm,tn", TILES)
    def test_tile_sweep(self, tm, tn):
        n = 400
        x = row_normalize_features(jax.random.normal(jax.random.key(9), (n, 3)))
        a, d = ref.affinity_and_degree_ref(x, kind="cosine_shifted")
        v = jax.random.uniform(jax.random.key(10), (n,))
        np.testing.assert_allclose(
            ops.degree_normalized_matvec(a, v, d, tm=tm, tn=tn),
            ref.degree_normalized_matvec_ref(a, v, d),
            atol=1e-5, rtol=1e-5,
        )

    def test_full_power_step_l1(self):
        n = 300
        x = row_normalize_features(jax.random.normal(jax.random.key(4), (n, 2)))
        a, d = ref.affinity_and_degree_ref(x, kind="cosine_shifted")
        v = jnp.ones((n,)) / n
        out = ops.power_step(a, v, d)
        np.testing.assert_allclose(jnp.sum(jnp.abs(out)), 1.0, atol=1e-5)
        np.testing.assert_allclose(out, ref.power_step_ref(a, v, d), atol=1e-6)

    def test_iterated_steps_match_reference_pic(self):
        """Running the kernel t times equals the reference power iteration."""
        n = 200
        x = row_normalize_features(jax.random.normal(jax.random.key(5), (n, 2)))
        a, d = ref.affinity_and_degree_ref(x, kind="cosine_shifted")
        v_k = v_r = d / jnp.sum(d)
        for _ in range(5):
            v_k = ops.power_step(a, v_k, d)
            v_r = ref.power_step_ref(a, v_r, d)
        np.testing.assert_allclose(v_k, v_r, atol=1e-6)


class TestKmeansAssignKernel:
    @pytest.mark.parametrize("n,d,k", [(100, 2, 3), (513, 5, 7), (1024, 1, 2),
                                       (2000, 8, 16), (333, 3, 130)])
    def test_shape_sweep(self, n, d, k):
        x = jax.random.normal(jax.random.key(n + d + k), (n, d))
        c = jax.random.normal(jax.random.key(n + d + k + 1), (k, d))
        l_k, d_k = ops.kmeans_assign(x, c)
        l_r, d_r = ref.kmeans_assign_ref(x, c)
        np.testing.assert_array_equal(l_k, l_r)
        np.testing.assert_allclose(d_k, d_r, atol=1e-4, rtol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        x = jax.random.normal(jax.random.key(6), (400, 3)).astype(dtype)
        c = jax.random.normal(jax.random.key(7), (5, 3)).astype(dtype)
        l_k, _ = ops.kmeans_assign(x, c)
        l_r, _ = ref.kmeans_assign_ref(x, c)
        match = float(jnp.mean((l_k == l_r).astype(jnp.float32)))
        assert match > 0.99  # bf16 ties may flip; near-total agreement required


class TestGramKernel:
    @pytest.mark.parametrize("n,r", [(64, 1), (100, 2), (300, 4), (517, 8),
                                     (1024, 3), (200, 16)])
    def test_shape_sweep(self, n, r):
        v = jax.random.uniform(jax.random.key(n + r), (n, r)) - 0.3
        g_k = ops.gram(v)
        g_r = ref.gram_ref(v)
        assert g_k.shape == (r, r) and g_k.dtype == jnp.float32
        np.testing.assert_allclose(g_k, g_r, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("tm", [128, 256, 512])
    def test_tile_sweep(self, tm):
        v = jax.random.normal(jax.random.key(0), (700, 4))
        np.testing.assert_allclose(ops.gram(v, tm=tm), ref.gram_ref(v),
                                   atol=1e-4, rtol=1e-5)

    def test_symmetric_and_psd_diag(self):
        v = jax.random.normal(jax.random.key(1), (333, 5))
        g = np.asarray(ops.gram(v))
        np.testing.assert_allclose(g, g.T, atol=1e-5)
        assert (np.diag(g) >= 0).all()

    def test_f32_accumulation_from_bf16_state(self):
        v = jax.random.uniform(jax.random.key(2), (400, 3))
        g16 = ops.gram(v.astype(jnp.bfloat16))
        assert g16.dtype == jnp.float32
        np.testing.assert_allclose(g16, ref.gram_ref(v), atol=2e-2, rtol=2e-2)

    def test_chunked_partials_sum_to_full(self):
        """The sharded contract: per-chunk Grams summed across chunks equal
        the full Gram (what op.sum(op.gram(v_loc)) computes under psum)."""
        v = jax.random.normal(jax.random.key(3), (512, 4))
        chunks = [ops.gram(v[i * 64:(i + 1) * 64]) for i in range(8)]
        np.testing.assert_allclose(sum(chunks), ref.gram_ref(v),
                                   atol=1e-4, rtol=1e-5)

    def test_registry_modes(self):
        assert set(ops.modes_for("gram")) == {"pallas", "reference"}


class TestKernelProperties:
    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(16, 384),
        m=st.integers(1, 9),
        kind=st.sampled_from(["cosine", "cosine_shifted", "rbf"]),
    )
    def test_affinity_property(self, n, m, kind):
        x = jax.random.normal(jax.random.key(n * 31 + m), (n, m))
        inp = x if kind == "rbf" else row_normalize_features(x)
        a_k, d_k = ops.affinity_and_degree(inp, kind=kind, sigma=1.1)
        a_r, d_r = ref.affinity_and_degree_ref(inp, kind=kind, sigma=1.1)
        np.testing.assert_allclose(a_k[:n, :n], a_r, atol=1e-5)
        np.testing.assert_allclose(d_k, d_r, atol=1e-3, rtol=1e-4)

    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(16, 384))
    def test_power_step_preserves_l1(self, n):
        x = row_normalize_features(jax.random.normal(jax.random.key(n), (n, 2)))
        a, d = ref.affinity_and_degree_ref(x, kind="cosine_shifted")
        v = jax.random.uniform(jax.random.key(n + 1), (n,))
        out = ops.power_step(a, v / jnp.sum(v), d)
        np.testing.assert_allclose(float(jnp.sum(jnp.abs(out))), 1.0, atol=1e-4)
