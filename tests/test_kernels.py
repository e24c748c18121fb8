"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True on CPU — the exact program that lowers to TPU Mosaic)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape)
    return x.astype(dtype)


TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


@pytest.mark.quick
class TestSegAggr:
    @pytest.mark.parametrize("mode", ["mean", "sum", "max"])
    @pytest.mark.parametrize("shape", [(8, 4, 128), (37, 6, 130), (1, 1, 8), (64, 32, 256)])
    def test_matches_ref(self, mode, shape):
        x = rand(0, shape, jnp.float32)
        mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.6, shape[:2])
        got = ops.seg_aggr(x, mask, mode=mode)
        want = ref.seg_aggr_ref(x, mask, mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = rand(2, (16, 8, 64), dtype)
        mask = jax.random.bernoulli(jax.random.PRNGKey(3), 0.5, (16, 8))
        got = ops.seg_aggr(x, mask, mode="mean")
        want = ref.seg_aggr_ref(x, mask, "mean")
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=TOL[dtype],
        )

    def test_all_invalid_rows_zero(self):
        x = rand(4, (8, 4, 32), jnp.float32)
        mask = jnp.zeros((8, 4), bool)
        for mode in ("mean", "sum", "max"):
            got = ops.seg_aggr(x, mask, mode=mode)
            np.testing.assert_allclose(np.asarray(got), 0.0)


class TestInbatchLoss:
    @pytest.mark.parametrize("P,d", [(16, 8), (100, 48), (128, 64), (257, 32)])
    def test_matches_ref(self, P, d):
        hs = rand(5, (P, d), jnp.float32)
        hd = rand(6, (P, d), jnp.float32)
        got = ops.inbatch_loss(hs, hd, 1.0)
        want = ref.inbatch_loss_ref(hs, hd, 1.0)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    @pytest.mark.parametrize("temp", [0.5, 1.0, 4.0])
    def test_temperature(self, temp):
        hs = rand(7, (64, 16), jnp.float32)
        hd = rand(8, (64, 16), jnp.float32)
        got = ops.inbatch_loss(hs, hd, temp)
        want = ref.inbatch_loss_ref(hs, hd, temp)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_custom_vjp_matches_autodiff_of_ref(self):
        hs = rand(9, (32, 16), jnp.float32)
        hd = rand(10, (32, 16), jnp.float32)
        g_kernel = jax.grad(lambda a, b: ops.inbatch_loss(a, b, 1.0), (0, 1))(hs, hd)
        g_ref = jax.grad(lambda a, b: ref.inbatch_loss_ref(a, b, 1.0), (0, 1))(hs, hd)
        for gk, gr in zip(g_kernel, g_ref):
            np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-6)

    def test_inside_jit_and_grad(self):
        hs = rand(11, (64, 8), jnp.float32)

        @jax.jit
        def step(a, b):
            return jax.value_and_grad(lambda x: ops.inbatch_loss(x, b, 1.0))(a)

        loss, g = step(hs, hs)
        assert np.isfinite(float(loss)) and np.isfinite(np.asarray(g)).all()


class TestFlashAttention:
    @pytest.mark.parametrize("S,H,K,hd", [(256, 4, 2, 64), (128, 8, 8, 32),
                                          (512, 4, 1, 128)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, S, H, K, hd, causal):
        q = rand(1, (2, S, H, hd), jnp.float32)
        k = rand(2, (2, S, K, hd), jnp.float32)
        v = rand(3, (2, S, K, hd), jnp.float32)
        got = ops.flash_attention(q, k, v, causal=causal)
        want = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    @pytest.mark.parametrize("window", [32, 64, 128])
    def test_sliding_window(self, window):
        S = 256
        q = rand(4, (1, S, 4, 64), jnp.float32)
        k = rand(5, (1, S, 2, 64), jnp.float32)
        v = rand(6, (1, S, 2, 64), jnp.float32)
        got = ops.flash_attention(q, k, v, causal=True, window=window)
        want = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_bf16(self):
        q = rand(7, (1, 128, 2, 64), jnp.bfloat16)
        k = rand(8, (1, 128, 2, 64), jnp.bfloat16)
        v = rand(9, (1, 128, 2, 64), jnp.bfloat16)
        got = ops.flash_attention(q, k, v, causal=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
        )

    def test_chunked_jnp_matches_ref(self):
        """The XLA chunked path (models/layers.py) against the same oracle."""
        from repro.models.layers import chunked_gqa_attention

        q = rand(10, (2, 256, 4, 32), jnp.float32)
        k = rand(11, (2, 256, 2, 32), jnp.float32)
        v = rand(12, (2, 256, 2, 32), jnp.float32)
        for window in (None, 64):
            got = chunked_gqa_attention(q, k, v, True, window, block_q=64)
            want = ref.attention_ref(q, k, v, causal=True, window=window)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        # unrolled variant (dry-run probes) identical
        got_u = chunked_gqa_attention(q, k, v, True, None, block_q=64, unroll=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got_u), np.asarray(want), atol=2e-5)


@pytest.mark.quick
class TestTopkOracle:
    """chunked_topk_pallas against its dense pure-jnp oracle (P003 pair)."""

    @pytest.mark.parametrize("Q,I,k", [(16, 100, 10), (130, 300, 25)])
    def test_matches_ref(self, Q, I, k):
        from repro.kernels.topk import chunked_topk_pallas

        q = rand(20, (Q, 32), jnp.float32)
        it = rand(21, (I, 32), jnp.float32)
        ex = jax.random.randint(jax.random.PRNGKey(22), (Q, 5), -1, I)
        s0, i0 = ref.chunked_topk_ref(q, it, k, exclude=ex)
        s1, i1 = chunked_topk_pallas(
            q, it, k, exclude=ex, item_chunk=64, tile_q=32, interpret=True
        )
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))

    def test_no_exclude(self):
        from repro.kernels.topk import chunked_topk_pallas

        q = rand(23, (8, 16), jnp.float32)
        it = rand(24, (50, 16), jnp.float32)
        s0, i0 = ref.chunked_topk_ref(q, it, 7)
        s1, i1 = chunked_topk_pallas(q, it, 7, item_chunk=16, interpret=True)
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


@pytest.mark.quick
class TestIVFListTopkOracle:
    """ivf_list_topk_pallas against its CSR gather-then-score oracle (P003
    pair): random ragged lists, exact-tie flats, and shortlist > candidate
    filler. interpret=True exercises the same DMA/merge program the TPU
    path compiles."""

    def _case(self, seed, Q, P, d, lpad, rows):
        from repro.kernels.ivf import dma_rows

        rng = np.random.default_rng(seed)
        pad = dma_rows(lpad)  # the builder's DMA padding contract
        codes = rng.integers(-127, 128, size=(rows + pad, d)).astype(np.int8)
        scales = rng.uniform(0.5, 2.0, size=(rows + pad, 1)).astype(np.float32)
        q = rng.normal(size=(Q, d)).astype(np.float32)
        starts = rng.integers(0, rows, size=(Q, P)).astype(np.int32)
        lens = rng.integers(0, lpad + 1, size=(Q, P)).astype(np.int32)
        # device arrays: the ref is the jitted production path, not a numpy fn
        return tuple(jax.device_put(a) for a in (q, codes, scales, starts, lens))

    @pytest.mark.parametrize("Q,P,lpad,shortlist", [(7, 3, 24, 16), (16, 5, 40, 64)])
    def test_matches_ref(self, Q, P, lpad, shortlist):
        from repro.kernels.ivf import ivf_list_topk_pallas

        q, codes, scales, starts, lens = self._case(40 + Q, Q, P, 16, lpad, 300)
        s0, r0 = ref.ivf_list_topk_ref(
            q, codes, scales, starts, lens, lpad=lpad, shortlist=shortlist
        )
        s1, r1 = ivf_list_topk_pallas(
            q, codes, scales, starts, lens,
            lpad=lpad, shortlist=shortlist, interpret=True,
        )
        # dots accumulate in different orders (DMA'd block vs gathered
        # rows): ulp-level score drift, identical candidate rows
        np.testing.assert_allclose(
            np.asarray(s0), np.asarray(s1), rtol=2e-5, atol=1e-4
        )
        np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))

    def test_tie_order_matches_flat_probe_order(self):
        # all-equal scores: both paths must keep the flat (probe, within-
        # list) order — the shared contract the exact re-rank builds on
        from repro.kernels.ivf import dma_rows, ivf_list_topk_pallas

        Q, P, d, lpad, rows = 4, 3, 8, 10, 60
        codes = jax.device_put(np.ones((rows + dma_rows(lpad), d), np.int8))
        scales = jax.device_put(np.ones((rows + dma_rows(lpad), 1), np.float32))
        q = jax.device_put(np.ones((Q, d), np.float32))
        rng = np.random.default_rng(9)
        starts = jax.device_put(rng.integers(0, rows, size=(Q, P)).astype(np.int32))
        lens = jax.device_put(rng.integers(1, lpad + 1, size=(Q, P)).astype(np.int32))
        s0, r0 = ref.ivf_list_topk_ref(
            q, codes, scales, starts, lens, lpad=lpad, shortlist=12
        )
        s1, r1 = ivf_list_topk_pallas(
            q, codes, scales, starts, lens,
            lpad=lpad, shortlist=12, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))

    def test_filler_when_shortlist_exceeds_candidates(self):
        from repro.kernels.ivf import ivf_list_topk_pallas

        q, codes, scales, starts, _ = self._case(77, 3, 2, 8, 6, 50)
        # eighths: every product and partial sum of a dot is exact in f32,
        # so the scores cannot depend on the dot's accumulation order
        q = jax.device_put(np.round(np.asarray(q) * 8) / 8)
        # 4 candidates < shortlist 10
        lens = jax.device_put(np.full((3, 2), 2, np.int32))
        s0, r0 = ref.ivf_list_topk_ref(
            q, codes, scales, starts, lens, lpad=6, shortlist=10
        )
        s1, r1 = ivf_list_topk_pallas(
            q, codes, scales, starts, lens,
            lpad=6, shortlist=10, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
        assert np.isneginf(np.asarray(s1)[:, 4:]).all()
        assert (np.asarray(r1)[:, 4:] == -1).all()
