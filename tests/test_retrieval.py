"""Device-side retrieval correctness (repro.retrieval + core.recall).

The contract under test: the chunked/streaming device top-k paths (lax
reference and Pallas kernel) agree with the numpy brute-force oracle
EXACTLY — same ids, same scores, same tie-breaks — across dtypes, chunk
sizes, and exclude-history masking; the IVF coarse-partition path is exact
when probing every cell and recall-bounded otherwise; and the full recall
evaluation (ICF/UCF/U2I + Recall/Hit/NDCG) is method-invariant.
"""
import numpy as np
import pytest

from repro.core.recall import (
    evaluate_recall, evaluate_recall_bruteforce, ranked_metrics,
)
from repro.retrieval import (
    IVFConfig, IVFIndex, brute_force_topk, chunked_topk, pad_id_rows,
)

pytestmark = pytest.mark.quick


def _data(seed=0, Q=29, I=501, d=16, dtype=np.float32, int_valued=False):
    rng = np.random.default_rng(seed)
    if int_valued:  # exact in f32 regardless of summation order -> real ties
        q = rng.integers(-3, 4, size=(Q, d)).astype(dtype)
        it = rng.integers(-3, 4, size=(I, d)).astype(dtype)
    else:
        q = rng.normal(size=(Q, d)).astype(dtype)
        it = rng.normal(size=(I, d)).astype(dtype)
    ex = np.full((Q, 6), -1, np.int32)
    ex[:, :4] = rng.integers(0, I, size=(Q, 4))
    return q, it, ex


class TestChunkedTopk:
    @pytest.mark.parametrize("chunk", [32, 100, 512, 4096])
    def test_ref_matches_oracle_across_chunks(self, chunk):
        q, it, ex = _data()
        s0, i0 = brute_force_topk(q, it, 25, exclude=ex)
        s1, i1 = chunked_topk(q, it, 25, exclude=ex, item_chunk=chunk)
        assert np.array_equal(i0, i1)
        # XLA:CPU and numpy accumulate the d-term dot in different orders
        np.testing.assert_allclose(s0, s1, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("dtype", [np.float32, np.float16, np.float64])
    def test_exact_across_dtypes(self, dtype):
        # every path casts to f32 before scoring, so f16/f64 inputs give
        # identical results to their f32-cast selves
        q, it, ex = _data(dtype=dtype)
        s0, i0 = brute_force_topk(q, it, 10, exclude=ex)
        s1, i1 = chunked_topk(q, it, 10, exclude=ex, item_chunk=64)
        s2, i2 = chunked_topk(q, it, 10, exclude=ex, item_chunk=64,
                              backend="pallas")
        assert np.array_equal(i0, i1) and np.array_equal(i0, i2)
        assert np.array_equal(s0, s1) and np.array_equal(s0, s2)

    def test_pallas_matches_oracle(self):
        q, it, ex = _data(Q=40, I=700)
        s0, i0 = brute_force_topk(q, it, 33, exclude=ex)
        s1, i1 = chunked_topk(q, it, 33, exclude=ex, item_chunk=128,
                              backend="pallas")
        assert np.array_equal(i0, i1)
        assert np.array_equal(s0, s1)

    def test_tie_break_lower_id_wins(self):
        # int-valued embeddings produce many exact score ties; all paths
        # must resolve them identically (ascending item id)
        q, it, _ = _data(int_valued=True, d=6, I=300)
        s0, i0 = brute_force_topk(q, it, 40)
        for backend, chunk in (("ref", 64), ("ref", 999), ("pallas", 128)):
            s, i = chunked_topk(q, it, 40, item_chunk=chunk, backend=backend)
            assert np.array_equal(i0, i), backend
            assert np.array_equal(s0, s), backend

    def test_query_chunking_exact_with_ragged_tail(self):
        q, it, ex = _data(Q=53)
        _, i0 = brute_force_topk(q, it, 7, exclude=ex)
        _, i1 = chunked_topk(q, it, 7, exclude=ex, item_chunk=128,
                             query_chunk=16)
        assert np.array_equal(i0, i1)

    def test_exclude_all_history_never_recommended(self):
        q, it, _ = _data()
        hist = [np.arange(i % 9) for i in range(len(q))]
        ex = pad_id_rows(hist)
        _, ids = chunked_topk(q, it, 20, exclude=ex, item_chunk=64)
        for row, h in zip(ids, hist):
            assert not set(row.tolist()) & set(h.tolist())

    def test_filler_contract_when_k_exceeds_survivors(self):
        # k > non-excluded items: every path must return (-inf, -1) filler
        # slots — never a real (excluded) id — and stay mutually identical
        rng = np.random.default_rng(2)
        q = rng.normal(size=(4, 8)).astype(np.float32)
        it = rng.normal(size=(10, 8)).astype(np.float32)
        ex = np.tile(np.arange(5, dtype=np.int32), (4, 1))  # half excluded
        s0, i0 = brute_force_topk(q, it, 8, exclude=ex)
        assert np.array_equal(i0[:, 7:], np.full((4, 1), -1))
        assert np.isneginf(s0[:, 7:]).all()
        for backend in ("ref", "pallas"):
            s1, i1 = chunked_topk(q, it, 8, exclude=ex, item_chunk=4,
                                  backend=backend)
            assert np.array_equal(i0, i1), backend
            # XLA:CPU and numpy accumulate the d-term dot in different orders
            np.testing.assert_allclose(s0, s1, rtol=1e-6, atol=1e-6, err_msg=backend)
        idx = IVFIndex.build(it, IVFConfig(nlist=3, nprobe=3, seed=0))
        s2, i2 = idx.search(q, 8, exclude=ex)
        assert np.array_equal(i0, i2)

    def test_k_bounds_validated(self):
        q, it, _ = _data()
        with pytest.raises(ValueError):
            chunked_topk(q, it, 0)
        with pytest.raises(ValueError):
            chunked_topk(q, it, len(it) + 1)

    def test_memory_and_latency_do_not_scale_with_sim_matrix(self):
        """The chunked program's temp footprint is O(chunk), not O(Q·I):
        growing the item table 16x leaves compiled temp bytes unchanged
        (a full-similarity-matrix implementation would grow 16x), and
        latency grows at most ~linearly (the unavoidable item sweep)."""
        import time

        from benchmarks.bench_recall import chunked_temp_bytes

        Q, chunk = 64, 1024
        small, big = 8192, 8192 * 16
        tb_small = chunked_temp_bytes(Q, small, chunk)
        tb_big = chunked_temp_bytes(Q, big, chunk)
        # flat up to scan bookkeeping (a few hundred bytes), nowhere near
        # the 16x growth of a materialized (Q, I) score matrix
        assert abs(tb_big - tb_small) < 16_384, (tb_small, tb_big)
        assert tb_big < Q * big * 4 // 8  # far below a (Q, I) score matrix

        rng = np.random.default_rng(0)
        q = rng.normal(size=(Q, 32)).astype(np.float32)
        t = {}
        for I in (small, big):
            it = rng.normal(size=(I, 32)).astype(np.float32)
            chunked_topk(q, it, 50, item_chunk=chunk)  # warm/compile
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                chunked_topk(q, it, 50, item_chunk=chunk)
                best = min(best, time.perf_counter() - t0)
            t[I] = best
        assert t[big] / t[small] < 16 * 4  # linear in I, with CPU-noise slack


class TestIVF:
    def test_probe_all_cells_is_exact(self):
        q, it, ex = _data(I=400)
        idx = IVFIndex.build(it, IVFConfig(nlist=13, nprobe=13, seed=0))
        s0, i0 = brute_force_topk(q, it, 21, exclude=ex)
        s1, i1 = idx.search(q, 21, exclude=ex)
        assert np.array_equal(i0, i1)
        # IVF scores come from a per-candidate gathered dot (einsum), not
        # the dense matmul — same math, ulp-level accumulation difference
        np.testing.assert_allclose(s0, s1, rtol=1e-5)

    def test_partial_probe_recall_bounded(self):
        # clustered corpus (the realistic case): queries sit near centroids,
        # so probing a quarter of the cells keeps most of the exact top-k
        rng = np.random.default_rng(3)
        centers = rng.normal(size=(8, 16)).astype(np.float32) * 3
        it = (centers[rng.integers(0, 8, 2000)]
              + rng.normal(size=(2000, 16)).astype(np.float32))
        q = (centers[rng.integers(0, 8, 64)]
             + 0.5 * rng.normal(size=(64, 16)).astype(np.float32))
        idx = IVFIndex.build(it, IVFConfig(nlist=16, nprobe=4, seed=0))
        _, i0 = brute_force_topk(q, it, 20)
        _, i1 = idx.search(q, 20)
        overlap = np.mean([
            len(set(a.tolist()) & set(b.tolist())) / 20 for a, b in zip(i0, i1)
        ])
        assert overlap >= 0.5, overlap

    def test_train_subsample_build(self):
        q, it, _ = _data(I=600)
        idx = IVFIndex.build(
            it, IVFConfig(nlist=8, nprobe=8, train_size=100, seed=0)
        )
        _, i0 = brute_force_topk(q, it, 9)
        _, i1 = idx.search(q, 9)
        assert np.array_equal(i0, i1)  # exhaustive probing stays exact

    def test_hot_cell_spill_bounds_lists_and_stays_exact(self):
        # pathological clustering: every item near one direction -> without
        # balancing one cell would hold nearly the whole table and the
        # padded candidate gather would scale like brute force
        rng = np.random.default_rng(4)
        it = (np.ones((600, 8)) * 3 + rng.normal(size=(600, 8))).astype(np.float32)
        q = rng.normal(size=(16, 8)).astype(np.float32)
        cfg = IVFConfig(nlist=12, nprobe=12, balance_factor=2.0, seed=0)
        idx = IVFIndex.build(it, cfg)
        cap = int(np.ceil(2.0 * 600 / 12))
        assert idx.lists.shape[1] <= cap
        assert np.sort((idx.lists[idx.lists >= 0])).tolist() == list(range(600))
        _, i0 = brute_force_topk(q, it, 11)
        _, i1 = idx.search(q, 11)
        assert np.array_equal(i0, i1)  # exhaustive probing still exact

    def test_exclusion_respected(self):
        q, it, ex = _data(I=300)
        idx = IVFIndex.build(it, IVFConfig(nlist=8, nprobe=8, seed=0))
        _, ids = idx.search(q, 15, exclude=ex)
        for row, exr in zip(ids, ex):
            assert not set(row.tolist()) & set(exr[exr >= 0].tolist())


class TestIVFQuantizedRerank:
    """The rebuilt IVF path: int8 asymmetric shortlist + exact-dot re-rank.

    At ``nprobe == nlist`` the shortlist is sized to the full probe budget,
    so every candidate survives to the exact re-rank and the result must
    match the brute-force oracle id-for-id — int8 quantization may only
    reorder the shortlist, never the final ranking."""

    @pytest.mark.parametrize("dtype", [np.float32, np.float16, np.float64])
    def test_full_probe_exact_across_dtypes(self, dtype):
        q, it, ex = _data(I=420, dtype=dtype)
        idx = IVFIndex.build(it, IVFConfig(nlist=11, nprobe=11, seed=0))
        s0, i0 = brute_force_topk(q, it, 17, exclude=ex)
        s1, i1 = idx.search(q, 17, exclude=ex)
        assert np.array_equal(i0, i1)
        np.testing.assert_allclose(s0, s1, rtol=1e-5)

    def test_tie_break_lower_id_wins_through_rerank(self):
        # int-valued embeddings: many exact score ties, and the re-rank's
        # f32 dots are exact, so scores AND ids must match the oracle
        q, it, _ = _data(int_valued=True, d=6, I=300)
        idx = IVFIndex.build(it, IVFConfig(nlist=7, nprobe=7, seed=0))
        s0, i0 = brute_force_topk(q, it, 40)
        s1, i1 = idx.search(q, 40)
        assert np.array_equal(i0, i1)
        assert np.array_equal(s0, s1)

    def test_host_and_device_rerank_agree(self):
        # keep_exact_device=False (the 10M mode: only int8 codes resident)
        # re-ranks on host from the builder's numpy table; same results
        q, it, ex = _data(I=350)
        dev = IVFIndex.build(it, IVFConfig(nlist=9, nprobe=9, seed=0))
        host = IVFIndex.build(
            it, IVFConfig(nlist=9, nprobe=9, seed=0, keep_exact_device=False)
        )
        sd, idd = dev.search(q, 13, exclude=ex)
        sh, ih = host.search(q, 13, exclude=ex)
        assert np.array_equal(idd, ih)
        np.testing.assert_allclose(sd, sh, rtol=1e-6)

    def test_hier_assign_full_probe_stays_exact(self):
        # hierarchical assignment approximates WHICH cell an item lands in,
        # never whether it lands somewhere — exhaustive probing stays exact
        q, it, ex = _data(I=500)
        idx = IVFIndex.build(
            it, IVFConfig(nlist=16, nprobe=16, seed=0, assign_mode="hier")
        )
        _, i0 = brute_force_topk(q, it, 19, exclude=ex)
        _, i1 = idx.search(q, 19, exclude=ex)
        assert np.array_equal(i0, i1)

    def test_rerank_budget_respected_and_results_valid(self):
        q, it, _ = _data(I=400)
        idx = IVFIndex.build(it, IVFConfig(nlist=10, nprobe=4, rerank=32, seed=0))
        s, i = idx.search(q, 20)
        assert s.shape == (len(q), 20) and i.shape == (len(q), 20)
        ok = i >= 0
        assert np.isfinite(s[ok]).all() and np.isneginf(s[~ok]).all()

    def test_build_deterministic(self):
        # k-means reseed + vectorized spill are pure functions of the seed
        _, it, _ = _data(I=700)
        cfg = IVFConfig(nlist=12, nprobe=4, balance_factor=1.5, seed=0)
        a = IVFIndex.build(it, cfg)
        b = IVFIndex.build(it, cfg)
        assert np.array_equal(a.order, b.order)
        assert np.array_equal(a.offsets, b.offsets)
        assert np.array_equal(a.codes, b.codes)
        assert np.array_equal(a.scales, b.scales)

    def test_spill_rank_rounds_cap_and_permutation(self):
        # pathological input: every item assigned to one hot cell; the
        # vectorized rank-round spill must end with every cell at <= cap,
        # every item placed exactly once, deterministically
        from repro.retrieval.ivf import _spill_hot_cells

        rng = np.random.default_rng(6)
        I, nlist, d = 400, 10, 8
        norm = rng.normal(size=(I, d)).astype(np.float32)
        norm /= np.linalg.norm(norm, axis=1, keepdims=True)
        cent = rng.normal(size=(nlist, d)).astype(np.float32)
        cent /= np.linalg.norm(cent, axis=1, keepdims=True)
        assign = np.zeros(I, dtype=np.int64)
        out = _spill_hot_cells(norm, cent, assign, cap=40)
        counts = np.bincount(out, minlength=nlist)
        assert counts.max() <= 40
        assert counts.sum() == I
        assert np.array_equal(out, _spill_hot_cells(norm, cent, assign, cap=40))

    def test_config_validation(self):
        it = np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32)
        with pytest.raises(ValueError, match="rerank"):
            IVFIndex.build(it, IVFConfig(nlist=4, rerank=-1))
        with pytest.raises(ValueError, match="assign_mode"):
            IVFIndex.build(it, IVFConfig(nlist=4, assign_mode="fast"))
        with pytest.raises(ValueError, match="backend"):
            IVFIndex.build(it, IVFConfig(nlist=4, backend="cuda"))


class TestIVFDeviceResidency:
    """Device residency contract: build() uploads the table once; search()
    only ever transfers queries/exclusions in and (Q, k) results out."""

    def test_search_under_disallow_transfer_guard(self):
        import jax

        q, it, ex = _data(I=800)
        idx = IVFIndex.build(it, IVFConfig(nlist=8, nprobe=3, seed=0))
        warm = idx.search(q, 12, exclude=ex)  # compile outside the guard
        with jax.transfer_guard("disallow"):  # implicit transfers -> error
            s, i = idx.search(q, 12, exclude=ex)
        assert np.array_equal(warm[1], i)
        assert np.array_equal(warm[0], s)

    def test_search_uploads_only_query_sized_arrays(self, monkeypatch):
        import jax

        q, it, ex = _data(I=1200)
        idx = IVFIndex.build(it, IVFConfig(nlist=16, nprobe=4, seed=0))
        idx.search(q, 9, exclude=ex)  # warm: jit cached, residency done
        real = jax.device_put
        put_bytes = []

        def spy(x, *args, **kwargs):
            put_bytes.append(getattr(x, "nbytes", 0))
            return real(x, *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", spy)
        idx.search(q, 9, exclude=ex)
        assert put_bytes, "spy saw no uploads at all"
        # nothing bigger than the query/exclusion batch — in particular
        # never the codes, scales, or exact item table
        assert max(put_bytes) <= max(q.nbytes, ex.nbytes), put_bytes

    def test_chunked_topk_table_cached_across_calls(self, monkeypatch):
        import jax

        q, it, ex = _data(I=2000)
        chunked_topk(q, it, 10, exclude=ex, item_chunk=256)  # populates cache
        real = jax.device_put
        put_bytes = []

        def spy(x, *args, **kwargs):
            put_bytes.append(getattr(x, "nbytes", 0))
            return real(x, *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", spy)
        chunked_topk(q, it, 10, exclude=ex, item_chunk=256)
        assert put_bytes and max(put_bytes) <= max(q.nbytes, ex.nbytes)


class TestRankedMetrics:
    def test_closed_form_values(self):
        # rec hits truth at ranks 0 and 2 of 4; |truth| = 3
        rec = np.array([[7, 1, 9, 2]])
        truth = [{7, 9, 5}]
        m = ranked_metrics(rec, truth, top_k=4)
        assert m["recall"] == pytest.approx(2 / 3)
        assert m["hit"] == 1.0
        dcg = 1 / np.log2(2) + 1 / np.log2(4)
        idcg = 1 / np.log2(2) + 1 / np.log2(3) + 1 / np.log2(4)
        assert m["ndcg"] == pytest.approx(dcg / idcg)

    def test_perfect_and_zero(self):
        rec = np.array([[3, 1], [5, 6]])
        assert ranked_metrics(rec, [{3, 1}, {5, 6}], 2) == {
            "recall": 1.0, "hit": 1.0, "ndcg": 1.0,
        }
        m = ranked_metrics(rec, [{9}, {9}], 2)
        assert m == {"recall": 0.0, "hit": 0.0, "ndcg": 0.0}

    def test_pad_ids_never_count(self):
        m = ranked_metrics(np.array([[-1, -1, 4]]), [{4}], 3)
        assert m["hit"] == 1.0 and m["recall"] == 1.0
        # -1 at ranks 0-1 pushed the hit to rank 2 -> discounted NDCG
        assert m["ndcg"] == pytest.approx((1 / np.log2(4)) / (1 / np.log2(2)))


class TestEvaluateRecall:
    def _pairs(self, seed=5, U=80, I=160):
        rng = np.random.default_rng(seed)
        ue = rng.normal(size=(U, 12)).astype(np.float32)
        ie = rng.normal(size=(I, 12)).astype(np.float32)
        train = np.stack([rng.integers(0, U, 500), rng.integers(0, I, 500)], 1)
        evalp = np.stack([rng.integers(0, U, 120), rng.integers(0, I, 120)], 1)
        return ue, ie, train, evalp

    def test_device_equals_oracle_all_strategies(self):
        ue, ie, train, evalp = self._pairs()
        kw = dict(top_k=20, top_n=8, item_chunk=64, user_chunk=17)
        a = evaluate_recall_bruteforce(ue, ie, train, evalp, **kw)
        b = evaluate_recall(ue, ie, train, evalp, method="device", **kw)
        assert a == b
        assert set(a) == {
            f"{s}{m}" for s in ("icf", "ucf", "u2i")
            for m in ("", "_hit", "_ndcg")
        }

    def test_method_invariant_when_topk_covers_catalog(self):
        # top_k == num_items forces filler slots for every user with
        # history; held-out items that also appear in train history make
        # miscounted fillers visible in the metrics
        rng = np.random.default_rng(11)
        U, I = 6, 8
        ue = rng.normal(size=(U, 4)).astype(np.float32)
        ie = rng.normal(size=(I, 4)).astype(np.float32)
        train = np.stack([np.arange(U), rng.integers(0, I, U)], 1)
        evalp = np.concatenate([train[:3], np.stack(
            [np.arange(U), rng.integers(0, I, U)], 1)])  # overlap w/ history
        kw = dict(top_k=I, top_n=I, item_chunk=4)
        a = evaluate_recall_bruteforce(ue, ie, train, evalp, **kw)
        b = evaluate_recall(ue, ie, train, evalp, method="device", **kw)
        assert a == b

    def test_pallas_backend_equals_oracle(self):
        ue, ie, train, evalp = self._pairs(seed=7, U=40, I=90)
        kw = dict(top_k=15, top_n=5, item_chunk=32)
        a = evaluate_recall_bruteforce(ue, ie, train, evalp, **kw)
        b = evaluate_recall(ue, ie, train, evalp, method="device",
                            backend="pallas", **kw)
        assert a == b

    def test_ivf_method_bounded(self):
        ue, ie, train, evalp = self._pairs(seed=9)
        out = evaluate_recall(ue, ie, train, evalp, top_k=20, method="ivf",
                              ivf=IVFConfig(nlist=8, nprobe=8))
        for v in out.values():
            assert 0.0 <= v <= 1.0

    def test_no_subsampling_by_default_and_cap_respected(self):
        ue, ie, train, evalp = self._pairs()
        full = evaluate_recall(ue, ie, train, evalp, top_k=10,
                               strategies=("u2i",))
        capped = evaluate_recall(ue, ie, train, evalp, top_k=10,
                                 strategies=("u2i",), max_users=5, seed=1)
        assert set(full) == set(capped)  # same shape, different user pools
        # determinism: same call twice is identical
        again = evaluate_recall(ue, ie, train, evalp, top_k=10,
                                strategies=("u2i",))
        assert full == again

    def test_strategy_subset_only_computes_requested(self):
        ue, ie, train, evalp = self._pairs()
        out = evaluate_recall(ue, ie, train, evalp, strategies=("u2i",))
        assert set(out) == {"u2i", "u2i_hit", "u2i_ndcg"}

    def test_empty_eval_users(self):
        ue, ie, train, _ = self._pairs()
        out = evaluate_recall(ue, ie, train, np.empty((0, 2), np.int64))
        assert all(v == 0.0 for v in out.values())

    def test_trained_checkpoint_embeddings_method_invariant(
        self, toy_ds, trained_embeddings
    ):
        """On the shared trained-checkpoint fixture (tests/conftest.py) the
        device path still matches the oracle exactly — realistic embedding
        geometry, not just random gaussians."""
        ue, ie, train = trained_embeddings
        evalp = toy_ds.val_pairs
        kw = dict(top_k=20, top_n=8, item_chunk=64)
        a = evaluate_recall_bruteforce(ue, ie, train, evalp, **kw)
        b = evaluate_recall(ue, ie, train, evalp, method="device", **kw)
        assert a == b


class TestChunkSizeValidation:
    """Non-positive chunk widths used to be silently accepted (clamped or
    looped over nothing); they now raise ValueError at the API boundary."""

    def test_ivf_rejects_nonpositive_chunks_and_probes(self):
        it = np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32)
        with pytest.raises(ValueError, match="assign_chunk"):
            IVFIndex.build(it, IVFConfig(nlist=4, assign_chunk=0))
        with pytest.raises(ValueError, match="assign_chunk"):
            IVFIndex.build(it, IVFConfig(nlist=4, assign_chunk=-5))
        with pytest.raises(ValueError, match="nlist"):
            IVFIndex.build(it, IVFConfig(nlist=0))
        with pytest.raises(ValueError, match="nprobe"):
            IVFIndex.build(it, IVFConfig(nlist=4, nprobe=0))
        idx = IVFIndex.build(it, IVFConfig(nlist=4, nprobe=2))
        with pytest.raises(ValueError, match="nprobe"):
            idx.search(it[:3], 5, nprobe=0)
        # custom positive chunk width stays exact
        idx2 = IVFIndex.build(it, IVFConfig(nlist=4, nprobe=4, assign_chunk=7))
        s, i = idx2.search(it[:3], 5)
        s0, i0 = IVFIndex.build(it, IVFConfig(nlist=4, nprobe=4)).search(it[:3], 5)
        assert np.array_equal(i, i0)

    def test_embed_all_nodes_rejects_nonpositive_batch(self, toy_ds, make_model_cfg):
        import jax

        from repro.core.model import init_model_params
        from repro.infer import embed_all_nodes

        g = toy_ds.graph
        cfg = make_model_cfg(g, gnn=False)
        params = init_model_params(jax.random.PRNGKey(0), cfg)
        for bad in (0, -1):
            with pytest.raises(ValueError, match="batch_size"):
                embed_all_nodes(params, cfg, g, g, batch_size=bad)

    def test_chunked_topk_rejects_nonpositive_chunks(self):
        q, it, _ = _data()
        with pytest.raises(ValueError, match="item_chunk"):
            chunked_topk(q, it, 5, item_chunk=0)
        with pytest.raises(ValueError, match="query_chunk"):
            chunked_topk(q, it, 5, query_chunk=-1)
