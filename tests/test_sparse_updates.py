"""Gather→step→scatter sparse training tests: sparse-vs-dense equivalence
(params + losses after K steps, across model families, slot modes, and the
ps_lookup/shard_map pull path), padded-bucket edge cases, and the
O(batch)-not-O(N) regression guard."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Graph4RecConfig, HeteroGNNConfig
from repro.embedding import (
    EmbeddingConfig, SlotSpec, gather_rows, lookup, ps_lookup, remap_ids,
    rowwise_adagrad_init, rowwise_adagrad_scatter_update, scatter_rows,
    unique_pad_ids,
)
from repro.graph import DistributedGraphEngine, TOY, generate
from repro.launch.mesh import make_host_mesh
from repro.sampling import EgoConfig, PairConfig, PipelineConfig
from repro.train import Graph4RecTrainer, TrainerConfig
from repro.train import optimizer as opt_lib
from repro.walk import WalkConfig

pytestmark = pytest.mark.quick

RELS = ("u2click2i", "i2click2u")


@pytest.fixture(scope="module")
def ds():
    return generate(TOY, seed=0)


def build_trainer(ds, sparse, gnn_type="lightgcn", side_info=False,
                  slot_mode="bag", loss="inbatch_softmax", steps=12, **cfg_kw):
    g = ds.graph
    slots = (
        (SlotSpec("slot0", 64, 3), SlotSpec("slot1", 64, 3)) if side_info else ()
    )
    walk_based = gnn_type is None
    mc = Graph4RecConfig(
        embedding=EmbeddingConfig(num_nodes=g.num_nodes, dim=16, slots=slots),
        gnn=None if walk_based else HeteroGNNConfig(
            gnn_type=gnn_type, num_relations=2, num_layers=2, dim=16),
        fanouts=() if walk_based else (3, 2),
        relations=RELS,
        use_side_info=side_info,
        slot_mode=slot_mode,
        loss=loss,
    )
    pc = PipelineConfig(
        walk=WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=5),
        pair=PairConfig(win_size=2,
                        neg_mode="random" if loss == "neg_sampling" else "inbatch"),
        ego=None if walk_based else EgoConfig(relations=list(RELS), fanouts=[3, 2]),
        batch_pairs=64, walks_per_round=32,
    )
    eng = DistributedGraphEngine(g, num_partitions=2)
    # The toy graph sits below the default sparse/dense crossover
    # (sparse_min_rows) — force the sparse path so these tests keep
    # exercising gather→step→scatter rather than the dense reroute.
    cfg_kw.setdefault("sparse_min_rows", 0)
    return Graph4RecTrainer(
        ds, eng, mc, pc,
        TrainerConfig(num_steps=steps, log_every=0, seed=0, sparse_lr=0.5,
                      prefetch_batches=0, eval_at_end=False,
                      sparse_updates=sparse, **cfg_kw),
    )


def assert_runs_match(rs, rd, rtol=1e-5, atol=1e-6):
    np.testing.assert_allclose(rs.losses, rd.losses, rtol=rtol, atol=atol)
    assert rs.params.keys() == rd.params.keys()
    for k in rs.params:
        np.testing.assert_allclose(
            np.asarray(rs.params[k]), np.asarray(rd.params[k]),
            rtol=rtol, atol=atol, err_msg=k,
        )


class TestSparseDenseEquivalence:
    @pytest.mark.parametrize("kw", [
        dict(gnn_type=None),
        dict(gnn_type="lightgcn"),
        dict(gnn_type="lightgcn", side_info=True, slot_mode="bag"),
        dict(gnn_type=None, side_info=True, slot_mode="values"),
        dict(gnn_type=None, loss="neg_sampling"),
    ], ids=["walk", "gnn", "gnn-bag", "walk-values", "walk-negsamp"])
    def test_k_steps_match(self, ds, kw):
        rs = build_trainer(ds, sparse=True, **kw).train()
        rd = build_trainer(ds, sparse=False, **kw).train()
        assert_runs_match(rs, rd)

    def test_bucket_overflow_still_exact(self, ds):
        """Batches touching more unique ids than the initial bucket width:
        the bucket grows (power-of-two recompile), results stay exact."""
        tr = build_trainer(ds, sparse=True, unique_bucket=8)
        assert tr._buckets["node"] == 8
        rs = tr.train()
        assert tr._buckets["node"] > 8  # grew past the deliberately-tiny seed
        rd = build_trainer(ds, sparse=False).train()
        assert_runs_match(rs, rd)

    def test_untouched_slot_tables_pass_through(self, ds):
        """Slot tables exist but side info is off: the batch never touches
        them, the sparse step must leave them (and training) intact."""
        tr = build_trainer(ds, sparse=True, gnn_type=None, steps=4)
        mc = tr.model_cfg
        mc = dataclasses.replace(
            mc,
            embedding=dataclasses.replace(
                mc.embedding, slots=(SlotSpec("ghost", 16, 2),)
            ),
            use_side_info=False,
        )
        tr2 = Graph4RecTrainer(ds, tr.engine, mc, tr.pipe_cfg, tr.cfg)
        params0 = tr2.init_params()
        ghost0 = np.asarray(params0["emb/slot:ghost"]).copy()
        res = tr2.train(params0)
        assert np.isfinite(res.losses).all()
        np.testing.assert_array_equal(
            np.asarray(res.params["emb/slot:ghost"]), ghost0
        )


class TestPsLookupEquivalence:
    def test_sparse_scatter_matches_ps_lookup_training(self):
        """K manual steps where embeddings are pulled via the shard_map
        ps_lookup (dense grads, full-table row-wise AdaGrad) vs the
        gather→step→scatter path — identical tables."""
        mesh = make_host_mesh()
        N, D, K = 32, 8, 6
        rng = np.random.default_rng(0)
        table_a = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
        table_b = table_a
        dense_opt = opt_lib.rowwise_adagrad(0.3, init_accum=0.1)
        st_a = dense_opt.init({"node": table_a})
        st_b = rowwise_adagrad_init({"node": table_b}, init_accum=0.1)
        batches = [rng.integers(0, N, size=24) for _ in range(K)]
        # a PAD in the batch exercises the masking on both paths
        batches[2][0] = -1

        def loss_ps(tab, ids):
            return (ps_lookup(tab, ids, mesh) ** 2).mean()

        def loss_local(sub, local_ids):
            return (lookup(sub, local_ids) ** 2).mean()

        for ids in batches:
            ids_j = jnp.asarray(ids)
            g = jax.grad(loss_ps)(table_a, ids_j)
            upd, st_a = dense_opt.update({"node": g}, st_a)
            table_a = table_a + upd["node"]

            uniq = unique_pad_ids([ids], bucket=64)
            local = jnp.asarray(remap_ids(uniq, ids))
            uniq_j = jnp.asarray(uniq)
            sub = gather_rows(table_b, uniq_j)
            g_sub = jax.grad(loss_local)(sub, local)
            new_p, st_b = rowwise_adagrad_scatter_update(
                {"node": table_b}, {"node": g_sub}, {"node": uniq_j}, st_b,
                lr=0.3,
            )
            table_b = new_p["node"]
        np.testing.assert_allclose(
            np.asarray(table_a), np.asarray(table_b), rtol=1e-5, atol=1e-6
        )


class TestUniqueBucketHelpers:
    def test_unique_pad_ids_layout(self):
        uniq = unique_pad_ids([np.array([5, 3, 5, -1, 9])], bucket=8)
        np.testing.assert_array_equal(uniq, [-1, -1, -1, -1, -1, 3, 5, 9])

    def test_bucket_grows_power_of_two(self):
        uniq = unique_pad_ids([np.arange(20)], bucket=8)
        assert len(uniq) == 32

    def test_remap_roundtrip(self):
        ids = np.array([[7, 2], [-1, 11]])
        uniq = unique_pad_ids([ids], bucket=8)
        local = remap_ids(uniq, ids)
        assert local[1, 0] == -1
        np.testing.assert_array_equal(uniq[local[local >= 0]], ids[ids >= 0])

    def test_scatter_rows_drops_pads(self):
        table = jnp.zeros((4, 2))
        uniq = jnp.asarray([-1, -1, 1, 3])
        rows = jnp.ones((4, 2))
        out = scatter_rows(table, uniq, rows)
        np.testing.assert_allclose(np.asarray(out), [[0, 0], [1, 1], [0, 0], [1, 1]])


class TestCostFlatInTableSize:
    def test_sparse_step_cost_does_not_scale_with_rows(self):
        """Regression guard: the sparse step is O(unique ids) — timing it on
        a 10k-row vs a 100k-row table at fixed batch must stay in the same
        ballpark (a dense update would be ~10x)."""
        B, D, bucket = 256, 32, 512
        lr = 0.5

        def make_step():
            def step(table, accum, uniq, local):
                sub = gather_rows(table, uniq)

                def loss_of(s):
                    return (lookup(s, local) ** 2).mean()

                g = jax.grad(loss_of)(sub)
                new_p, st = rowwise_adagrad_scatter_update(
                    {"t": table}, {"t": g}, {"t": uniq},
                    rowwise_adagrad_init({"t": table}), lr=lr,
                )
                return new_p["t"], st.accum["t"]

            return jax.jit(step, donate_argnums=(0, 1))

        def time_step(N):
            rng = np.random.default_rng(0)
            table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
            accum = jnp.full((N, 1), 0.1, jnp.float32)
            ids = rng.integers(0, N, size=B)
            uniq = unique_pad_ids([ids], bucket=bucket)
            local = jnp.asarray(remap_ids(uniq, ids))
            uniq_j = jnp.asarray(uniq)
            step = make_step()
            table, accum = step(table, accum, uniq_j, local)  # compile
            jax.block_until_ready(table)
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(20):
                    table, accum = step(table, accum, uniq_j, local)
                jax.block_until_ready(table)
                best = min(best, (time.perf_counter() - t0) / 20)
            return best

        t_small = time_step(10_000)
        t_large = time_step(100_000)
        # flat in N up to noise; a dense O(N) update would be ~10x
        assert t_large < t_small * 4 + 1e-4, (t_small, t_large)
