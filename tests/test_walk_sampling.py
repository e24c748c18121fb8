"""Random-walk + ego/pair sampling pipeline tests (paper §3.2-3.4, §3.6)."""
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from repro.graph import DistributedGraphEngine, TOY, generate
from repro.sampling import (
    EgoConfig, PAD, PairConfig, PipelineConfig, SamplePipeline,
    sample_ego_batch, window_pairs, pairs_to_nodes,
)
from repro.walk import MetapathWalker, WalkConfig, parse_metapath


@pytest.fixture(scope="module")
def ds():
    return generate(TOY, seed=0)


class TestMetapath:
    def test_parse(self):
        assert parse_metapath("u2click2i - i2click2u") == ["u2click2i", "i2click2u"]

    def test_parse_type_mismatch(self):
        with pytest.raises(ValueError):
            parse_metapath("u2click2i - u2click2i")

    def test_walk_follows_relations(self, ds):
        cfg = WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=6)
        walker = MetapathWalker(ds.graph, cfg)
        rng = np.random.default_rng(0)
        starts = walker.start_nodes(rng, 0, 16)
        paths = walker.walk(rng, starts, 0)
        assert paths.shape == (16, 6)
        rels = ["u2click2i", "i2click2u"]
        for row in paths:
            for step in range(1, 6):
                if row[step] == PAD:
                    continue
                rel = ds.graph.relations[rels[(step - 1) % 2]]
                assert row[step] in rel.neighbors(row[step - 1])

    def test_walk_alternates_types(self, ds):
        cfg = WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=5)
        walker = MetapathWalker(ds.graph, cfg)
        rng = np.random.default_rng(1)
        paths = walker.walk(rng, walker.start_nodes(rng, 0, 8), 0)
        nu = TOY.num_users
        for row in paths:
            for step, node in enumerate(row):
                if node == PAD:
                    continue
                expected = "u" if step % 2 == 0 else "i"
                got = "u" if node < nu else "i"
                assert got == expected

    def test_pad_after_dead_end(self, ds):
        cfg = WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=8)
        walker = MetapathWalker(ds.graph, cfg)
        rng = np.random.default_rng(2)
        paths = walker.generate(rng, 32)
        for row in paths:
            seen_pad = False
            for x in row:
                if x == PAD:
                    seen_pad = True
                else:
                    assert not seen_pad  # PAD only as suffix


class TestEgo:
    def test_level_widths(self, ds):
        cfg = EgoConfig(relations=["u2click2i", "i2click2u"], fanouts=[3, 2])
        rng = np.random.default_rng(0)
        ego = sample_ego_batch(rng, ds.graph, np.arange(5), cfg)
        assert ego.levels[0].shape == (5, 1)
        assert ego.levels[1].shape == (5, 2 * 3)
        assert ego.levels[2].shape == (5, 6 * 2 * 2)
        assert cfg.level_width(2) == 24

    def test_relation_slices_are_neighbors(self, ds):
        cfg = EgoConfig(relations=["u2click2i", "i2click2u"], fanouts=[4])
        rng = np.random.default_rng(0)
        centers = np.arange(8)
        ego = sample_ego_batch(rng, ds.graph, centers, cfg)
        lvl = ego.levels[1].reshape(8, 1, 2, 4)
        for b, c in enumerate(centers):
            for ri, rel in enumerate(cfg.relations):
                nbrs = set(ds.graph.relations[rel].neighbors(c).tolist())
                for x in lvl[b, 0, ri]:
                    assert (x == PAD and not nbrs) or x in nbrs

    def test_pad_propagates(self, ds):
        # a center with no neighbors under the relation -> all levels PAD
        cfg = EgoConfig(relations=["u2click2i"], fanouts=[2, 2])
        rng = np.random.default_rng(0)
        item_node = np.array([TOY.num_users])  # items have no u2click2i edges
        ego = sample_ego_batch(rng, ds.graph, item_node, cfg)
        assert (ego.levels[1] == PAD).all()
        assert (ego.levels[2] == PAD).all()


class TestPairs:
    def test_window_pairs(self):
        paths = np.array([[1, 2, 3, PAD]])
        pairs = window_pairs(paths, win_size=2)
        got = {(r[1], r[2]) for r in pairs}
        assert got == {(0, 1), (1, 0), (1, 2), (2, 1), (0, 2), (2, 0)}

    def test_window_respects_pad(self):
        paths = np.array([[1, PAD, 3]])
        pairs = window_pairs(paths, win_size=2)
        for r in pairs:
            assert paths[r[0], r[1]] != PAD and paths[r[0], r[2]] != PAD


class TestPipelineOrders:
    """RQ5: ego-first does O(L) ego samplings, pair-first O(wL)."""

    def _run(self, ds, order):
        eng = DistributedGraphEngine(ds.graph, num_partitions=4)
        cfg = PipelineConfig(
            walk=WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=6),
            pair=PairConfig(win_size=2),
            ego=EgoConfig(relations=["u2click2i", "i2click2u"], fanouts=[3]),
            order=order, batch_pairs=64, walks_per_round=16,
        )
        pipe = SamplePipeline(eng, cfg, seed=0)
        batches = list(pipe.batches(3))
        return pipe, batches

    def test_batches_fixed_size(self, ds):
        _, batches = self._run(ds, "walk_ego_pair")
        for b in batches:
            assert len(b.src_ids) == 64
            assert b.src_ego.levels[0].shape[0] == 64

    def test_ego_first_cheaper(self, ds):
        # the engine's request counter is the communication cost (§3.6)
        pipe_fast, _ = self._run(ds, "walk_ego_pair")
        pipe_slow, _ = self._run(ds, "walk_pair_ego")
        assert (pipe_fast.engine.stats.neighbor_requests
                < pipe_slow.engine.stats.neighbor_requests)

    def test_pair_endpoints_match_ego_centers(self, ds):
        _, batches = self._run(ds, "walk_ego_pair")
        for b in batches:
            np.testing.assert_array_equal(b.src_ids, b.src_ego.centers)
            np.testing.assert_array_equal(b.dst_ids, b.dst_ego.centers)

    def test_random_negative_mode(self, ds):
        eng = DistributedGraphEngine(ds.graph, num_partitions=2)
        cfg = PipelineConfig(
            walk=WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=6),
            pair=PairConfig(win_size=2, neg_mode="random", num_negatives=3),
            ego=EgoConfig(relations=["u2click2i", "i2click2u"], fanouts=[2]),
            batch_pairs=32, walks_per_round=16,
        )
        pipe = SamplePipeline(eng, cfg, seed=0)
        b = next(iter(pipe.batches(1)))
        assert b.neg_ids.shape == (32, 3)
        assert b.neg_ego.levels[0].shape[0] == 32 * 3
