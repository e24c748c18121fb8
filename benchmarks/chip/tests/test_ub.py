"""The vectorised UB generator at a small size, against the semantics of
``repro.graph.generator``: behaviour mix, split fractions, and Zipf skew
of item popularity within clusters."""
import json

import numpy as np

from repro.graph.generator import DatasetSpec, generate as seed_generate

from data import ub

DEP = {
    "num_users": 2000, "num_items": 4000, "num_clusters": 4,
    "behaviors": {"click": 60000, "buy": 3000, "cart": 4000, "fav": 2000},
    "data_seed": 5, "affinity": 0.85, "zipf_a": 1.3, "activity_sigma": 0.7,
}


def _edges(arrays, rel):
    indptr, indices = arrays[f"{rel}.indptr"], arrays[f"{rel}.indices"]
    src = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return src, indices.astype(np.int64)


def test_totals_split_and_csr():
    arrays, meta = ub.generate(DEP)
    U = DEP["num_users"]
    assert meta["events"] == sum(DEP["behaviors"].values())
    assert meta["behaviour_totals"] == DEP["behaviors"]
    # the per-user rule of generator.generate: max(1, int(0.8 n)) train
    train = sum(len(arrays[f"u2{b}2i.indices"]) for b in ub.BEHAVIOURS)
    assert train == meta["train_events"]
    # int() floors each user's share: about half an event under 80% and
    # 10% per user, at 34.5 events per user here
    assert 0.77 < train / meta["events"] < 0.80
    assert 0.08 < meta["val_events"] / meta["events"] < 0.10
    for b in ub.BEHAVIOURS:
        # train edges of each behaviour keep its share of the log
        share = len(arrays[f"u2{b}2i.indices"]) / train
        assert abs(share - DEP["behaviors"][b] / meta["events"]) < 0.01
        # the reverse relation holds the same edges
        u, i = _edges(arrays, f"u2{b}2i")
        i2, u2 = _edges(arrays, f"i2{b}2u")
        fwd = np.sort(u * 10**7 + i)
        rev = np.sort(u2 * 10**7 + i2)
        assert np.array_equal(fwd, rev)
        assert (u < U).all() and (i >= U).all()
    hist = ub.user_history(arrays, U, np.arange(U))
    assert meta["max_history"] == max(len(h) for h in hist)


def _rank_share(users, items, user_cluster, item_cluster, pools):
    """Share of in-cluster events on each cluster's rank-0 and rank-1
    item (pool order = ascending item id, as both generators draw)."""
    top = np.zeros(2)
    n_in = 0
    for c, pool in enumerate(pools):
        m = (user_cluster[users] == c) & (item_cluster[items] == c)
        n_in += m.sum()
        for r in range(2):
            top[r] += (items[m] == pool[r]).sum()
    return top / n_in


def test_zipf_skew_within_clusters_matches_seed_generator():
    dep = dict(DEP, data_seed=11)
    arrays, _ = ub.generate(dep)
    rng = np.random.default_rng(dep["data_seed"])
    user_cluster = rng.integers(0, dep["num_clusters"], dep["num_users"])
    item_cluster = rng.integers(0, dep["num_clusters"], dep["num_items"])
    pools = [np.flatnonzero(item_cluster == c)
             for c in range(dep["num_clusters"])]
    us, its = [], []
    for b in ub.BEHAVIOURS:
        u, i = _edges(arrays, f"u2{b}2i")
        us.append(u)
        its.append(i - dep["num_users"])
    ours = _rank_share(np.concatenate(us), np.concatenate(its), user_cluster,
                       item_cluster, pools)

    spec = DatasetSpec("s", dep["num_users"], dep["num_items"],
                       dep["num_clusters"], {"click": 20000})
    ds = seed_generate(spec, seed=3)
    u, i = ds.train_edges["click"]
    seed_pools = [np.flatnonzero(ds.item_clusters == c)
                  for c in range(spec.num_clusters)]
    theirs = _rank_share(u, i, ds.user_clusters, ds.item_clusters,
                         seed_pools)
    # P(rank 0) / P(rank 1) = 2^1.3 under a Zipf(1.3) law in both
    assert abs(ours[0] / ours[1] - 2 ** 1.3) < 0.15
    assert abs(theirs[0] / theirs[1] - 2 ** 1.3) < 0.3
    # and rank 0 takes about the same share of in-cluster traffic
    assert abs(ours[0] - theirs[0]) < 0.05


def test_cache_round_trip(tmp_path):
    a1, m1 = ub.load_or_generate(DEP, tmp_path, log=lambda s: None)
    a2, m2 = ub.load_or_generate(DEP, tmp_path, log=lambda s: None)
    assert m1 == m2 and a1.keys() == a2.keys()
    for k in a1:
        assert np.array_equal(a1[k], a2[k])
    (entry,) = [p for p in tmp_path.iterdir()]
    assert entry.name == f"ub-{ub.cache_key(DEP)}"
    assert json.loads((entry / "meta.json").read_text())["events"] == \
        m1["events"]
    # another deployment is another entry
    assert ub.cache_key(dict(DEP, data_seed=6)) != ub.cache_key(DEP)
