"""Operation and byte counts against hand counts at tiny shapes, and the
peak table."""
from pathlib import Path

import numpy as np
import pytest

import counts
import harness
from conftest import full_tower

CHIP = Path(__file__).resolve().parents[1]


def test_tower_edges_fanouts_4_3():
    # layer 0: root <- 4 children, 4 level-1 nodes <- 3 each; layer 1:
    # root <- 4 children: 1 + 4 + 1 = 6 aggregations over 4 + 12 + 4 edges,
    # each parent mixing one live relation
    assert counts.tower_work(full_tower([4, 3]), [4, 3], 1) == (
        6, 20, {1: 6})
    assert counts.tower_work(full_tower([2]), [2], 1) == (1, 2, {1: 1})
    # a second relation whose blocks are all PAD adds nothing
    assert counts.tower_work(full_tower([4, 3], 2, 1), [4, 3], 2) == (
        6, 20, {1: 6})


def test_tower_flops_by_hand():
    # d = 2, fanouts (4, 3): 6 parents, 20 edges
    lightgcn = {"gnn_type": "lightgcn", "dim": 2, "fanouts": [4, 3],
                "relations": ["r0"]}
    tower = full_tower([4, 3])
    # per parent: d divides + 3d for the residual mix; per edge: d adds
    assert counts.tower_flops(lightgcn, harness.encoder(lightgcn, CHIP),
                              tower) == 6 * (2 + 6) + 20 * 2
    gat = {"gnn_type": "gat", "dim": 2, "fanouts": [4, 3],
           "relations": ["r0"]}
    # per parent: 2d^2 (W h) + 2d (score) + d (relu) + 3d (mix) = 20
    # per edge: 2d^2 (W h) + 2d (score) + 5 (softmax) + 2d (sum) = 21
    assert counts.tower_flops(gat, harness.encoder(gat, CHIP), tower) == (
        6 * 20 + 20 * 21)


def several_relations_tower():
    """d = 2, fanouts (2, 2), relations u2a2i, u2b2i, i2a2u, i2b2u: a user
    root with two live relations (a: 2 children; b: 1 child and one PAD
    child) and none under the item relations; items i1 and i3 with two
    live relations of 2 children each, item i2 with relation b empty."""
    P = -1
    lvl1 = [1, 2, 3, P, P, P, P, P]  # blocks u2a2i, u2b2i, i2a2u, i2b2u
    lvl2 = ([P, P, P, P, 0, 4, 4, 4]      # i1: a and b live
            + [P, P, P, P, 0, 0, P, P]    # i2: b empty
            + [P, P, P, P, 0, 5, 0, 0]    # i3: a and b live
            + [P] * 8 * 5)                # the PAD slots' children
    return [np.array([[0]]), np.array([lvl1]), np.array([lvl2])]


def test_several_live_relations_by_hand():
    tower = several_relations_tower()
    # layer 0: the root (2 pairs, 3 edges, mixes 2), i1 (2, 4, 2), i2
    # (1, 2, 1), i3 (2, 4, 2); layer 1: the root again (2, 3, 2)
    assert counts.tower_work(tower, [2, 2], 4) == (9, 16, {2: 4, 1: 1})
    rels = ["u2a2i", "u2b2i", "i2a2u", "i2b2u"]
    uniform = harness.encoder({"gnn_type": "lightgcn"}, CHIP).mix
    # n - 1 adds per dim: nothing for one relation
    assert [uniform.flops(2, n) for n in (1, 2, 4)] == [0, 2, 6]
    lightgcn = {"gnn_type": "lightgcn", "dim": 2, "fanouts": [2, 2],
                "relations": rels}
    # 9 pairs x d divides + 16 edges x d adds + 5 live parents x 3d
    # residual + 4 two-relation mixes x d adds
    assert counts.tower_flops(lightgcn, harness.encoder(lightgcn, CHIP),
                              tower) == 9 * 2 + 16 * 2 + 5 * 6 + 4 * 2
    gat = {"gnn_type": "gat", "dim": 2, "fanouts": [2, 2], "relations": rels}
    # per pair 14 (W h_self, score, relu), per edge 21, as above
    assert counts.tower_flops(gat, harness.encoder(gat, CHIP), tower) == (
        9 * 14 + 16 * 21 + 5 * 6 + 4 * 2)


def test_step_and_loss_flops_by_hand():
    assert counts.loss_flops(2, 3) == 2 * 2 * 2 * 3 + 4 * 2 * 2
    model = {"gnn_type": "lightgcn", "dim": 3, "fanouts": [1],
             "relations": ["r0"]}
    # one tower: 1 parent (3 + 9) + 1 edge (3) = 15; 4 towers, 2 pairs
    fwd = 4 * 15 + counts.loss_flops(2, 3)
    enc = harness.encoder(model, CHIP)
    step = [np.repeat(l, 4, axis=0) for l in full_tower([1])]
    assert counts.train_step_flops(model, enc, 2, [step]) == 3 * fwd
    # the mean over checked steps: a step with two of its towers PAD
    half = [np.where(np.arange(4)[:, None] < 2, l, -1) for l in step]
    assert counts.train_step_flops(model, enc, 2, [step, half]) == 3 * (
        3 * 15 + counts.loss_flops(2, 3))


def test_kernel_costs_by_hand():
    # 2 walks of length 3, 4 window positions: read 6 ids, write 2 x 8
    assert counts.window_pairs_cost(2, 3, 4) == (3 * 2 * 4, 4 * 6 + 8 * 8)
    # Q=2, I=5, d=3, E=4, k=1: 2*2*5*3 flops; 4 bytes x (15 + 6 + 8 + 4)
    assert counts.topk_scan_cost(2, 5, 3, 4, 1) == (60, 4 * 33)


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds(1000, 5, peak) == 10.0
    assert counts.roofline_seconds(100, 50, peak) == 5.0


def test_peaks_known_and_unknown_device():
    p = harness.peak_for("TPU v5 lite", CHIP)
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peak_for("some other chip", CHIP)
