"""Operation and byte counts against hand counts at tiny shapes, and the
peak table."""
from pathlib import Path

import pytest

import counts
import harness

CHIP = Path(__file__).resolve().parents[1]


def test_tower_edges_fanouts_4_3():
    # layer 0: root <- 4 children, 4 level-1 nodes <- 3 each; layer 1:
    # root <- 4 children: 1 + 4 + 1 = 6 aggregations over 4 + 12 + 4 edges
    assert counts.tower_edges([4, 3]) == (6, 20)
    assert counts.tower_edges([2]) == (1, 2)


def test_tower_flops_by_hand():
    # d = 2, fanouts (4, 3): 6 parents, 20 edges
    lightgcn = {"gnn_type": "lightgcn", "dim": 2, "fanouts": [4, 3]}
    # per parent: d divides + 3d for the residual mix; per edge: d adds
    assert counts.tower_flops(lightgcn) == 6 * (2 + 6) + 20 * 2
    gat = {"gnn_type": "gat", "dim": 2, "fanouts": [4, 3]}
    # per parent: 2d^2 (W h) + 2d (score) + d (relu) + 3d (mix) = 20
    # per edge: 2d^2 (W h) + 2d (score) + 5 (softmax) + 2d (sum) = 21
    assert counts.tower_flops(gat) == 6 * 20 + 20 * 21


def test_step_and_loss_flops_by_hand():
    assert counts.loss_flops(2, 3) == 2 * 2 * 2 * 3 + 4 * 2 * 2
    model = {"gnn_type": "lightgcn", "dim": 3, "fanouts": [1]}
    # one tower: 1 parent (3 + 9) + 1 edge (3) = 15; 4 towers, 2 pairs
    fwd = 4 * 15 + counts.loss_flops(2, 3)
    assert counts.train_step_flops(model, 2, 4) == 3 * fwd


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
