"""Operations and bytes the algorithms need, computed from shapes.

Only needed work is counted, never padding: an ego tower of a bipartite
user-item graph has, at every hop, neighbours under exactly one of its
relations (users have none under the item relation and the reverse), so
the dense layout's other relation slots, and the children of those PAD
slots, are padding. Backward is counted as twice the forward.
"""
from __future__ import annotations

from typing import Dict, Tuple


def tower_edges(fanouts) -> Tuple[int, int]:
    """(parent aggregations, child edges) over all layers of one needed
    ego tree: layer l collapses levels 0..K-1-l into their parents."""
    K = len(fanouts)
    width = [1]
    for f in fanouts:
        width.append(width[-1] * f)
    parents = edges = 0
    for layer in range(K):
        for k in range(K - layer):
            parents += width[k]
            edges += width[k] * fanouts[k]
    return parents, edges


def tower_flops(model: Dict) -> int:
    """Forward FLOPs of the encoder for one ego tower."""
    d = int(model["dim"])
    parents, edges = tower_edges([int(f) for f in model["fanouts"]])
    mix = 3 * d  # alpha * h0 + (1 - alpha) * mixed
    if model["gnn_type"] == "lightgcn":
        per_parent, per_edge = d + mix, d  # one divide per dim; sums
    elif model["gnn_type"] == "gat":
        # W h_self and its score; per edge W h_nbr, its score, softmax and
        # the weighted sum; relu on the parent
        per_parent = 2 * d * d + 2 * d + d + mix
        per_edge = 2 * d * d + 2 * d + 5 + 2 * d
    else:
        raise ValueError(f"no count for gnn_type {model['gnn_type']!r}")
    return parents * per_parent + edges * per_edge


def loss_flops(pairs: int, dim: int) -> int:
    """In-batch softmax: the (P, P) score matrix, its logsumexp and mean."""
    return 2 * pairs * pairs * dim + 4 * pairs * pairs


def train_step_flops(model: Dict, pairs: int, towers: int) -> int:
    """Forward plus backward FLOPs of one step that encodes ``towers`` ego
    trees and scores ``pairs`` pairs."""
    fwd = towers * tower_flops(model) + loss_flops(pairs, int(model["dim"]))
    return 3 * fwd


def window_pairs_cost(walks: int, walk_len: int, npos: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of the window-pair kernel: read the (walks, walk_len)
    int32 paths, write two (walks, npos) int32 id tiles; two PAD compares
    and a select per output pair."""
    flops = 3 * walks * npos
    nbytes = 4 * walks * walk_len + 2 * 4 * walks * npos
    return flops, nbytes


def topk_scan_cost(queries: int, items: int, dim: int, exclude: int,
                   k: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one exact top-k scan: the (Q, I) inner products,
    one read of the f32 item table, the queries and exclusion lists in,
    the (Q, k) scores and ids out."""
    flops = 2 * queries * items * dim
    nbytes = 4 * (items * dim + queries * dim + queries * exclude
                  + 2 * queries * k)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
