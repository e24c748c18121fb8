"""Operations and bytes the algorithms need, computed from shapes.

Only needed work is counted, never padding. An encoder's work is counted
on the ego towers a run really encoded, in the reference's layout: a
parent slot holds one block of ``fanouts[k]`` child slots per relation,
and which of those are live depends on the graph (a user has no
neighbours under an item relation; a node with no ``buy`` edge gets an
all-PAD ``buy`` block). PAD slots, and relation blocks with no live
child, are padding. Backward is counted as twice the forward. An
encoder's own work per (parent, relation) pair, per edge and per mix
comes from its modules, the ``harness.encoder`` of its configuration.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Sequence, Tuple

import numpy as np


def tower_work(levels: Sequence[np.ndarray], fanouts: Sequence[int],
               relations: int) -> Tuple[int, int, Dict[int, int]]:
    """Needed work of the ego towers ``levels`` (level k: (B, W_k) ids,
    PAD -1; level k + 1 holds ``relations`` blocks of ``fanouts[k]``
    children per level-k slot) over all layers: layer l collapses levels
    0..K-1-l into their parents, so level k is a parent in K - k layers.

    Returns (live (parent, relation) pairs, live child edges, live parents
    by the number of live relations they mix). A pair is live where the
    parent is live and has at least one live child under the relation.
    """
    live = [np.asarray(l) >= 0 for l in levels]
    K = len(fanouts)
    pairs = edges = 0
    mixes: Counter = Counter()
    for k, f in enumerate(fanouts):
        B, W = live[k].shape
        child = (live[k + 1].reshape(B, W, relations, f)
                 & live[k][..., None, None])
        per_rel = child.sum(-1)  # live children of each (parent, relation)
        n = (per_rel > 0).sum(-1)[live[k]]  # live relations of live parents
        pairs += (K - k) * int(n.sum())
        edges += (K - k) * int(per_rel.sum())
        for v, c in zip(*np.unique(n, return_counts=True)):
            mixes[int(v)] += (K - k) * int(c)
    return pairs, edges, dict(mixes)


def tower_flops(model: Dict, enc, levels: Sequence[np.ndarray]) -> int:
    """Needed forward FLOPs of the encoder over the ego towers ``levels``
    (all B of them): the layer module's work per live (parent, relation)
    pair and per live edge, the mix module's over each live parent's live
    relations, and Eq. 3's residual per live parent (alone where the
    parent has no live relation)."""
    d = int(model["dim"])
    pairs, edges, mixes = tower_work(
        levels, [int(f) for f in model["fanouts"]], len(model["relations"]))
    per_pair, per_edge = enc.layer.flops(d)
    residual = 3 * d  # alpha * h0 + (1 - alpha) * mixed
    return (pairs * per_pair + edges * per_edge
            + sum(c * (residual + (enc.mix.flops(d, n) if n else 0))
                  for n, c in mixes.items()))


def loss_flops(pairs: int, dim: int) -> int:
    """In-batch softmax: the (P, P) score matrix, its logsumexp and mean."""
    return 2 * pairs * pairs * dim + 4 * pairs * pairs


def train_step_flops(model: Dict, enc, pairs: int, steps) -> float:
    """Forward plus backward FLOPs of one step that scores ``pairs``
    pairs: the encoder's needed work on the towers of the checked
    ``steps`` (each a list of levels, a row per tower the step encodes),
    averaged over them, and the loss."""
    fwd = np.mean([tower_flops(model, enc, levels) for levels in steps])
    return 3 * (float(fwd) + loss_flops(pairs, int(model["dim"])))


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
