"""Paper Tables 3/4 (RQ1/RQ2): recall of the model zoo under one pipeline.

Walk-based (DeepWalk ~ homogeneous walk, metapath2vec ~ heterogeneous walk)
vs the GNN zoo (GraphSAGE mean/sum, LightGCN, GAT, GIN, NGCF, GATNE), all
trained by the same five-stage pipeline on the synthetic dataset.
"""
from __future__ import annotations

import time

from benchmarks.common import dataset, emit, fmt_recall, trainer

ZOO = [
    ("deepwalk(walk)", dict(gnn_type=None)),
    ("metapath2vec(walk)", dict(gnn_type=None)),
    ("graphsage-mean", dict(gnn_type="sage-mean")),
    ("graphsage-sum", dict(gnn_type="sage-sum")),
    ("lightgcn", dict(gnn_type="lightgcn")),
    ("gat", dict(gnn_type="gat")),
    ("gin", dict(gnn_type="gin")),
    ("ngcf", dict(gnn_type="ngcf")),
    ("gatne", dict(gnn_type="lightgcn", relation_agg="gatne")),
]


def run(quick: bool = True) -> None:
    ds = dataset("toy" if quick else "retailrocket")
    steps = 120 if quick else 400
    for name, kw in ZOO:
        tr = trainer(ds, steps=steps, **kw)
        t0 = time.perf_counter()
        res = tr.train()
        dt = time.perf_counter() - t0
        ev = res.eval_history[-1]
        emit(f"zoo/{name}", dt / steps * 1e6, fmt_recall(ev))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
