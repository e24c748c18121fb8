"""Paper Table 5 (RQ3): the effect of side information.

Each model trained with and without side-info slots (slot 0 is correlated
with the planted clusters, as the paper's category/brand features are with
real item categories). Expectation (paper): +side-info improves recall.
"""
from __future__ import annotations

import time

from benchmarks.common import dataset, emit, fmt_recall, trainer

MODELS = [
    ("metapath2vec", dict(gnn_type=None)),
    ("graphsage-mean", dict(gnn_type="sage-mean")),
    ("lightgcn", dict(gnn_type="lightgcn")),
    ("gin", dict(gnn_type="gin")),
    ("gatne", dict(gnn_type="lightgcn", relation_agg="gatne")),
]


def run(quick: bool = True) -> None:
    ds = dataset("toy" if quick else "tmall")
    steps = 120 if quick else 400
    for name, kw in MODELS:
        for side in (False, True):
            tr = trainer(ds, steps=steps, side_info=side, **kw)
            t0 = time.perf_counter()
            res = tr.train()
            dt = time.perf_counter() - t0
            tag = f"sideinfo/{name}{'+side' if side else ''}"
            emit(tag, dt / steps * 1e6, fmt_recall(res.eval_history[-1]))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
