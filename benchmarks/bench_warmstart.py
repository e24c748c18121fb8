"""Paper Fig. 3/4 (RQ6): pre-training + parameter warm start.

Pre-train sparse embeddings with the (fast) walk-based model, inherit them
into GNN training, and compare recall trajectories against a cold start at
equal GNN budget. Expectation (paper): warm start reaches better recall in
less training time.
"""
from __future__ import annotations

import time

from benchmarks.common import dataset, emit, fmt_recall, trainer


def run(quick: bool = True) -> None:
    ds = dataset("toy" if quick else "tmall")
    pre_steps = 150 if quick else 500
    gnn_steps = 60 if quick else 200

    # stage 1: metapath2vec pre-training (cheap pairs, no ego sampling)
    walk_tr = trainer(ds, gnn_type=None, steps=pre_steps)
    t0 = time.perf_counter()
    walk_res = walk_tr.train()
    pre_dt = time.perf_counter() - t0
    emit("warmstart/pretrain-metapath2vec", pre_dt / pre_steps * 1e6,
         fmt_recall(walk_res.eval_history[-1]))

    for warm in (False, True):
        tr = trainer(ds, gnn_type="lightgcn", steps=gnn_steps)
        params = tr.init_params()
        if warm:
            params = dict(params)
            params["emb/node"] = walk_res.params["emb/node"]
        t0 = time.perf_counter()
        res = tr.train(params)
        dt = time.perf_counter() - t0
        emit(f"warmstart/gnn-{'warm' if warm else 'cold'}",
             dt / gnn_steps * 1e6, fmt_recall(res.eval_history[-1]))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
