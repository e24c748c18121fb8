"""Paper Table 7 (RQ5): sample-generation order — Walk,Pair,Ego vs
Walk,Ego,Pair.

Ego-first reduces ego samplings per path from O(wL) to O(L) at a small
diversity (recall) cost. We report wall-clock, the engine's neighbor-request
counter (the communication the paper optimizes), and recall.
"""
from __future__ import annotations

import time

from benchmarks.common import dataset, emit, fmt_recall, trainer


def run(quick: bool = True) -> None:
    ds = dataset("toy" if quick else "tmall")
    steps = 100 if quick else 300
    wall = {}
    for order, tag in (("walk_pair_ego", "pair-first"),
                       ("walk_ego_pair", "ego-first")):
        tr = trainer(ds, gnn_type="lightgcn", steps=steps, order=order)
        t0 = time.perf_counter()
        res = tr.train()
        dt = time.perf_counter() - t0
        wall[order] = dt
        pipe_ops = None
        emit(
            f"order/{tag}", dt / steps * 1e6,
            f"{fmt_recall(res.eval_history[-1])} "
            f"engine_requests={tr.engine.stats.neighbor_requests} "
            f"cross_partition={tr.engine.stats.cross_partition_requests}",
        )
    emit("order/speedup", 0.0,
         f"ego_first_is_{wall['walk_pair_ego'] / wall['walk_ego_pair']:.2f}x_faster")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
