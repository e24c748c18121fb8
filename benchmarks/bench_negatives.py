"""Paper Table 6 (RQ4): in-batch vs random negative sampling.

The paper reports ~4x faster training at equal recall for in-batch
negatives. Random negatives cost extra data input (negative ids + their
side info + their ego graphs when a GNN is used) — exactly the traffic the
engine's request counters expose.
"""
from __future__ import annotations

import time

from benchmarks.common import dataset, emit, fmt_recall, trainer


def run(quick: bool = True) -> None:
    ds = dataset("toy" if quick else "rec15")
    steps = 100 if quick else 300
    rows = {}
    for mode in ("random", "inbatch"):
        tr = trainer(ds, gnn_type="lightgcn", steps=steps, neg_mode=mode)
        t0 = time.perf_counter()
        res = tr.train()
        dt = time.perf_counter() - t0
        ev = res.eval_history[-1]
        rows[mode] = dt
        reqs = tr.engine.stats.neighbor_requests
        emit(f"negatives/{mode}", dt / steps * 1e6,
             f"{fmt_recall(ev)} engine_requests={reqs}")
    emit("negatives/speedup", 0.0,
         f"inbatch_is_{rows['random'] / rows['inbatch']:.2f}x_faster")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
