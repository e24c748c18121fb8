"""Ablation: the α residual against over-smoothing (paper §3.5, Eq. 3).

h_v = α·h⁰ + (1-α)·Σ_r φ_r h_{v,r}: α=0 is a vanilla GNN (prone to
over-smoothing as depth grows), α=1 degenerates to the walk-based embedding.
The paper adopts the PPR-flavored residual as its default; this ablation
shows the recall surface over α.
"""
from __future__ import annotations

import dataclasses
import time

from benchmarks.common import dataset, emit, fmt_recall, trainer


def run(quick: bool = True) -> None:
    ds = dataset("toy" if quick else "tmall")
    steps = 100 if quick else 300
    for alpha in (0.0, 0.15, 0.5, 1.0):
        tr = trainer(ds, gnn_type="lightgcn", steps=steps)
        tr.model_cfg = dataclasses.replace(
            tr.model_cfg,
            gnn=dataclasses.replace(tr.model_cfg.gnn, alpha=alpha),
        )
        # rebuild the jitted step with the new config
        tr._grad_step = __import__("jax").jit(tr._make_grad_step())
        t0 = time.perf_counter()
        res = tr.train()
        dt = time.perf_counter() - t0
        emit(f"alpha/{alpha}", dt / steps * 1e6, fmt_recall(res.eval_history[-1]))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
