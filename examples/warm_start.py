"""Two-stage warm-start workflow (paper §3.6, RQ6).

    PYTHONPATH=src python examples/warm_start.py

Stage 1: pre-train sparse embeddings with metapath2vec (fast, ego-free).
Stage 2: inherit them into LightGCN training and compare against cold start.
"""
import time

from repro.embedding import save_table
from repro.graph import DistributedGraphEngine, TOY, generate
from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import trainer


def main() -> None:
    enable_compile_cache()
    ds = generate(TOY, seed=0)

    print("== stage 1: metapath2vec pre-training ==")
    walk_tr = trainer(ds, gnn_type=None, steps=200)
    t0 = time.perf_counter()
    walk_res = walk_tr.train()
    print(f"  {time.perf_counter() - t0:.1f}s,",
          {k: round(v, 4) for k, v in walk_res.eval_history[-1].items()})
    save_table("/tmp/mp2v.npz", {"node": walk_res.params["emb/node"]})

    print("== stage 2: LightGCN, cold vs warm ==")
    for warm in (False, True):
        tr = trainer(ds, gnn_type="lightgcn", steps=80)
        params = tr.init_params()
        if warm:
            params = dict(params)
            params["emb/node"] = walk_res.params["emb/node"]
        res = tr.train(params)
        print(f"  {'warm' if warm else 'cold'}:",
              {k: round(v, 4) for k, v in res.eval_history[-1].items()})


if __name__ == "__main__":
    main()
