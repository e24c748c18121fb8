"""Multi-scenario recall evaluation: model x dataset x strategy sweep.

The serving-side counterpart of examples/train_recsys.py — reproduces the
shape of the paper's systematic comparison (§4.2) on the synthetic
datasets: for every (dataset, model) scenario it trains (or warm-loads a
checkpoint), runs full-graph inference (repro.infer), evaluates every
recall strategy through the device-side retrieval stack (repro.retrieval),
and writes a structured JSON report plus a rendered markdown table
(repro.launch.recall_report).

    PYTHONPATH=src python examples/eval_recsys.py \
        --datasets toy,retailrocket --models lightgcn,metapath2vec \
        --steps 200 --method device --report /tmp/recall.json \
        --markdown /tmp/recall.md

``--method ivf`` switches retrieval to the coarse-partition index
(million-item serving mode); ``--load-embeddings``/``--export-embeddings``
skip or persist the inference stage through train/checkpoint.py shards.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import Graph4RecConfig, HeteroGNNConfig
from repro.core.recall import evaluate_recall
from repro.embedding import EmbeddingConfig
from repro.graph import DistributedGraphEngine, SPECS, generate
from repro.infer import embed_all_nodes, export_embeddings, load_embeddings
from repro.retrieval import IVFConfig
from repro.sampling import EgoConfig, PairConfig, PipelineConfig
from repro.train import Graph4RecTrainer, TrainerConfig
from repro.utils.compile_cache import enable_compile_cache
from repro.walk import WalkConfig

WALK_MODELS = ("deepwalk", "metapath2vec")
RELS = ("u2click2i", "i2click2u")


def build_trainer(ds, model: str, steps: int, dim: int, seed: int,
                  engine_backend: str, engine_workers: int,
                  telemetry=None) -> Graph4RecTrainer:
    walk_based = model in WALK_MODELS
    mc = Graph4RecConfig(
        embedding=EmbeddingConfig(num_nodes=ds.graph.num_nodes, dim=dim),
        gnn=None if walk_based else HeteroGNNConfig(
            gnn_type=model, num_relations=2, num_layers=2, dim=dim),
        fanouts=() if walk_based else (4, 3),
        relations=RELS,
    )
    pc = PipelineConfig(
        walk=WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=6),
        pair=PairConfig(win_size=2),
        ego=None if walk_based else EgoConfig(relations=list(RELS), fanouts=[4, 3]),
        batch_pairs=256,
    )
    engine = (
        ds.graph if engine_backend == "mp"
        else DistributedGraphEngine(ds.graph, num_partitions=4)
    )
    return Graph4RecTrainer(
        ds, engine, mc, pc,
        TrainerConfig(num_steps=steps, log_every=0, sparse_lr=1.0, seed=seed,
                      eval_at_end=False, engine_backend=engine_backend,
                      num_engine_workers=engine_workers,
                      telemetry=telemetry),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", default="toy",
                    help=f"comma list from {sorted(SPECS)}")
    ap.add_argument("--models", default="lightgcn,metapath2vec",
                    help="comma list of zoo GNNs and/or walk models")
    ap.add_argument("--strategies", default="icf,ucf,u2i")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=100)
    ap.add_argument("--top-n", type=int, default=20)
    ap.add_argument("--method", default="device",
                    choices=["device", "ivf", "bruteforce"],
                    help="retrieval implementation (see core/recall.py)")
    ap.add_argument("--ivf-nlist", type=int, default=64)
    ap.add_argument("--ivf-nprobe", type=int, default=8)
    ap.add_argument("--split", default="test", choices=["val", "test"])
    ap.add_argument("--engine-backend", default="inproc", choices=["inproc", "mp"])
    ap.add_argument("--engine-workers", type=int, default=2)
    ap.add_argument("--export-embeddings", default=None, metavar="PATH",
                    help="save each scenario's (num_nodes, dim) matrix as "
                         "sharded npz: PATH.<dataset>.<model>.npz")
    ap.add_argument("--load-embeddings", default=None, metavar="PATH",
                    help="skip training+inference; evaluate a matrix saved "
                         "by --export-embeddings (single scenario only)")
    ap.add_argument("--trace", default=None, metavar="OUT.JSON",
                    help="enable the unified telemetry layer (repro.obs) "
                         "across every scenario — training, inference, and "
                         "retrieval searches — and write one Perfetto-"
                         "loadable Chrome trace here at the end")
    ap.add_argument("--report", default=None, help="write JSON results here")
    ap.add_argument("--markdown", default=None, help="write rendered table here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    datasets = args.datasets.split(",")
    models = args.models.split(",")
    strategies = tuple(args.strategies.split(","))
    ivf = IVFConfig(nlist=args.ivf_nlist, nprobe=args.ivf_nprobe, seed=args.seed)
    telemetry = None
    if args.trace:
        from repro.obs import Telemetry

        telemetry = Telemetry()
    results = []
    for ds_name in datasets:
        ds = generate(SPECS[ds_name], seed=args.seed)
        train_pairs = np.concatenate(
            [np.stack([u, i], 1) for (u, i) in ds.train_edges.values()], axis=0
        )
        eval_pairs = ds.test_pairs if args.split == "test" else ds.val_pairs
        for model in models:
            train_s = 0.0
            if args.load_embeddings:
                t0 = time.perf_counter()
                emb = load_embeddings(args.load_embeddings)
                embed_s = time.perf_counter() - t0
            else:
                trainer = build_trainer(
                    ds, model, args.steps, args.dim, args.seed,
                    args.engine_backend, args.engine_workers,
                    telemetry=telemetry,
                )
                with trainer:
                    t0 = time.perf_counter()
                    res = trainer.train()
                    train_s = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    emb = embed_all_nodes(
                        res.params, trainer.model_cfg, trainer.engine, ds.graph,
                        seed=args.seed,
                    )
                    embed_s = time.perf_counter() - t0
            if args.export_embeddings:
                path = export_embeddings(
                    f"{args.export_embeddings}.{ds_name}.{model}", emb,
                    num_shards=4,
                )
                print(f"exported {ds_name}/{model} embeddings -> {path}")
            t0 = time.perf_counter()
            metrics = evaluate_recall(
                emb[: ds.num_users],
                emb[ds.num_users : ds.num_users + ds.num_items],
                train_pairs, eval_pairs,
                top_k=args.top_k, top_n=args.top_n, strategies=strategies,
                method=args.method, ivf=ivf, telemetry=telemetry,
            )
            eval_s = time.perf_counter() - t0
            rec = {
                "dataset": ds_name, "model": model, "method": args.method,
                "top_k": args.top_k, "num_users": ds.num_users,
                "num_items": ds.num_items, "metrics": metrics,
                "train_s": round(train_s, 3), "embed_s": round(embed_s, 3),
                "eval_s": round(eval_s, 3),
            }
            results.append(rec)
            shown = {k: round(v, 4) for k, v in metrics.items() if "_" not in k}
            print(f"{ds_name}/{model} [{args.method}] {shown} "
                  f"(train {train_s:.1f}s, embed {embed_s:.1f}s, "
                  f"eval {eval_s:.1f}s)")

    if telemetry is not None:
        print(telemetry.text_summary())
        print("trace ->", telemetry.write_trace(args.trace),
              "(open in https://ui.perfetto.dev)")
    payload = {"split": args.split, "seed": args.seed, "results": results}
    if args.report:
        with open(args.report, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print("report ->", args.report)
    from repro.launch.recall_report import render_recall_report

    table = render_recall_report(results)
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(table + "\n")
        print("markdown ->", args.markdown)
    else:
        print()
        print(table)


if __name__ == "__main__":
    main()
