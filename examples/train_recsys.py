"""End-to-end recsys training driver (the paper's main workflow).

    PYTHONPATH=src python examples/train_recsys.py \
        --dataset retailrocket --model lightgcn --steps 400 \
        --side-info --warm-start /tmp/mp2v.npz --save /tmp/model.npz

Supports every zoo model, both negative-sampling modes, both generation
orders, side information, warm start from a pre-trained embedding
checkpoint, and checkpoint save. ``--model metapath2vec`` / ``deepwalk``
select the walk-based (ego-skipping) configuration.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import Graph4RecConfig, HeteroGNNConfig
from repro.embedding import EmbeddingConfig, SlotSpec
from repro.graph import DistributedGraphEngine, SPECS, generate
from repro.sampling import EgoConfig, PairConfig, PipelineConfig
from repro.train import Graph4RecTrainer, TrainerConfig, checkpoint
from repro.utils.compile_cache import enable_compile_cache
from repro.walk import WalkConfig

WALK_MODELS = ("deepwalk", "metapath2vec")
GNN_MODELS = ("lightgcn", "sage-mean", "sage-sum", "gat", "gin", "ngcf", "gatne")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="toy", choices=list(SPECS))
    ap.add_argument("--model", default="lightgcn",
                    choices=WALK_MODELS + GNN_MODELS)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--batch-pairs", type=int, default=256)
    ap.add_argument("--neg-mode", default="inbatch", choices=["inbatch", "random"])
    ap.add_argument("--order", default="walk_ego_pair",
                    choices=["walk_ego_pair", "walk_pair_ego"])
    ap.add_argument("--side-info", action="store_true")
    ap.add_argument("--partitions", type=int, default=4,
                    help="graph engine partitions (simulated servers)")
    ap.add_argument("--engine-backend", default="inproc", choices=["inproc", "mp"],
                    help="'mp' serves partitions from shared-memory worker "
                         "processes (graph/service) instead of in-process")
    ap.add_argument("--sampling-backend", default="host",
                    choices=["host", "fused", "auto"],
                    help="'fused' runs walk->pair->ego as one jitted device "
                         "program when the graph fits the padded-adjacency "
                         "budget (falls back to 'host' otherwise); 'auto' "
                         "lets start-of-run calibration choose")
    ap.add_argument("--prefetch-batches", type=int, default=None,
                    help="prefetch queue depth; 0 = serial loop; unset = let "
                         "the calibrated backend plan decide "
                         "(docs/throughput.md)")
    ap.add_argument("--no-auto-backend", action="store_true",
                    help="skip start-of-run calibration; use the legacy "
                         "fixed prefetch depth unless --prefetch-batches")
    ap.add_argument("--attribution", action="store_true",
                    help="record per-step phase timings (sample/assemble/"
                         "h2d/dispatch/...) and print the breakdown after "
                         "training")
    ap.add_argument("--engine-workers", type=int, default=2,
                    help="worker processes for --engine-backend=mp")
    ap.add_argument("--engine-local-threshold", type=int, default=8192,
                    help="mp backend: rounds with at most this many total "
                         "nodes are served in-process over the client's own "
                         "shard views (0 = every round goes to a worker)")
    ap.add_argument("--trace", default=None, metavar="OUT.JSON",
                    help="enable the unified telemetry layer (repro.obs) and "
                         "write a Perfetto-loadable Chrome trace here after "
                         "training; also prints the metrics/span text "
                         "summary (docs/observability.md)")
    ap.add_argument("--health", action="store_true",
                    help="enable the run-health guardrails (repro.obs.health):"
                         " a watchdog thread that flight-records and fails "
                         "the run on stalls, NaN/diverging losses, and "
                         "silent graph workers (dumps land under flightrec/)")
    ap.add_argument("--stall-timeout", type=float, default=120.0,
                    help="--health: no completed step for this many seconds "
                         "-> flight-record dump + RunStalledError (size it "
                         "above the first step's compile time)")
    ap.add_argument("--warm-start", default=None, help="npz of pre-trained tables")
    ap.add_argument("--save", default=None)
    ap.add_argument("--eval-recall", default="device",
                    choices=["device", "ivf", "bruteforce"],
                    help="retrieval path for the final recall evaluation: "
                         "'device' = chunked streaming top-k over every "
                         "held-out user (exact, no subsampling), 'ivf' = "
                         "coarse-partition approximate search, 'bruteforce' "
                         "= the O(U*I) numpy oracle")
    ap.add_argument("--eval-max-users", type=int, default=0,
                    help="cap evaluated users (0 = all; the old behavior "
                         "silently subsampled to 256)")
    ap.add_argument("--export-embeddings", default=None, metavar="PATH",
                    help="after training, run full-graph inference "
                         "(repro.infer) and save the (num_nodes, dim) "
                         "matrix as sharded npz via train/checkpoint.py")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    ds = generate(SPECS[args.dataset], seed=args.seed)
    # mp backend: hand the trainer the bare graph — the GraphClient
    # partitions it straight into shared memory, so no in-process partition
    # copies are ever built alongside the worker shards
    engine = (
        ds.graph
        if args.engine_backend == "mp"
        else DistributedGraphEngine(ds.graph, num_partitions=args.partitions)
    )
    rels = ("u2click2i", "i2click2u")

    walk_based = args.model in WALK_MODELS
    # DeepWalk = homogeneous walk (single metapath over one relation pair);
    # metapath2vec adds the behavior-specific metapaths (paper §3.2).
    metapaths = ["u2click2i - i2click2u"]
    if args.model != "deepwalk":
        extra = [f"u2{b}2i - i2{b}2u" for b in ("buy",) if f"u2{b}2i" in ds.graph.relations]
        metapaths += extra

    gnn_type = {"gatne": "lightgcn"}.get(args.model, args.model)
    slots = (
        (SlotSpec("slot0", 64, 3), SlotSpec("slot1", 64, 3))
        if args.side_info else ()
    )
    model_cfg = Graph4RecConfig(
        embedding=EmbeddingConfig(num_nodes=ds.graph.num_nodes, dim=args.dim,
                                  slots=slots),
        gnn=None if walk_based else HeteroGNNConfig(
            gnn_type=gnn_type, num_relations=2, num_layers=2, dim=args.dim,
            relation_agg="gatne" if args.model == "gatne" else "uniform"),
        fanouts=() if walk_based else (4, 3),
        relations=rels,
        use_side_info=args.side_info,
        loss="inbatch_softmax" if args.neg_mode == "inbatch" else "neg_sampling",
    )
    pipe_cfg = PipelineConfig(
        walk=WalkConfig(metapaths=metapaths, walk_len=6),
        pair=PairConfig(win_size=2, neg_mode=args.neg_mode),
        ego=None if walk_based else EgoConfig(relations=list(rels), fanouts=[4, 3]),
        order=args.order, batch_pairs=args.batch_pairs,
    )
    telemetry = None
    if args.trace:
        from repro.obs import Telemetry

        telemetry = Telemetry()
    health = None
    if args.health:
        from repro.obs import HealthConfig

        health = HealthConfig(stall_timeout_s=args.stall_timeout)
    trainer = Graph4RecTrainer(
        ds, engine, model_cfg, pipe_cfg,
        TrainerConfig(num_steps=args.steps, sparse_lr=1.0, log_every=50,
                      seed=args.seed, engine_backend=args.engine_backend,
                      num_engine_workers=args.engine_workers,
                      num_engine_partitions=args.partitions,
                      engine_local_threshold=args.engine_local_threshold,
                      sampling_backend=args.sampling_backend,
                      prefetch_batches=args.prefetch_batches,
                      auto_backend=not args.no_auto_backend,
                      attribution=args.attribution,
                      eval_method=args.eval_recall,
                      eval_max_users=args.eval_max_users,
                      telemetry=telemetry,
                      health=health),
    )
    params = trainer.init_params()
    if args.warm_start:
        from repro.embedding import load_table, warm_start

        pre = load_table(args.warm_start)
        params = warm_start(dict(params), {f"emb/{k}" if not k.startswith("emb/")
                                           else k: v for k, v in pre.items()})
        print(f"warm-started from {args.warm_start}")

    with trainer:  # reaps mp engine workers on exit/exception
        result = trainer.train(params)
        # trainer.engine is the GraphClient when --engine-backend=mp; its
        # stats mirror the in-process engine's counters exactly
        eng = trainer.engine
        print("plan:", result.plan["reason"])
        if result.attribution:
            a = result.attribution
            print(f"attribution ({a['steps']} steps, "
                  f"{a['wall_us_per_step']:.0f}us/step, device residual "
                  f"{a['device_residual_s'] / a['wall_s']:.0%}):")
            for phase, entry in a["phases"].items():
                print(f"  {phase:<11} {entry['per_call_us']:>10.1f}us/call "
                      f"x{entry['count']:<6} "
                      f"frac_of_wall={entry.get('frac_of_wall', 0.0):.3f}")
        print("recall:", {k: round(v, 4) for k, v in result.eval_history[-1].items()})
        print(f"engine: {eng.stats.neighbor_requests} neighbor requests, "
              f"{eng.stats.cross_partition_requests} cross-partition")
        if args.engine_backend == "mp":
            agg = eng.aggregate_stats()
            print(f"workers: {agg['num_workers']} procs served "
                  f"{agg['neighbor_requests']} queries in {agg['batches']} "
                  f"request rounds ({agg['busy_s']:.2f}s busy, "
                  f"{agg['local_neighbor_requests']} answered in-process)")
        if telemetry is not None:
            print(telemetry.text_summary())
            print("trace ->", telemetry.write_trace(args.trace),
                  "(open in https://ui.perfetto.dev)")
    if args.save:
        print("saved", checkpoint.save(args.save, result.params))
    if args.export_embeddings:
        from repro.infer import embed_all_nodes, export_embeddings

        emb = embed_all_nodes(
            result.params, model_cfg, engine, ds.graph, seed=args.seed
        )
        path = export_embeddings(
            args.export_embeddings, emb, num_shards=4,
            meta={"dataset": np.bytes_(args.dataset), "model": np.bytes_(args.model)},
        )
        print(f"exported full-graph embeddings {emb.shape} -> {path}")


if __name__ == "__main__":
    main()
