"""Quickstart: train a heterogeneous LightGCN recommender in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py

Builds a synthetic multi-behavior user-item graph, configures the paper's
five-stage pipeline (graph input -> walks -> ego graphs -> pairs -> GNN),
trains with in-batch negatives and reports ICF/UCF/U2I recall@100.
"""
from repro.core import Graph4RecConfig, HeteroGNNConfig
from repro.embedding import EmbeddingConfig
from repro.graph import DistributedGraphEngine, TOY, generate
from repro.sampling import EgoConfig, PairConfig, PipelineConfig
from repro.train import Graph4RecTrainer, TrainerConfig
from repro.utils.compile_cache import enable_compile_cache
from repro.walk import WalkConfig

enable_compile_cache()

# 1. graph input — synthetic RetailRocket-like multi-behavior graph
dataset = generate(TOY, seed=0)
engine = DistributedGraphEngine(dataset.graph, num_partitions=4)

# 2-5. pipeline + model configuration (each paper stage is one config knob)
model_cfg = Graph4RecConfig(
    embedding=EmbeddingConfig(num_nodes=dataset.graph.num_nodes, dim=32),
    gnn=HeteroGNNConfig(gnn_type="lightgcn", num_relations=2, num_layers=2, dim=32),
    fanouts=(4, 3),
    relations=("u2click2i", "i2click2u"),
    loss="inbatch_softmax",
)
pipe_cfg = PipelineConfig(
    walk=WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=6),
    pair=PairConfig(win_size=2),
    ego=EgoConfig(relations=["u2click2i", "i2click2u"], fanouts=[4, 3]),
    order="walk_ego_pair",  # the paper's O(L) fast ordering (RQ5)
    batch_pairs=256,
)

trainer = Graph4RecTrainer(
    dataset, engine, model_cfg, pipe_cfg,
    TrainerConfig(num_steps=150, sparse_lr=1.0, log_every=50),
)
result = trainer.train()
print("final loss:", round(result.losses[-1], 4))
print("recall@100:", {k: round(v, 4) for k, v in result.eval_history[-1].items()})
print(f"{result.pairs_seen} pairs in {result.wall_time_s:.1f}s "
      f"({result.pairs_seen / result.wall_time_s:.0f} pairs/s)")
