"""Readings that set a cell's correctness limits, at the cell's own size.

    python3 benchmarks/chip/control.py --workload <name> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--out file.jsonl]

One process, one set-up, then per seed: the program's sound readings (the
same checked steps, or the same query batches, as a benchmark run, against
the float32 reference at ``highest`` matmul precision) and, on the control
seeds, the controls' readings: the reference put in the program's place at
``high`` matmul precision (three bfloat16 passes, the step below the
configurations' ``highest``), at ``default`` (one pass; training only: the
program's own path where the precision is left to JAX), and in bfloat16.
Training cells also read a planted fault, the reference stepping on half
of each batch's pairs with the mean over the rest. (A step that returns
its state unchanged reads 1 on ``change_norm`` by that number's
definition, and needs no run.) The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def _controls():
    import jax.numpy as jnp

    return {"control_high": (jnp.float32, "high"),
            "control_default": (jnp.float32, "default"),
            "control_bf16": (jnp.bfloat16, "default")}


def half_batch(b):
    n = len(b["src_sel"]) // 2
    return {**b, "src_sel": b["src_sel"][:n], "dst_sel": b["dst_sel"][:n]}


def training(ctx, seeds, control_seeds, emit):
    tr = harness.load_module(HERE / "entries" / "train.py", "bench_train")
    st = tr.build(ctx, tr.seeds(seeds[0])[0])
    try:
        _training_seeds(ctx, st, seeds, control_seeds, emit)
    finally:
        st["trainer"].close()


def _training_seeds(ctx, st, seeds, control_seeds, emit):
    import reference as ref

    controls = _controls()
    tr = harness.load_module(HERE / "entries" / "train.py", "bench_train")
    trainer, fused = st["trainer"], st["fused"]
    for seed in seeds:
        s_trainer, s_weights = tr.seeds(seed)
        trainer.cfg.seed = s_trainer
        p0 = tr.weights(ctx, trainer, s_weights, st["num_nodes"])
        first, prog, batches = tr.checked_steps(ctx, trainer, p0, fused)
        del first
        emit(seed, "program", tr.reference_checks(ctx, p0, prog, batches,
                                                  st["arrays"]))
        if seed in control_seeds:
            for kind, control in controls.items():
                emit(seed, kind, tr.reference_checks(
                    ctx, p0, prog, batches, st["arrays"], control))
            want = ref.train_steps(p0, ctx["config"]["model"],
                                   ctx["config"]["trainer"], batches)
            half = ref.train_steps(p0, ctx["config"]["model"],
                                   ctx["config"]["trainer"],
                                   [half_batch(b) for b in batches])
            emit(seed, "fault_half_batch", ref.compare_training(half, want))
        del p0


def retrieval(ctx, seeds, control_seeds, emit):
    import jax
    import jax.numpy as jnp

    from repro.retrieval import chunked_topk

    import reference as ref

    rt = harness.load_module(HERE / "entries" / "retrieve.py", "bench_ret")
    ub = harness.load_module(HERE / "data" / "ub.py", "bench_ub")
    cfg, trf = ctx["config"], ctx["traffic"]
    arrays, meta = ub.load_or_generate(cfg["deployment"], ctx["cache"],
                                       ctx["log"])
    I = int(meta["num_items"])
    d, Q, k = int(cfg["model"]["dim"]), int(trf["query_chunk"]), int(trf["k"])
    n = int(trf["check_queries"])
    for seed in seeds:
        s_corpus, _, items, queries, exclude = rt.inputs(
            seed, trf, d, Q, ub, arrays, meta)
        got = chunked_topk(queries, items, k, exclude=exclude,
                           item_chunk=int(trf["item_chunk"]),
                           query_chunk=Q)[1]
        del items
        items_dev, _ = rt.make_corpus(jax.random.PRNGKey(s_corpus), I, Q, d,
                                      trf["corpus"])
        rows = np.random.default_rng(seed).choice(Q, min(n, Q), replace=False)
        emit(seed, "program", ref.topk_reference(
            queries[rows], items_dev, exclude[rows], k, got[rows]))
        if seed in control_seeds:
            _, ids = ref.exact_topk(queries[rows], items_dev, exclude[rows], k,
                                    dtype=jnp.bfloat16)
            emit(seed, "control_bf16", ref.topk_reference(
                queries[rows], items_dev, exclude[rows], k, ids))
            _, ids = ref.exact_topk(queries[rows], items_dev, exclude[rows], k,
                                    precision="high")
            emit(seed, "control_high", ref.topk_reference(
                queries[rows], items_dev, exclude[rows], k, ids))
        del items_dev


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    root = HERE.parents[1]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = harness.resolve(bench, root, args.workload)
    harness.configure_jax(cell["config"], harness.CACHE)
    devices = harness.require_chip(int(cell["workload"]["chips"]))
    sys.path.insert(0, str(root / "src"))
    ctx = {"name": args.workload, "config": cell["config"],
           "traffic": cell["traffic"], "cache": harness.CACHE,
           "log": harness.log, "devices": devices, "t_start": t_start}

    def emit(seed, kind, readings):
        row = {"workload": args.workload, "seed": seed, "kind": kind,
               "readings": {k: float(v) for k, v in readings.items()}}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")

    if cell["traffic"]["entry"] == "train":
        training(ctx, seeds, controls, emit)
    else:
        retrieval(ctx, seeds, controls, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
