"""Smoke run of the recommender's main path on one TPU chip.

    python3 chip_smoke.py [--dataset ub] [--steps 40] [--seed 0]

Runs, in one process and through the entry points a user calls, LightGCN
(2 layers, fanouts (4, 3), embedding width 64 as in the upstream Graph4Rec
configs) on the ``ub`` synthetic multi-behaviour graph (28,000 nodes, four
behaviours), with weights drawn from ``--seed``:

  (a) host sampling, in-process graph engine, sparse row updates;
  (b) (a) again with the multi-process graph engine — its numpy-only
      workers are spawned by this process, which holds the chip;
  (c) the fused on-device sampler inlined into the grad step;
  (d) full-graph inference over every node;
  (e) recall evaluation with ``method="device"`` and ``method="ivf"``.

Every setting is explicit (``auto_backend=False``), so each phase runs the
path it names. Each check prints one line; a failed check exits non-zero
at once. Results are checked against references: training and inference
against the same computation on the host's CPU backend, top-k retrieval
(device scan, and IVF probing every cell) against the numpy oracle. The
last line is one JSON object naming the device. Without a TPU the script
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable from a bare checkout: nothing is installed
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

DIM = 64
TOP_K = 100
ORACLE_QUERIES = 64


def _require_tpu():
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {backend!r}")
    return jax.devices()[0]


def _has_kernel(compiled) -> bool:
    """Whether a compiled program carries a Mosaic (Pallas) kernel."""
    return "tpu_custom_call" in compiled.as_text()


def check(name: str, ok: bool, detail: str) -> None:
    print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {name}")


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (compile time is set-up, not step time)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def build(spec_name: str, seed: int):
    from repro.core import Graph4RecConfig, HeteroGNNConfig
    from repro.embedding import EmbeddingConfig
    from repro.graph import SPECS, DistributedGraphEngine, generate
    from repro.sampling import EgoConfig, PairConfig, PipelineConfig
    from repro.walk import WalkConfig

    ds = generate(SPECS[spec_name], seed=seed)
    engine = DistributedGraphEngine(ds.graph, num_partitions=4)
    rels = ("u2click2i", "i2click2u")
    metapaths = ["u2click2i - i2click2u"] + [
        f"u2{b}2i - i2{b}2u" for b in ("buy",) if f"u2{b}2i" in ds.graph.relations
    ]
    model_cfg = Graph4RecConfig(
        embedding=EmbeddingConfig(num_nodes=ds.graph.num_nodes, dim=DIM),
        gnn=HeteroGNNConfig(
            gnn_type="lightgcn", num_relations=2, num_layers=2, dim=DIM
        ),
        fanouts=(4, 3),
        relations=rels,
        loss="inbatch_softmax",
    )
    pipe_cfg = PipelineConfig(
        walk=WalkConfig(metapaths=metapaths, walk_len=6),
        pair=PairConfig(win_size=2, neg_mode="inbatch"),
        ego=EgoConfig(relations=list(rels), fanouts=[4, 3]),
        order="walk_ego_pair",
        batch_pairs=256,
    )
    return ds, engine, model_cfg, pipe_cfg


def train_phase(name, ds, engine, model_cfg, pipe_cfg, clock, steps, seed, **kw):
    """Two identical short training runs on one trainer: the first pays
    set-up and compilation, the second times the steps. Returns (result of
    the first run, whether the compiled fused step holds a kernel, or None
    for host sampling)."""
    import jax
    import numpy as np

    from repro.core import model as model_lib
    from repro.sampling.pipeline import make_train_sampler
    from repro.train import Graph4RecTrainer, TrainerConfig

    cfg = TrainerConfig(
        num_steps=steps, sparse_lr=1.0, log_every=0, seed=seed,
        auto_backend=False, eval_at_end=False, sparse_min_rows=0, **kw,
    )
    t0, c0 = time.perf_counter(), clock.compile_s
    fused_kernel = None
    with Graph4RecTrainer(ds, engine, model_cfg, pipe_cfg, cfg) as tr:
        res = tr.train()
        setup_s, compile_s = time.perf_counter() - t0, clock.compile_s - c0
        again = tr.train()
        p0 = tr.init_params()  # what train() started from
        if cfg.sampling_backend == "fused":
            check(f"{name} plan", res.plan["sampling"] == "fused",
                  f"sampling={res.plan['sampling']} ({res.plan['reason']})")
            key0 = next(tr._fused_batch_iter())[0]
            sampler, tables = tr._fused_sampler, tr._fused_sampler.tables()
            batch0 = jax.jit(lambda k, t: sampler.with_tables(t).sample(k))(
                key0, tables
            )
            compiled = tr._fused_step.lower(
                p0, tr.opt.init(p0), key0, tables
            ).compile()
            fused_kernel = _has_kernel(compiled)
        else:
            pipe = make_train_sampler(tr.engine, pipe_cfg, backend="host", seed=seed)
            batch0 = jax.device_put(model_lib.host_batch(
                ds.graph, next(iter(pipe.batches(1))), model_cfg
            ))
    losses = np.asarray(res.losses, np.float64)
    check(
        f"{name} losses",
        len(losses) == steps and bool(np.isfinite(losses).all()),
        f"{len(losses)} steps, first {losses[0]!r} last {losses[-1]!r}",
    )
    # Per-batch losses move by less than their batch-to-batch spread in a
    # few dozen steps at this init scale; re-scoring the first batch with
    # the trained weights compares like with like.
    loss = jax.jit(lambda p, b: model_lib.loss_fn(p, model_cfg, b))
    before, after = float(loss(p0, batch0)), float(loss(res.params, batch0))
    check(
        f"{name} learns",
        abs(before - losses[0]) <= 1e-5 * abs(before) and after < before,
        f"first batch: step-0 loss {losses[0]!r}, re-scored {before!r} "
        f"before and {after!r} after training",
    )
    check(f"{name} rerun", np.array_equal(res.losses, again.losses),
          "second run's losses bitwise equal (same seed)")
    print(
        f"[time] {name}: first run {setup_s:.3f}s incl. set-up and "
        f"{compile_s:.3f}s compile; second run {again.wall_time_s:.3f}s = "
        f"{again.wall_time_s / steps * 1e3:.3f} ms/step, "
        f"{again.pairs_seen / again.wall_time_s:.1f} pairs/s",
        flush=True,
    )
    return res, fused_kernel


def loss_reference(ds, engine, model_cfg, pipe_cfg, params, seed):
    """One training batch's loss on the chip vs the same program on the
    host CPU backend (plain f32)."""
    import jax
    import numpy as np

    from repro.core import model as model_lib
    from repro.sampling.pipeline import make_train_sampler

    pipe = make_train_sampler(engine, pipe_cfg, backend="host", seed=seed)
    batch = next(iter(pipe.batches(1)))
    host = model_lib.host_batch(ds.graph, batch, model_cfg)
    fn = jax.jit(lambda p, b: model_lib.loss_fn(p, model_cfg, b))
    cpu = jax.devices("cpu")[0]
    got = float(fn(params, jax.device_put(host)))
    with jax.default_matmul_precision("highest"):
        hi = float(jax.jit(lambda p, b: model_lib.loss_fn(p, model_cfg, b))(
            params, jax.device_put(host)))
    want = float(fn(jax.device_put(params, cpu), jax.device_put(host, cpu)))
    check(
        "loss vs CPU reference",
        bool(np.isfinite(got)) and abs(hi - want) <= 1e-5 * abs(want),
        f"tpu {got!r} (highest precision {hi!r}) vs cpu {want!r}",
    )


def inference_phase(ds, engine, model_cfg, params, clock, seed):
    import jax
    import numpy as np

    from repro.core import model as model_lib
    from repro.infer import embed_all_nodes
    from repro.sampling.ego import EgoConfig, sample_ego_batch

    N = ds.graph.num_nodes
    batch = 1024
    c0, t0 = clock.compile_s, time.perf_counter()
    emb = embed_all_nodes(params, model_cfg, engine, ds.graph,
                          batch_size=batch, seed=seed)
    wall = time.perf_counter() - t0
    compile_s = clock.compile_s - c0
    check("infer shape/finite", emb.shape == (N, DIM)
          and bool(np.isfinite(emb).all()), f"{emb.shape}")
    # reference: the first chunk's encoder on the host CPU backend, fed the
    # same ego sample (embed_all_nodes draws chunk 0 first from this seed)
    ids = np.arange(min(batch, N), dtype=np.int64)
    ego = sample_ego_batch(
        np.random.default_rng(seed), engine, ids,
        EgoConfig(relations=list(model_cfg.relations),
                  fanouts=list(model_cfg.fanouts)),
    )
    levels, slots = model_lib._ego_arrays_np(ds.graph, ego, model_cfg)
    cpu = jax.devices("cpu")[0]
    ref = np.asarray(jax.jit(
        lambda p, lv: model_lib.encode_ego(p, model_cfg, lv, slots)
    )(jax.device_put(params, cpu), jax.device_put(levels, cpu)))
    err = float(np.max(np.abs(ref - emb[: len(ids)])))
    scale = float(np.max(np.abs(ref)))
    check("infer vs CPU reference", err <= 1e-5 * max(scale, 1e-6),
          f"max |diff| {err!r} over {len(ids)} nodes (max |ref| {scale!r})")
    print(f"[time] infer: {wall:.3f}s wall of which compile "
          f"{compile_s:.3f}s, {N / max(wall - compile_s, 1e-9):.1f} nodes/s "
          "after compile", flush=True)
    return emb


def retrieval_phase(ds, emb, clock):
    import jax
    import numpy as np

    from repro.core.recall import evaluate_recall
    from repro.retrieval import (
        IVFConfig, IVFIndex, brute_force_topk, chunked_topk, pad_id_rows,
    )
    from repro.retrieval.ivf import _ivf_shortlist

    U, I = ds.num_users, ds.num_items
    train_pairs = np.concatenate(
        [np.stack([u, i], 1) for (u, i) in ds.train_edges.values()], axis=0
    )
    for method in ("device", "ivf"):
        c0, t0 = clock.compile_s, time.perf_counter()
        m = evaluate_recall(emb[:U], emb[U : U + I], train_pairs, ds.val_pairs,
                            top_k=TOP_K, method=method)
        wall = time.perf_counter() - t0
        vals = np.array(list(m.values()), np.float64)
        check(f"recall {method}", bool(np.isfinite(vals).all())
              and bool(((vals >= 0) & (vals <= 1)).all()),
              ", ".join(f"{k}={v!r}" for k, v in sorted(m.items())))
        print(f"[time] recall {method}: {wall:.3f}s wall of which compile "
              f"{clock.compile_s - c0:.3f}s", flush=True)

    def unit(x):
        return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)

    ue, ie = unit(emb[:U]), unit(emb[U : U + I])
    users = np.arange(min(ORACLE_QUERIES, U))
    hist = {}
    for u, i in train_pairs:
        if u < len(users):
            hist.setdefault(int(u), set()).add(int(i))
    ex = pad_id_rows([sorted(hist.get(int(u), ())) for u in users])
    q = ue[users]
    _, want = brute_force_topk(q, ie, TOP_K, exclude=ex)
    _, got = chunked_topk(q, ie, TOP_K, exclude=ex)
    bad = int((got != want).any(axis=1).sum())
    check("device top-k ids == numpy oracle", bad == 0,
          f"{bad}/{len(users)} queries differ (k={TOP_K})")
    idx = IVFIndex.build(ie, IVFConfig(nlist=64, seed=0))
    _, got = idx.search(q, TOP_K, exclude=ex, nprobe=idx.config.nlist)
    bad = int((got != want).any(axis=1).sum())
    check("ivf full-probe top-k ids == numpy oracle", bad == 0,
          f"{bad}/{len(users)} queries differ (nlist={idx.config.nlist}, "
          f"lpad={idx.lpad}, backend={idx._backend})")
    dev = idx._dev
    compiled = _ivf_shortlist.lower(
        jax.device_put(q), jax.device_put(ex), dev["centroids"], dev["codes"],
        dev["scales"], dev["order"], dev["offsets"],
        nprobe=idx.config.nprobe,
        shortlist=min(4 * TOP_K, idx.config.nprobe * idx.lpad), lpad=idx.lpad,
        backend=idx._backend,
    ).compile()
    check("ivf search program has tpu_custom_call", _has_kernel(compiled),
          f"backend={idx._backend}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dataset", default="ub")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from repro.obs.memory import device_memory_stats
    from repro.utils.compile_cache import enable_compile_cache

    dev = _require_tpu()
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"[info] device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    ds, engine, model_cfg, pipe_cfg = build(args.dataset, args.seed)
    print(f"[time] dataset {args.dataset} + engine: "
          f"{time.perf_counter() - t0:.3f}s, {ds.graph.num_nodes} nodes, "
          f"{len(ds.spec.behaviors)} behaviours", flush=True)

    common = dict(clock=clock, steps=args.steps, seed=args.seed)
    res_a, _ = train_phase("(a) host/inproc/sparse", ds, engine, model_cfg,
                           pipe_cfg, engine_backend="inproc",
                           sampling_backend="host", prefetch_batches=2,
                           **common)
    res_b, _ = train_phase("(b) host/mp/sparse", ds, engine, model_cfg,
                           pipe_cfg, engine_backend="mp",
                           num_engine_workers=2, sampling_backend="host",
                           prefetch_batches=2, **common)
    check("(b) mp losses == inproc losses",
          np.array_equal(res_a.losses, res_b.losses),
          "bitwise, same seed (engine determinism contract)")
    _, fused_kernel = train_phase("(c) fused", ds, engine, model_cfg,
                                  pipe_cfg, sampling_backend="fused", **common)
    check("(c) fused step has tpu_custom_call", bool(fused_kernel),
          "window-pair kernel in the compiled step")
    params = res_a.params
    loss_reference(ds, engine, model_cfg, pipe_cfg, params, args.seed)
    emb = inference_phase(ds, engine, model_cfg, params, clock, args.seed)
    retrieval_phase(ds, emb, clock)

    stats = device_memory_stats()[str(dev)]
    check("peak_bytes_in_use", stats.get("peak_bytes_in_use", 0) > 0,
          f"{stats.get('peak_bytes_in_use')} of {stats.get('bytes_limit')} "
          "bytes")
    print(f"[info] compile: {clock.compile_s:.3f}s total, persistent cache "
          f"{clock.hits} hits / {clock.misses} misses", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
