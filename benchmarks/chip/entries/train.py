"""Training window: one ``Graph4RecTrainer.train()`` call over the UB graph.

Set-up builds the trainer once, with every setting pinned, hands it the
benchmark's own weights, and drives its first three steps through
``train()`` itself. A spy on the trainer's jitted step keeps what those
steps were fed (the device batch, or the fused step's key) and reads the
optimizer state after the first. A warm-up call gives the rate that sizes
the window, and the window is one more ``train()`` of the same trainer.

After the window (and after the device memory peak is read and the
trainer is freed) the plain reference repeats the three steps on the same
batches from the same weights; the compared numbers are the losses, the
first gradient's norm as the optimizer received it, and the parameters'
change after three steps, plus a count of sampled entries that do not lie
in the graph.
"""
from __future__ import annotations

import gc
import math
import sys
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import harness  # noqa: E402

CHECK_STEPS = 3


def deployment(arrays: Dict, meta: Dict):
    """The UB arrays as the program's graph and dataset objects."""
    from repro.graph import CSR, DatasetSpec, HeteroGraph, RecsysDataset

    U, I = int(meta["num_users"]), int(meta["num_items"])
    rels = sorted({k.rsplit(".", 1)[0] for k in arrays})
    graph = HeteroGraph(
        {"u": (0, U), "i": (U, I)},
        {r: CSR(indptr=arrays[f"{r}.indptr"], indices=arrays[f"{r}.indices"])
         for r in rels},
    )
    train_edges = {}
    for r in rels:
        if r.startswith("u2"):
            indptr = arrays[f"{r}.indptr"][: U + 1]
            u = np.repeat(np.arange(U, dtype=np.int32), np.diff(indptr))
            train_edges[r.split("2")[1]] = (u, arrays[f"{r}.indices"] - U)
    spec = DatasetSpec("ub", num_users=U, num_items=I, num_clusters=0,
                       behaviors=meta["behaviour_totals"], num_side_slots=0)
    empty = np.zeros((0, 2), np.int64)
    ds = RecsysDataset(spec=spec, graph=graph, train_edges=train_edges,
                       val_pairs=empty, test_pairs=empty,
                       user_clusters=np.zeros(0, np.int64),
                       item_clusters=np.zeros(0, np.int64))
    return graph, ds


def program_configs(cfg: Dict, num_nodes: int):
    from repro.core import Graph4RecConfig, HeteroGNNConfig
    from repro.embedding import EmbeddingConfig
    from repro.sampling import EgoConfig, PairConfig, PipelineConfig
    from repro.walk import WalkConfig

    m, s = cfg["model"], cfg["sampling"]
    model_cfg = Graph4RecConfig(
        embedding=EmbeddingConfig(num_nodes=num_nodes, dim=int(m["dim"]),
                                  dtype=m["dtype"]),
        gnn=HeteroGNNConfig(
            gnn_type=m["gnn_type"], num_relations=len(m["relations"]),
            num_layers=int(m["num_layers"]), dim=int(m["dim"]),
            alpha=float(m["alpha"]), relation_agg=m["relation_agg"]),
        fanouts=tuple(int(f) for f in m["fanouts"]),
        relations=tuple(m["relations"]),
        loss=m["loss"], temperature=float(m["temperature"]),
    )
    pipe_cfg = PipelineConfig(
        walk=WalkConfig(metapaths=list(s["metapaths"]),
                        walk_len=int(s["walk_len"])),
        pair=PairConfig(win_size=int(s["win_size"]), neg_mode="inbatch"),
        ego=EgoConfig(relations=list(m["relations"]),
                      fanouts=[int(f) for f in m["fanouts"]]),
        order=s["order"], batch_pairs=int(s["batch_pairs"]),
    )
    return model_cfg, pipe_cfg


def trainer_config(cfg: Dict, traffic: Dict, seed: int):
    from repro.train import TrainerConfig

    t = cfg["trainer"]
    return TrainerConfig(
        num_steps=CHECK_STEPS, sparse_lr=float(t["sparse_lr"]),
        dense_lr=float(t["dense_lr"]),
        adagrad_init_accum=float(t["adagrad_init_accum"]),
        unique_bucket=int(t["unique_bucket"]),
        sparse_updates=bool(t["sparse_updates"]),
        sparse_min_rows=int(t["sparse_min_rows"]),
        loss_fetch_every=int(t["loss_fetch_every"]),
        engine_local_threshold=int(t["engine_local_threshold"]),
        fused_budget_mb=float(t["fused_budget_mb"]),
        fused_max_degree=int(t["fused_max_degree"]),
        fused_oversample=float(t["fused_oversample"]),
        seed=seed, eval_at_end=False, log_every=0, auto_backend=False,
        sampling_backend=traffic["sampling_backend"],
        engine_backend=traffic.get("engine_backend", "inproc"),
        num_engine_workers=int(traffic.get("num_engine_workers", 0)),
        num_engine_partitions=int(traffic.get("num_partitions", 4)),
        prefetch_batches=int(traffic.get("prefetch_batches", 0)),
        fused_use_kernel_pairs=bool(traffic.get("fused_use_kernel_pairs",
                                                True)),
    )


def state_readings(p0, p1, state, lr: float, eps: float = 1e-8):
    """The first gradient's norm per leaf, as the optimizer received it.

    Row-wise AdaGrad moved each row by lr * g / (sqrt(accum) + eps), so the
    table's gradient is the step's change times (sqrt(accum) + eps) / lr
    (the accumulator alone, init + mean(g^2), rounds small rows away at
    float32); Adam keeps (1 - b1) g as its first moment.
    """
    import jax
    import jax.numpy as jnp

    from reference import ADAM_B1

    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(state)[0]:
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        name = next((k for k in keys if isinstance(k, str) and "/" in k),
                    None)
        if name is None:
            continue
        if name.startswith("emb/") and x.ndim == 2 and x.shape[1] == 1:
            g = (p0[name] - p1[name]) * (jnp.sqrt(x) + eps) / lr
            out[name] = jnp.sqrt(jnp.sum(g * g))
        elif "mu" in keys:
            out[name] = jnp.sqrt(jnp.sum(x * x)) / (1 - ADAM_B1)
    return out


class StepSpy:
    """Wraps the trainer's jitted step for the checked steps: keeps a
    device copy of each step's batch argument (the step donates it) and
    the gradient readings of the first step, from ``p0`` (the weights the
    first step started from, which the caller keeps) and the step's
    outputs."""

    def __init__(self, fn, p0, lr: float):
        import jax

        self.fn, self.p0, self.fed, self.state = fn, p0, [], None
        self._read = jax.jit(lambda a, b, s: state_readings(a, b, s, lr))

    def __call__(self, params, opt_state, batch, **kw):
        import jax

        self.fed.append(jax.tree.map(lambda x: x.copy(), batch))
        out = self.fn(params, opt_state, batch, **kw)
        if self.state is None:
            self.state = self._read(self.p0, out[0], out[1])
        return out


class StepClock:
    """Wraps the trainer's jitted step inside the window and keeps the host
    clock at each call, so that a run which loses time shows where."""

    def __init__(self, fn):
        self.fn, self.at = fn, []

    def __call__(self, *args, **kw):
        self.at.append(time.perf_counter())
        return self.fn(*args, **kw)

    def summary(self) -> str:
        gaps = np.diff(self.at)
        if not len(gaps):
            return "no step gaps"
        top = np.argsort(gaps)[::-1][:3]
        return (f"step gaps: median {np.median(gaps) * 1e3:.3f} ms, longest "
                + ", ".join(f"{gaps[i] * 1e3:.1f} ms at step {i + 1}"
                            for i in top))


def global_batch(fed, sampler) -> Dict:
    """A checked step's batch in the reference's layout: towers of global
    ids and the pair selectors into them."""
    import jax

    if sampler is not None:  # the fused step samples from its key
        b = jax.device_get(jax.jit(
            lambda k, t: sampler.with_tables(t).sample(k))(fed, sampler.tables()))
        return {"towers": [np.asarray(l) for l in b["shared"][0]],
                "src_sel": np.asarray(b["src_sel"]),
                "dst_sel": np.asarray(b["dst_sel"])}
    b = jax.device_get(fed)
    uniq = np.asarray(b["uniq"]["node"], np.int64)

    def glob(a):
        a = np.asarray(a, np.int64)
        return np.where(a >= 0, uniq[np.maximum(a, 0)], -1)

    src, dst = b["src"][0], b["dst"][0]
    P = np.asarray(src[0]).shape[0]
    towers = [np.concatenate([glob(s), glob(d)]) for s, d in zip(src, dst)]
    return {"towers": towers, "src_sel": np.arange(P),
            "dst_sel": P + np.arange(P)}


def build(ctx: Dict, seed_trainer: int) -> Dict:
    """Set-up up to a trainer ready for its first step: the encoder's
    reference modules, the graph (cached or generated), the engine, the
    program's configurations, the trainer with every setting pinned."""
    from repro.graph import DistributedGraphEngine
    from repro.train import Graph4RecTrainer

    cfg, traffic = ctx["config"], ctx["traffic"]
    enc = harness.encoder(cfg["model"], HERE)  # before the graph: fail fast
    ub = harness.load_module(HERE / "data" / "ub.py", "bench_ub")
    arrays, meta = ub.load_or_generate(cfg["deployment"], ctx["cache"],
                                       ctx["log"])
    graph, ds = deployment(arrays, meta)
    engine = (DistributedGraphEngine(
        graph, num_partitions=int(traffic.get("num_partitions", 4)))
        if traffic.get("engine_backend", "inproc") == "inproc" else graph)
    model_cfg, pipe_cfg = program_configs(cfg, graph.num_nodes)
    tcfg = trainer_config(cfg, traffic, seed_trainer)
    trainer = Graph4RecTrainer(ds, engine, model_cfg, pipe_cfg, tcfg)
    fused = traffic["sampling_backend"] == "fused"
    if fused and trainer._fused_sampler is None:
        raise harness.RunError("the fused sampler was not admitted")
    return {"arrays": arrays, "meta": meta, "num_nodes": graph.num_nodes,
            "trainer": trainer, "pipe_cfg": pipe_cfg, "fused": fused,
            "encoder": enc}


def weights(ctx: Dict, trainer, enc, seed_weights: int, num_nodes: int):
    """The benchmark's weights, checked against the program's layout."""
    import jax

    p0 = weights_only(ctx, enc, seed_weights, num_nodes)
    want = jax.eval_shape(trainer.init_params)
    got = jax.eval_shape(lambda: p0)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise harness.RunError("weights do not match the program's layout")
    return p0


def checked_steps(ctx: Dict, trainer, p0, fused: bool):
    """The first steps through ``train()`` itself, with the spy on the
    jitted step. Returns (result, program readings, batches)."""
    import jax
    import jax.numpy as jnp

    cfg = ctx["config"]
    attr = "_fused_step" if fused else "_sparse_step"
    spy = StepSpy(getattr(trainer, attr), p0,
                  float(cfg["trainer"]["sparse_lr"]))
    setattr(trainer, attr, spy)
    trainer.cfg.num_steps = CHECK_STEPS
    try:
        first = trainer.train(p0)
    finally:
        setattr(trainer, attr, spy.fn)
    change = jax.jit(lambda a, b: {
        k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})(
            first.params, p0)
    prog = {"losses": [float(x) for x in first.losses],
            "grad_norms": {k: float(v) for k, v in spy.state.items()},
            "change_norms": {k: float(v) for k, v in change.items()}}
    sampler = trainer._fused_sampler if fused else None
    batches = [global_batch(f, sampler) for f in spy.fed]
    return first, prog, batches


def reference_checks(ctx: Dict, enc, p0, prog: Dict, batches, arrays,
                     control=None) -> Dict:
    """The compared numbers: the program's readings against the reference
    run on the same batches from ``p0``. With ``control`` (a dtype and a
    matmul precision), the reference at that precision stands in the
    program's place."""
    import reference as ref

    cfg = ctx["config"]
    model = cfg["model"]
    want = ref.train_steps(p0, model, enc, cfg["trainer"], batches)
    if control is not None:
        prog = ref.train_steps(p0, model, enc, cfg["trainer"], batches,
                               *control)
    checks = ref.compare_training(prog, want)
    csr = {r: (arrays[f"{r}.indptr"], arrays[f"{r}.indices"])
           for r in {k.rsplit(".", 1)[0] for k in arrays}}
    walk_rels = sorted({r.strip() for mp in cfg["sampling"]["metapaths"]
                        for r in mp.split("-")})
    checks["batch_invalid"] = float(sum(
        ref.invalid_in_batch(b, csr, model, walk_rels) for b in batches))
    ctx["log"](f"[info] program {prog}")
    ctx["log"](f"[info] reference {want}")
    return checks


def kernel_patterns(sampler, pipe_cfg) -> Dict[str, str]:
    """The window-pair kernel's operation in the fused step: the Mosaic
    custom call from the (walks, walk_len) paths to two (walks, npos) id
    tiles."""
    if sampler is None:
        return {}
    W, L = sampler.num_walks, pipe_cfg.walk.walk_len
    P = len(sampler._positions)
    return {"window_pairs": (
        rf"= \(s32\[{W},{P}\]\S*, s32\[{W},{P}\]\S*\) "
        rf"custom-call\(s32\[{W},{L}\].*tpu_custom_call")}


def seeds(seed: int):
    """(trainer seed, weight seed) below 2^31 from any run seed."""
    return tuple(int(x) & 0x7FFFFFFF
                 for x in np.random.SeedSequence(seed).generate_state(2))


def run(ctx: Dict) -> Dict:
    seed_trainer, seed_weights = seeds(ctx["seed"])
    st = build(ctx, seed_trainer)
    try:
        return _run(ctx, st, seed_trainer, seed_weights)
    finally:
        if st["trainer"] is not None:
            st["trainer"].close()


def _run(ctx: Dict, st: Dict, seed_trainer: int, seed_weights: int) -> Dict:
    import jax

    traffic, log, clock = ctx["traffic"], ctx["log"], ctx["clock"]
    model = ctx["config"]["model"]
    trainer, pipe_cfg, fused = st["trainer"], st["pipe_cfg"], st["fused"]
    p0 = weights(ctx, trainer, st["encoder"], seed_weights, st["num_nodes"])
    first, prog, batches = checked_steps(ctx, trainer, p0, fused)
    sampler = trainer._fused_sampler if fused else None
    del p0
    params = first.params

    # warm-up: the rate that sizes the window
    trainer.cfg.num_steps = int(traffic["warmup_steps"])
    t0 = time.perf_counter()
    warm = trainer.train(params)
    rate = warm.pairs_seen / (time.perf_counter() - t0)
    params = warm.params
    span = min(ctx["seconds"], float(traffic["trace_seconds"])) \
        if ctx["trace"] else ctx["seconds"]
    steps = max(8, math.ceil(rate * span / pipe_cfg.batch_pairs))
    trainer.cfg.num_steps = steps
    if fused:  # the key split the window makes, at its shape
        list(jax.random.split(jax.random.PRNGKey(seed_trainer), steps))
    tel = None
    if ctx["trace"]:
        from repro.obs import Telemetry

        tel = Telemetry()
        trainer.cfg.attribution = True
        trainer.cfg.telemetry = tel
    harness.settle_heap(log)
    setup_s = time.perf_counter() - ctx["t_start"]
    setup_compile_s = clock.compile_s
    compiles0 = clock.compiles

    attr = "_fused_step" if fused else "_sparse_step"
    step_clock = StepClock(getattr(trainer, attr))
    setattr(trainer, attr, step_clock)
    with harness.Traced(ctx, ctx["trace"]) as traced, \
            harness.GcWatch() as gcw:
        t0 = time.perf_counter()
        res = trainer.train(params)
        wall = time.perf_counter() - t0
    setattr(trainer, attr, step_clock.fn)
    in_window = clock.compiles - compiles0
    log(f"[info] window {steps} steps, {wall:.3f}s, {in_window} compiles "
        f"in the window, {gcw.passes} gc passes (longest {gcw.worst:.4f}s); "
        f"{step_clock.summary()}; set-up {setup_s:.3f}s of which "
        f"{setup_compile_s:.3f}s compile ({clock.cache_hits} cache hits); "
        f"plan {res.plan}")
    pairs_per_s = res.pairs_seen / wall
    losses = np.asarray(res.losses, np.float64)
    failed = int((~np.isfinite(losses)).sum())
    mem = harness.memory_peak_bytes(ctx["devices"])

    layer = {}
    if ctx["trace"]:
        spans = [(n, t, d) for _, _, ss, _ in tel.tracer.threads()
                 for n, _, t, d, _ in ss]
        layer = {
            "trace": traced.reduce(modules=("jit_step",),
                                   ops=kernel_patterns(sampler, pipe_cfg),
                                   extra_spans=spans),
            "phases": res.attribution, "steps": steps, "wall_s": wall,
            "pairs_per_s": pairs_per_s,
            "fused": fused, "peak": ctx["peak"], "model": model,
            "encoder": st["encoder"],
            "pairs": pipe_cfg.batch_pairs,
            "checked_towers": [b["towers"] for b in batches],
            "walks": sampler.num_walks if fused else None,
            "walk_len": pipe_cfg.walk.walk_len,
            "npos": len(sampler._positions) if fused else None,
            "setup_compile_s": setup_compile_s,
            "setup_graph_s": setup_s - setup_compile_s,
        }

    # free the program's state, then the reference
    trainer.close()
    del trainer, res, warm, first, params, sampler
    st["trainer"] = None
    gc.collect()
    p0 = weights_only(ctx, st["encoder"], seed_weights, st["num_nodes"])
    checks = reference_checks(ctx, st["encoder"], p0, prog, batches,
                              st["arrays"])
    return {
        "e2e": {"pairs_per_s": pairs_per_s, "setup_s": setup_s},
        "checks": checks, "attempted": steps, "failed": failed,
        "memory_peak_bytes": mem, "layer": layer,
    }


def weights_only(ctx: Dict, enc, seed_weights: int, num_nodes: int):
    import jax

    import reference as ref

    return ref.make_params(jax.random.PRNGKey(seed_weights),
                           ctx["config"]["model"], enc, num_nodes)
