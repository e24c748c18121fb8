"""Paper Fig. 2 (RQ1): system throughput, plus kernel microbenchmarks.

- pairs/second of the full pipeline for walk-based vs GNN models, each run
  two ways: the *serial* seed path (no prefetch, per-step device sync,
  loop-built engine partitions, per-node slot padding) vs the *fast* path
  (background prefetch thread, no per-step sync, vectorized engine build and
  slot padding). The prefetch/serial ratio is the tentpole speedup.
- engine partition build time, loop vs vectorized CSR slice-gather.
- per-kernel us/call (interpret mode on CPU: correctness-path timing; TPU
  numbers come from the roofline analysis, not wall clock).

Results are also written to ``BENCH_throughput.json`` at the repo root as a
machine-readable baseline for regression tracking.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from typing import Dict

if __package__ in (None, ""):  # `python benchmarks/bench_throughput.py`
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (_ROOT, os.path.join(_ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

import jax
import jax.numpy as jnp

from benchmarks.common import dataset, emit, trainer
from repro.utils.compile_cache import enable_compile_cache

_JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_throughput.json")


@contextlib.contextmanager
def _seed_loop_padding():
    """Restore the seed's per-node pad_slot_values Python loop for the
    serial baseline arm (active while that arm compiles AND runs, so its
    host path matches the seed's exactly)."""
    from repro.embedding import table as table_mod

    orig = table_mod.pad_slot_values
    table_mod.pad_slot_values = table_mod._pad_slot_values_loop
    try:
        yield
    finally:
        table_mod.pad_slot_values = orig


def _median(xs):
    s = sorted(xs)
    return s[len(s) // 2]


def pipeline_throughput(quick: bool = True, results: Dict = None) -> None:
    """Serial seed path vs overhauled path vs auto-selected path, per model
    family.

    The serial arm reproduces the seed end to end: no prefetch thread, a
    device sync every step, loop-built engine partitions, per-node Python
    slot padding, 'values' (padded gather+sum) side info, and the dense
    full-table grad step (sparse_updates=False). The prefetch arm is the
    explicit production path: background prefetch, double-buffered H2D
    staging, async loss readback. The auto arm leaves prefetch to the
    calibrated backend plan (``auto_backend``) — for cheap samplers (the
    walk-based family) it degrades to the serial loop instead of paying a
    queue handoff that costs more than it hides. Arms are measured
    INTERLEAVED and speedups are per-rep ratios (median reported), so
    shared-host throughput drift cancels out.
    """
    ds = dataset("toy" if quick else "rec15")
    steps = 60 if quick else 200
    reps = 3
    arms = (
        ("walk-based", dict(gnn_type=None)),
        ("gnn-lightgcn", dict(gnn_type="lightgcn")),
        ("gnn-side-info", dict(gnn_type="lightgcn", side_info=True)),
    )
    for name, kw in arms:
        trainers = {
            "serial": trainer(
                ds, steps=steps, prefetch_batches=0, sync_every_step=True,
                eval_at_end=False, engine_build="loop", slot_mode="values",
                sparse_updates=False, **kw,
            ),
            "prefetch": trainer(
                ds, steps=steps, prefetch_batches=3, sync_every_step=False,
                eval_at_end=False, **kw,
            ),
            "auto": trainer(
                ds, steps=steps, prefetch_batches=None, auto_backend=True,
                sync_every_step=False, eval_at_end=False, **kw,
            ),
        }
        wall: Dict[str, list] = {m: [] for m in trainers}
        pairs: Dict[str, int] = {}
        for mode, tr in trainers.items():  # compile + warm (+ calibrate)
            with _seed_loop_padding() if mode == "serial" else contextlib.nullcontext():
                tr.train()
        for _ in range(reps):
            for mode, tr in trainers.items():
                with _seed_loop_padding() if mode == "serial" else contextlib.nullcontext():
                    res = tr.train()
                wall[mode].append(res.wall_time_s)
                pairs[mode] = res.pairs_seen
        best = {m: min(w) for m, w in wall.items()}
        pps = {m: pairs[m] / best[m] for m in best}
        for mode in trainers:
            emit(
                f"throughput/{name}/{mode}", best[mode] / steps * 1e6,
                f"pairs_per_sec={pps[mode]:.0f}",
            )
        ratios = {
            m: _median([s / w for s, w in zip(wall["serial"], wall[m])])
            for m in ("prefetch", "auto")
        }
        emit(f"throughput/{name}/speedup", 0.0,
             f"speedup={ratios['prefetch']:.2f}x")
        emit(f"throughput/{name}/speedup_auto", 0.0,
             f"speedup={ratios['auto']:.2f}x "
             f"plan_prefetch={trainers['auto']._plan['prefetch']}")
        if results is not None:
            results[f"pipeline/{name}"] = {
                "pairs_per_sec_serial": round(pps["serial"], 1),
                "pairs_per_sec_prefetch": round(pps["prefetch"], 1),
                "pairs_per_sec_auto": round(pps["auto"], 1),
                "speedup": round(ratios["prefetch"], 3),
                "speedup_auto": round(ratios["auto"], 3),
                "auto_plan_prefetch": trainers["auto"]._plan["prefetch"],
            }


def engine_build(quick: bool = True, results: Dict = None) -> None:
    from repro.graph import DistributedGraphEngine

    ds = dataset("toy" if quick else "rec15")
    reps = 5 if quick else 3
    times: Dict[str, float] = {}
    for mode in ("loop", "vectorized"):
        DistributedGraphEngine(ds.graph, num_partitions=4, build=mode)  # warm caches
        t0 = time.perf_counter()
        for _ in range(reps):
            DistributedGraphEngine(ds.graph, num_partitions=4, build=mode)
        times[mode] = (time.perf_counter() - t0) / reps
        emit(f"engine_build/{mode}", times[mode] * 1e6, f"partitions=4 reps={reps}")
    speedup = times["loop"] / times["vectorized"]
    emit("engine_build/speedup", 0.0, f"speedup={speedup:.2f}x")
    if results is not None:
        results["engine_build"] = {
            "loop_ms": round(times["loop"] * 1e3, 3),
            "vectorized_ms": round(times["vectorized"] * 1e3, 3),
            "speedup": round(speedup, 3),
        }


def sparse_step_bench(quick: bool = True, results: Dict = None) -> None:
    """Sparse gather→step→scatter vs dense full-table grad step, by rows.

    Both arms run the production code paths (embedding.table gather/scatter +
    embedding.optimizer row-wise AdaGrad vs train.optimizer.rowwise_adagrad
    over dense grads) on the same batch stream; the 1M-row point is always
    measured — it is the regression baseline for the O(batch)-vs-O(N) claim
    (sparse steps/sec must stay flat in N, dense decays ~linearly).
    """
    import numpy as np

    from repro.embedding import (
        gather_rows, lookup, remap_ids, rowwise_adagrad_init,
        rowwise_adagrad_scatter_update, unique_pad_ids,
    )
    from repro.train import optimizer as opt_lib

    dim, B, bucket, lr = 32, 1024, 2048, 0.5
    sizes = (10_000, 100_000, 1_000_000)
    reps, iters = (3, 10) if quick else (5, 20)

    def dense_step_fn():
        opt = opt_lib.rowwise_adagrad(lr)

        def step(table, accum, ids):
            def loss_of(t):
                return (lookup(t, ids) ** 2).mean()

            g = jax.grad(loss_of)(table)
            upd, accum = opt.update({"t": g}, {"t": accum})
            return table + upd["t"], accum["t"]

        return jax.jit(step, donate_argnums=(0, 1))

    def sparse_step_fn():
        def step(table, accum, uniq, local):
            sub = gather_rows(table, uniq)

            def loss_of(s):
                return (lookup(s, local) ** 2).mean()

            g = jax.grad(loss_of)(sub)
            from repro.embedding import RowAdagradState

            new_p, st = rowwise_adagrad_scatter_update(
                {"t": table}, {"t": g}, {"t": uniq},
                RowAdagradState(accum={"t": accum}), lr=lr,
            )
            return new_p["t"], st.accum["t"]

        return jax.jit(step, donate_argnums=(0, 1))

    step_results: Dict[str, Dict[str, float]] = {}
    for N in sizes:
        rng = np.random.default_rng(0)
        id_pool = [rng.integers(0, N, size=B) for _ in range(8)]
        times: Dict[str, float] = {}

        dense = dense_step_fn()
        table = jnp.asarray(rng.normal(size=(N, dim)).astype(np.float32))
        accum = jnp.full((N, 1), 0.1, jnp.float32)
        ids_dev = [jnp.asarray(i) for i in id_pool]
        table, accum = dense(table, accum, ids_dev[0])
        jax.block_until_ready(table)
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            for it in range(iters):
                table, accum = dense(table, accum, ids_dev[it % 8])
            jax.block_until_ready(table)
            best = min(best, (time.perf_counter() - t0) / iters)
        times["dense"] = best
        del table, accum

        sparse = sparse_step_fn()
        table = jnp.asarray(rng.normal(size=(N, dim)).astype(np.float32))
        accum = jnp.full((N, 1), 0.1, jnp.float32)
        # host-side dedup+remap is part of the sparse path: keep it inside
        # the timed loop
        table, accum = sparse(
            table, accum,
            jnp.asarray(unique_pad_ids([id_pool[0]], bucket=bucket)),
            jnp.asarray(remap_ids(unique_pad_ids([id_pool[0]], bucket=bucket), id_pool[0])),
        )
        jax.block_until_ready(table)
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            for it in range(iters):
                ids = id_pool[it % 8]
                uniq = unique_pad_ids([ids], bucket=bucket)
                local = jnp.asarray(remap_ids(uniq, ids))
                table, accum = sparse(table, accum, jnp.asarray(uniq), local)
            jax.block_until_ready(table)
            best = min(best, (time.perf_counter() - t0) / iters)
        times["sparse"] = best
        del table, accum

        speedup = times["dense"] / times["sparse"]
        for mode in ("dense", "sparse"):
            emit(
                f"grad_step/N{N}/{mode}", times[mode] * 1e6,
                f"steps_per_sec={1.0 / times[mode]:.1f}",
            )
        emit(f"grad_step/N{N}/speedup", 0.0, f"speedup={speedup:.2f}x")
        step_results[f"N{N}"] = {
            "dense_us": round(times["dense"] * 1e6, 1),
            "sparse_us": round(times["sparse"] * 1e6, 1),
            "steps_per_sec_dense": round(1.0 / times["dense"], 1),
            "steps_per_sec_sparse": round(1.0 / times["sparse"], 1),
            "speedup": round(speedup, 3),
        }
    flat = (
        step_results[f"N{sizes[-1]}"]["sparse_us"]
        / step_results[f"N{sizes[0]}"]["sparse_us"]
    )
    emit("grad_step/sparse_flat_ratio", 0.0, f"t(1M)/t(10k)={flat:.2f}x")
    if results is not None:
        step_results["sparse_flat_ratio_1M_vs_10k"] = round(flat, 3)
        # self-describing: --step merges into an existing JSON whose
        # top-level "quick" flag reflects the last full run, not this arm
        step_results["quick"] = quick
        results["grad_step"] = step_results


def engine_service_bench(quick: bool = True, results: Dict = None) -> None:
    """Sampling throughput: in-process engine vs mp graph service (1/2/4
    workers), on the medium synthetic graph (`make bench-engine`).

    The workload is the pipeline's own access pattern — grouped
    ``sample_many`` queries (one per relation, ego-hop style) issued by four
    concurrent driver threads, the way the prefetch producer, a mid-training
    eval, and sibling pipelines hit the engine. In-process, those threads
    share one GIL with all the NumPy glue; the mp service moves the sampling
    work to worker processes that run truly in parallel — with "balanced"
    dispatch each whole request round goes to the least-loaded worker, which
    composes the reply in caller order inside its shared-memory slab, so the
    client's per-sample cost is one contiguous copy. ``saturation`` is
    worker busy-time / (wall x workers) — how much of the fleet the client
    kept fed; the "owner" dispatch arm (partition-pinned fan-out, the
    paper's multi-machine layout) is reported for comparison. Also runs a
    short end-to-end training arm (GNN model) per backend, reporting
    pipeline pairs/sec.
    """
    import threading

    import numpy as np

    from repro.graph import DistributedGraphEngine
    from repro.graph.service import GraphClient

    ds = dataset("rec15")  # the paper-scale "medium" synthetic graph
    g = ds.graph
    from benchmarks.common import RELS

    P = 4
    B = 16384
    k = 8
    threads = 6
    iters = 10 if quick else 30
    reps = 5
    out: Dict = {
        "dataset": "rec15", "batch_nodes": B, "num_samples": k,
        "driver_threads": threads, "partitions": P,
    }

    def drive(engine, n_iters: int) -> float:
        barrier = threading.Barrier(threads + 1)
        errs: list = []

        def worker(tid: int) -> None:
            rng = np.random.default_rng(100 + tid)
            pool = [rng.integers(0, g.num_nodes, size=B) for _ in range(8)]
            barrier.wait()
            try:
                for i in range(n_iters):
                    engine.sample_many(
                        rng, [(pool[i % 8], r, k, -1) for r in RELS]
                    )
            except BaseException as e:  # surface in the main thread
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
        for t in ts:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        wall = time.perf_counter() - t0
        return threads * n_iters * len(RELS) * B / wall

    # Arms are measured INTERLEAVED, one inproc + one of each mp
    # configuration per rep, and speedups are per-rep ratios (median
    # reported): on shared/throttled hosts absolute throughput drifts by 2x
    # over minutes, but arms measured seconds apart see the same machine.
    inproc = DistributedGraphEngine(g, num_partitions=P)
    arms = [(w, "balanced") for w in (1, 2, 4)] + [(4, "owner")]
    clients = {
        (w, d): GraphClient(
            g, num_partitions=P, num_workers=w, dispatch=d, pin_workers=True
        )
        for w, d in arms
    }
    try:
        drive(inproc, 3)  # warm every arm (spawn + first-touch)
        for client in clients.values():
            drive(client, 3)
        qin: list = []
        qps: Dict[Tuple, list] = {a: [] for a in arms}
        sat: Dict[Tuple, list] = {a: [] for a in arms}
        for _ in range(reps):
            qin.append(drive(inproc, iters))
            for a, client in clients.items():
                client.reset_stats()
                t0 = time.perf_counter()
                qps[a].append(drive(client, iters))
                wall = time.perf_counter() - t0
                sat[a].append(
                    client.aggregate_stats()["busy_s"] / (wall * a[0])
                )
    finally:
        for client in clients.values():
            client.shutdown()
    emit("engine_service/inproc", 0.0, f"queries_per_sec={max(qin):.0f}")
    out["inproc_qps"] = round(max(qin), 0)
    out["mp"] = {}
    for (w, dispatch) in arms:
        ratios = sorted(q / b for q, b in zip(qps[(w, dispatch)], qin))
        med_ratio = ratios[len(ratios) // 2]
        best = max(qps[(w, dispatch)])
        name = f"mp{w}" if dispatch == "balanced" else f"mp{w}_{dispatch}"
        emit(
            f"engine_service/{name}", 0.0,
            f"queries_per_sec={best:.0f} speedup_median={med_ratio:.2f}x "
            f"saturation={max(sat[(w, dispatch)]):.2f}",
        )
        out["mp"][f"workers{w}_{dispatch}"] = {
            "qps": round(best, 0),
            "speedup_median": round(med_ratio, 3),
            "saturation": round(max(sat[(w, dispatch)]), 3),
        }
    speedup = out["mp"]["workers4_balanced"]["speedup_median"]
    emit("engine_service/speedup_mp4", 0.0, f"speedup={speedup:.2f}x")
    out["speedup_mp4_vs_inproc"] = speedup

    # ---- end-to-end pipeline pairs/sec per backend. Interleaved per-rep
    # wall-clock ratios (median), like the component arms above: the two
    # trainers alternate inside each rep so machine drift cancels.
    steps = 40 if quick else 120
    e2e_reps = 5
    trainers = {
        backend: trainer(
            ds, steps=steps, eval_at_end=False, gnn_type="lightgcn",
            engine_backend=backend, num_engine_workers=workers,
        )
        for backend, workers in (("inproc", 0), ("mp", 2))
    }
    walls: Dict[str, list] = {m: [] for m in trainers}
    try:
        for tr in trainers.values():
            tr.train()  # compile + warm
        for _ in range(e2e_reps):
            for backend, tr in trainers.items():
                walls[backend].append(tr.train().wall_time_s)
    finally:
        for tr in trainers.values():
            tr.close()
    pipe = {
        m: steps * trainers[m].pipe_cfg.batch_pairs / min(w)
        for m, w in walls.items()
    }
    for backend in trainers:
        emit(f"engine_service/pipeline_{backend}", 0.0,
             f"pairs_per_sec={pipe[backend]:.0f}")
    mp_ratio = sorted(
        i / m for i, m in zip(walls["inproc"], walls["mp"])
    )[e2e_reps // 2]
    out["pipeline_pairs_per_sec"] = {m: round(v, 1) for m, v in pipe.items()}
    out["pipeline_mp_speedup"] = round(mp_ratio, 3)
    if results is not None:
        results["engine_service"] = out


def walk_fusion_bench(quick: bool = True, results: Dict = None) -> None:
    """Fused on-device walk->pair->ego sampling vs the host pipeline
    (`make bench-walk`).

    Measures the sampling front end alone — the stage the fused backend
    moves onto the device: host arm = ``SamplePipeline.batches`` against the
    in-process partitioned engine (the prefetch producer's exact workload),
    fused arm = the jitted ``FusedSampler.sample`` program (walk, Pallas
    window-pair gather, ego gather, one dispatch per batch). Arms are
    measured interleaved and speedups are per-rep ratios (median reported)
    to tame shared-host noise. Also records end-to-end trainer pairs/sec
    with ``sampling_backend="fused"`` vs "host" for the GNN model
    (informational: with host prefetching the grad step overlaps sampling,
    so the end-to-end CPU ratio is far below the sampling-stage ratio).
    """
    import jax as _jax
    import numpy as np

    from repro.graph import DistributedGraphEngine
    from repro.sampling import EgoConfig, PairConfig, PipelineConfig, SamplePipeline
    from repro.sampling.fused import FusedSampler, fused_eligibility
    from repro.walk import WalkConfig

    ds = dataset("toy" if quick else "rec15")
    g = ds.graph
    from benchmarks.common import RELS

    batch_pairs = 512
    nb = 20 if quick else 40
    reps = 5
    out: Dict = {
        "dataset": ds.spec.name, "batch_pairs": batch_pairs, "batches": nb,
    }
    arms = (
        ("walk-based", None),
        ("gnn", EgoConfig(relations=list(RELS), fanouts=[4, 3])),
    )
    for name, ego in arms:
        pc = PipelineConfig(
            walk=WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=6),
            pair=PairConfig(win_size=2), ego=ego,
            batch_pairs=batch_pairs, walks_per_round=128,
        )
        eng = DistributedGraphEngine(g, num_partitions=4)
        ok, why = fused_eligibility(g, pc)
        assert ok, f"bench graph must fit the padded-adjacency budget: {why}"
        fs = FusedSampler(g, pc)
        sample = _jax.jit(fs.sample)
        # keys batched up front: a per-batch eager fold_in would cost more
        # than the fused program itself
        keys = _jax.random.split(_jax.random.PRNGKey(0), nb)
        _jax.block_until_ready(sample(keys[0]))  # compile
        list(SamplePipeline(eng, pc, seed=0).batches(2))  # warm host caches

        def host_run() -> float:
            pipe = SamplePipeline(eng, pc, seed=0)
            t0 = time.perf_counter()
            list(pipe.batches(nb))
            return nb * batch_pairs / (time.perf_counter() - t0)

        def fused_run() -> float:
            t0 = time.perf_counter()
            for i in range(nb):
                got = sample(keys[i])
            _jax.block_until_ready(got)
            return nb * batch_pairs / (time.perf_counter() - t0)

        host_pps, fused_pps, ratios = [], [], []
        for _ in range(reps):  # interleaved: both arms see the same machine
            h = host_run()
            f = fused_run()
            host_pps.append(h)
            fused_pps.append(f)
            ratios.append(f / h)
        med = sorted(ratios)[len(ratios) // 2]
        emit(f"walk_fusion/{name}/host", 0.0,
             f"pairs_per_sec={max(host_pps):.0f}")
        emit(f"walk_fusion/{name}/fused", 0.0,
             f"pairs_per_sec={max(fused_pps):.0f}")
        emit(f"walk_fusion/{name}/speedup", 0.0, f"speedup_median={med:.2f}x")
        out[name] = {
            "pairs_per_sec_host": round(max(host_pps), 1),
            "pairs_per_sec_fused": round(max(fused_pps), 1),
            "speedup_median": round(med, 3),
        }

    # ---- end-to-end trainer pairs/sec per sampling backend. Interleaved
    # per-rep wall-clock ratios (median), same methodology as the component
    # arms: both trainers run inside each rep so machine drift cancels.
    steps = 40 if quick else 100
    e2e_reps = 5
    trainers = {
        backend: trainer(
            ds, steps=steps, eval_at_end=False, gnn_type="lightgcn",
            batch_pairs=batch_pairs, sampling_backend=backend,
        )
        for backend in ("host", "fused")
    }
    walls: Dict[str, list] = {m: [] for m in trainers}
    for tr in trainers.values():
        tr.train()  # compile + warm
    for _ in range(e2e_reps):
        for backend, tr in trainers.items():
            walls[backend].append(tr.train().wall_time_s)
    pipe = {m: steps * batch_pairs / min(w) for m, w in walls.items()}
    for backend in trainers:
        emit(f"walk_fusion/pipeline_{backend}", 0.0,
             f"pairs_per_sec={pipe[backend]:.0f}")
    fused_ratio = sorted(
        h / f for h, f in zip(walls["host"], walls["fused"])
    )[e2e_reps // 2]
    out["pipeline_pairs_per_sec"] = {m: round(v, 1) for m, v in pipe.items()}
    out["pipeline_fused_speedup"] = round(fused_ratio, 3)
    if results is not None:
        results["walk_fusion"] = out


def attribution_bench(quick: bool = True, results: Dict = None) -> None:
    """Per-step time attribution (`--attribution` / `make bench-attr`).

    Runs the trainer with ``TrainerConfig.attribution`` on for every
    {engine backend} x {loop mode} combination — inproc/mp x
    serial/prefetch/fused — and records each run's PhaseTimer summary
    (sample / assemble / batch_wait / h2d / dispatch / loss_fetch, plus
    consumer-visible vs device-residual wall time) into the
    ``step_attribution`` section of BENCH_throughput.json. This is the
    measuring instrument behind the throughput work: it shows WHERE a
    step's wall time goes per configuration, so regressions like "mp is
    2.4x faster at sampling but 0.8x end-to-end" decompose into the phase
    that actually ate the difference. Timing is sync-free (ring-buffered
    host timestamps; the only device barrier is the trainer's end-of-run
    drain), so the instrumented runs are faithful to production behavior.
    """
    ds = dataset("toy" if quick else "rec15")
    steps = 48 if quick else 150
    combos = [
        ("inproc", "serial", dict(prefetch_batches=0)),
        ("inproc", "prefetch", dict(prefetch_batches=2)),
        ("inproc", "fused", dict(sampling_backend="fused")),
        ("mp", "serial", dict(engine_backend="mp", prefetch_batches=0)),
        ("mp", "prefetch", dict(engine_backend="mp", prefetch_batches=2)),
        ("mp", "fused", dict(engine_backend="mp", sampling_backend="fused")),
    ]
    out: Dict = {"dataset": ds.spec.name, "steps": steps}
    for eng_name, mode, kw in combos:
        tr = trainer(
            ds, steps=steps, eval_at_end=False, gnn_type="lightgcn",
            attribution=True, **kw,
        )
        with tr:
            tr.train()  # compile + warm
            res = tr.train()
        combo = f"{eng_name}/{mode}"
        summary = dict(res.attribution)
        summary["plan"] = {
            k: res.plan[k] for k in ("sampling", "prefetch", "engine_backend")
        }
        out[combo] = summary
        emit(f"attr/{combo}/wall", summary["wall_us_per_step"],
             f"steps={summary['steps']}")
        for phase, entry in summary["phases"].items():
            emit(
                f"attr/{combo}/{phase}", entry["per_call_us"],
                f"frac_of_wall={entry.get('frac_of_wall', 0.0):.3f}",
            )
        emit(
            f"attr/{combo}/device_residual", 0.0,
            f"frac_of_wall={summary['device_residual_s'] / summary['wall_s']:.3f}",
        )
    if results is not None:
        results["step_attribution"] = out


def sanitize_bench(quick: bool = True, results: Dict = None) -> None:
    """Transfer-guard sanitizer overhead (`--sanitize` / `make bench-sanitize`).

    Runs the trainer with ``sanitize_transfers`` on vs off, host and fused
    sampling backends, reporting the wall-time overhead of dispatching every
    jitted step under ``jax.transfer_guard("disallow")``. The guarded arms
    double as the hard check: an implicit host->device transfer anywhere in
    the step dispatch raises instead of silently serializing, so this arm
    failing IS the regression signal. Arms are interleaved per rep.
    """
    ds = dataset("toy")
    steps = 40 if quick else 120
    out: Dict = {"dataset": "toy", "steps": steps}
    for backend in ("host", "fused"):
        trainers = {
            mode: trainer(
                ds, steps=steps, eval_at_end=False, gnn_type="lightgcn",
                sampling_backend=backend, sanitize_transfers=(mode == "guarded"),
            )
            for mode in ("off", "guarded")
        }
        for tr in trainers.values():
            tr.train()  # compile + warm
        best: Dict[str, float] = {}
        for _ in range(3):  # interleaved: both arms see the same machine
            for mode, tr in trainers.items():
                res = tr.train()
                best[mode] = min(best.get(mode, 1e9), res.wall_time_s)
        overhead = best["guarded"] / best["off"]
        for mode in ("off", "guarded"):
            emit(
                f"sanitize/{backend}/{mode}", best[mode] / steps * 1e6,
                f"pairs_per_sec={steps * tr.pipe_cfg.batch_pairs / best[mode]:.0f}",
            )
        emit(f"sanitize/{backend}/overhead", 0.0, f"overhead={overhead:.3f}x")
        out[backend] = {
            "wall_s_off": round(best["off"], 4),
            "wall_s_guarded": round(best["guarded"], 4),
            "overhead": round(overhead, 4),
        }
    if results is not None:
        results["sanitize"] = out


def telemetry_bench(quick: bool = True, results: Dict = None) -> None:
    """Telemetry-layer overhead (`--telemetry` / `make bench-trace`).

    Runs the trainer with the unified telemetry layer (``repro.obs``) off vs
    on, reporting the wall-time overhead of span recording + metric updates
    on the prefetch pipeline (the most instrumented configuration: stager
    gauges, phase spans, client round spans all active). The disabled arm is
    the production default and must stay within noise of a build that never
    had telemetry: every instrumented site guards on a preresolved handle
    (``if tracer is not None``), so "off" costs one attribute load + is-None
    test per site. Arms are interleaved per rep so machine drift cancels.
    """
    from repro.obs import Telemetry

    from repro.obs import HealthConfig
    from repro.obs.memory import memory_snapshot

    ds = dataset("toy")
    steps = 40 if quick else 120
    out: Dict = {"dataset": "toy", "steps": steps}
    tel = Telemetry()
    tel_h = Telemetry()
    # "guarded" = traced + the run-health monitor (watchdog thread, per-step
    # beats, loss-drain anomaly checks): its overhead is measured against
    # the traced arm, pinning the guardrails at <=2% on top of tracing.
    trainers = {
        "off": trainer(
            ds, steps=steps, eval_at_end=False, gnn_type="lightgcn",
            prefetch_batches=2,
        ),
        "traced": trainer(
            ds, steps=steps, eval_at_end=False, gnn_type="lightgcn",
            prefetch_batches=2, telemetry=tel,
        ),
        "guarded": trainer(
            ds, steps=steps, eval_at_end=False, gnn_type="lightgcn",
            prefetch_batches=2, telemetry=tel_h,
            health=HealthConfig(worker_heartbeat_s=0.0),
        ),
    }
    for tr in trainers.values():
        tr.train()  # compile + warm
    best: Dict[str, float] = {}
    for _ in range(3):  # interleaved: all arms see the same machine
        for mode, tr in trainers.items():
            res = tr.train()
            best[mode] = min(best.get(mode, 1e9), res.wall_time_s)
    overhead = best["traced"] / best["off"]
    overhead_health = best["guarded"] / best["traced"]
    events = len(tel.chrome_trace()["traceEvents"])
    for mode in trainers:
        emit(
            f"telemetry/{mode}", best[mode] / steps * 1e6,
            f"pairs_per_sec={steps * tr.pipe_cfg.batch_pairs / best[mode]:.0f}",
        )
    emit("telemetry/overhead", 0.0,
         f"overhead={overhead:.3f}x trace_events={events}")
    emit("telemetry/overhead_health", 0.0,
         f"overhead={overhead_health:.3f}x vs traced")
    if results is not None:
        results["telemetry"] = {
            "wall_s_off": round(best["off"], 4),
            "wall_s_traced": round(best["traced"], 4),
            "wall_s_guarded": round(best["guarded"], 4),
            "overhead": round(overhead, 4),
            "overhead_health": round(overhead_health, 4),
            "pairs_per_sec_off": round(
                steps * tr.pipe_cfg.batch_pairs / best["off"], 1),
            "pairs_per_sec_traced": round(
                steps * tr.pipe_cfg.batch_pairs / best["traced"], 1),
            "trace_events": events,
        }
        # device-memory accounting: the guarded run's per-phase live-array
        # peaks plus a process-level snapshot (allocator stats are empty on
        # the CPU backend; populated on real accelerators)
        mem = trainers["guarded"]._memory
        results["memory"] = (
            mem.summary() if mem is not None else memory_snapshot()
        )


def kernel_micro(quick: bool = True, results: Dict = None) -> None:
    from repro.kernels import ops

    def timeit(fn, *args, iters=20):
        fn(*args)  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e6

    x = jax.random.normal(jax.random.PRNGKey(0), (512, 8, 128))
    m = jax.random.bernoulli(jax.random.PRNGKey(1), 0.7, (512, 8))
    us = timeit(lambda a, b: ops.seg_aggr(a, b, "mean"), x, m)
    emit("kernel/seg_aggr_mean", us, "shape=512x8x128")
    if results is not None:
        results["kernel/seg_aggr_mean_us"] = round(us, 1)

    hs = jax.random.normal(jax.random.PRNGKey(2), (512, 64))
    us = timeit(lambda a: ops.inbatch_loss(a, a), hs)
    emit("kernel/inbatch_loss", us, "P=512,d=64")
    if results is not None:
        results["kernel/inbatch_loss_us"] = round(us, 1)

    q = jax.random.normal(jax.random.PRNGKey(3), (1, 512, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 512, 2, 64))
    us = timeit(lambda a, b: ops.flash_attention(a, b, b, causal=True), q, k)
    emit("kernel/flash_attn", us, "S=512,H=4,K=2,hd=64(interpret)")
    if results is not None:
        results["kernel/flash_attn_us"] = round(us, 1)


def run(quick: bool = True) -> Dict:
    results: Dict = {"quick": quick}
    engine_build(quick, results)
    pipeline_throughput(quick, results)
    sparse_step_bench(quick, results)
    engine_service_bench(quick, results)
    walk_fusion_bench(quick, results)
    attribution_bench(quick, results)
    kernel_micro(quick, results)
    with open(_JSON_PATH, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    return results


def _run_one_arm(fn, quick: bool) -> Dict:
    """Run a single benchmark arm and merge its results into the JSON."""
    try:
        with open(_JSON_PATH) as f:
            results = json.load(f)
    except (OSError, ValueError):
        results = {"quick": quick}
    fn(quick, results)
    with open(_JSON_PATH, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    return results


def run_step_only(quick: bool = True) -> Dict:
    """`make bench-step`: just the grad-step arm, merged into the JSON."""
    return _run_one_arm(sparse_step_bench, quick)


def run_engine_only(quick: bool = True) -> Dict:
    """`make bench-engine`: just the graph-service arm, merged into the JSON."""
    return _run_one_arm(engine_service_bench, quick)


def run_walk_only(quick: bool = True) -> Dict:
    """`make bench-walk`: just the fused-sampling arm, merged into the JSON."""
    return _run_one_arm(walk_fusion_bench, quick)


def run_sanitize_only(quick: bool = True) -> Dict:
    """`--sanitize`: just the transfer-guard arm, merged into the JSON."""
    return _run_one_arm(sanitize_bench, quick)


def run_attr_only(quick: bool = True) -> Dict:
    """`--attribution` / `make bench-attr`: the per-step attribution arm,
    merged into the JSON."""
    return _run_one_arm(attribution_bench, quick)


def run_trace_only(quick: bool = True) -> Dict:
    """`--telemetry` / `make bench-trace`: the telemetry-overhead arm,
    merged into the JSON."""
    return _run_one_arm(telemetry_bench, quick)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    grp = ap.add_mutually_exclusive_group()
    grp.add_argument("--quick", action="store_true", default=True,
                     help="toy dataset, short runs (default)")
    grp.add_argument("--full", action="store_true", help="larger synthetic dataset")
    arm = ap.add_mutually_exclusive_group()
    arm.add_argument("--step", action="store_true",
                     help="run only the sparse-vs-dense grad-step arm")
    arm.add_argument("--engine", action="store_true",
                     help="run only the inproc-vs-mp graph-service arm")
    arm.add_argument("--walk", action="store_true",
                     help="run only the fused-vs-host sampling arm")
    arm.add_argument("--sanitize", action="store_true",
                     help="run only the transfer-guard sanitizer arm")
    arm.add_argument("--attribution", action="store_true",
                     help="run only the per-step time-attribution arm")
    arm.add_argument("--telemetry", action="store_true",
                     help="run only the telemetry-overhead arm")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.step:
        run_step_only(quick=not args.full)
    elif args.engine:
        run_engine_only(quick=not args.full)
    elif args.walk:
        run_walk_only(quick=not args.full)
    elif args.sanitize:
        run_sanitize_only(quick=not args.full)
    elif args.attribution:
        run_attr_only(quick=not args.full)
    elif args.telemetry:
        run_trace_only(quick=not args.full)
    else:
        run(quick=not args.full)
