"""Retrieval window: offline u2i candidate generation, closed loop.

One caller sends batches of user queries to ``repro.retrieval.chunked_topk``
as ``core.recall.evaluate_recall`` calls it (``query_chunk`` and
``item_chunk`` from the mix), each with the users' train histories
excluded, padded to the longest history in the deployment. The item table
and the queries are a mixture-of-Gaussians corpus made from the seed on the
device; the users, and so their histories, are drawn from the seed too.
The window counts the batches whose ids reached the host.

After the window, with the program's table freed, a sample of the answered
queries drawn from the seed is checked against the exact reference over
the whole table.
"""
from __future__ import annotations

import gc
import sys
import time
from pathlib import Path
from typing import Dict

import numpy as np

HERE = Path(__file__).resolve().parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def make_corpus(key, num_items: int, num_queries: int, dim: int, c: Dict):
    """(items, queries) on the device in one jitted call: cluster centres
    scaled by ``center_scale``; items and queries near random centres with
    Gaussian noise; rows scaled to unit norm, as evaluate_recall does."""
    import jax
    import jax.numpy as jnp

    def f(key):
        kc, ki, kin, kq, kqn = jax.random.split(key, 5)
        centers = jax.random.normal(kc, (c["centers"], dim)) * c["center_scale"]
        it = centers[jax.random.randint(ki, (num_items,), 0, c["centers"])]
        it = it + c["item_noise"] * jax.random.normal(kin, (num_items, dim))
        q = centers[jax.random.randint(kq, (num_queries,), 0, c["centers"])]
        q = q + c["query_noise"] * jax.random.normal(kqn, (num_queries, dim))
        if c["normalize"]:
            it = it / jnp.maximum(jnp.linalg.norm(it, axis=1, keepdims=True),
                                  1e-12)
            q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True),
                                1e-12)
        return it, q

    return jax.jit(f)(key)


def inputs(seed: int, tr: Dict, dim: int, n: int, ub, arrays: Dict,
           meta: Dict):
    """A run's draws from its seed, for ``n`` queries: (corpus seed, check
    seed, the host item table, the queries, and each query's user's train
    history as exclusion lists padded to the deployment's longest)."""
    import jax

    s_corpus, s_users, s_check = (
        int(x) & 0x7FFFFFFF
        for x in np.random.SeedSequence(seed).generate_state(3))
    U, I = int(meta["num_users"]), int(meta["num_items"])
    items_dev, q_dev = make_corpus(jax.random.PRNGKey(s_corpus), I, n, dim,
                                   tr["corpus"])
    items = np.asarray(jax.device_get(items_dev))
    queries = np.asarray(jax.device_get(q_dev))
    del items_dev, q_dev
    users = np.random.default_rng(s_users).choice(U, n, replace=n > U)
    exclude = np.full((n, int(meta["max_history"])), -1, np.int32)
    for row, h in enumerate(ub.user_history(arrays, U, users)):
        exclude[row, : len(h)] = h
    return s_corpus, s_check, items, queries, exclude


def run(ctx: Dict) -> Dict:
    import jax

    from repro.retrieval import chunked_topk

    import reference as ref

    cfg, tr, log, clock = (ctx["config"], ctx["traffic"], ctx["log"],
                           ctx["clock"])
    ub = harness.load_module(HERE / "data" / "ub.py", "bench_ub")
    arrays, meta = ub.load_or_generate(cfg["deployment"], ctx["cache"], log)
    I, E = int(meta["num_items"]), int(meta["max_history"])
    d = int(cfg["model"]["dim"])
    Q, k = int(tr["query_chunk"]), int(tr["k"])
    nb = int(tr["pool_batches"])
    s_corpus, s_check, items, queries, exclude = inputs(
        ctx["seed"], tr, d, nb * Q, ub, arrays, meta)

    def call(b):
        sl = slice((b % nb) * Q, (b % nb + 1) * Q)
        return chunked_topk(queries[sl], items, k, exclude=exclude[sl],
                            item_chunk=int(tr["item_chunk"]),
                            query_chunk=Q)[1]

    for b in range(int(tr["warmup_batches"])):
        call(b)
    span = min(ctx["seconds"], float(tr["trace_seconds"])) \
        if ctx["trace"] else ctx["seconds"]
    harness.settle_heap(log)
    setup_s = time.perf_counter() - ctx["t_start"]
    setup_compile_s = clock.compile_s
    compiles0 = clock.compiles

    answers = []
    with harness.Traced(ctx, ctx["trace"]) as traced, \
            harness.GcWatch() as gcw:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < span:
            if ctx["trace"]:
                with jax.profiler.TraceAnnotation("bench.retrieve_batch"):
                    answers.append(call(len(answers)))
            else:
                answers.append(call(len(answers)))
        wall = time.perf_counter() - t0
    in_window = clock.compiles - compiles0
    qps = len(answers) * Q / wall
    log(f"[info] window {len(answers)} batches of {Q}, {wall:.3f}s, "
        f"{in_window} compiles in the window, {gcw.passes} gc passes "
        f"(longest {gcw.worst:.4f}s); set-up {setup_s:.3f}s of which "
        f"{setup_compile_s:.3f}s compile; exclusion width {E}")
    failed = sum(int((a < 0).all(axis=1).sum()) for a in answers)
    mem = harness.memory_peak_bytes(ctx["devices"])

    layer = {}
    if ctx["trace"]:
        layer = {
            "trace": traced.reduce(modules=("jit__chunked_topk_scan",)),
            "batches": len(answers), "queries": Q, "items": I, "dim": d,
            "exclude": E, "k": k, "queries_per_s": qps, "peak": ctx["peak"],
            "setup_compile_s": setup_compile_s,
            "setup_graph_s": setup_s - setup_compile_s,
        }

    # free the program's table (its device copy is keyed to the host
    # array), then check a sample of the answers against the reference
    del items
    gc.collect()
    rng = np.random.default_rng(s_check)
    n_check = min(int(tr["check_queries"]), len(answers) * Q)
    pick = rng.choice(len(answers) * Q, n_check, replace=False)
    rows = np.array([(p // Q % nb) * Q + p % Q for p in pick])
    got = np.stack([answers[p // Q][p % Q] for p in pick])
    items_dev, _ = make_corpus(jax.random.PRNGKey(s_corpus), I, nb * Q, d,
                               tr["corpus"])
    checks = ref.topk_reference(queries[rows], items_dev, exclude[rows], k,
                                got)
    return {
        "e2e": {"queries_per_s": qps, "setup_s": setup_s},
        "checks": {k_: float(v) for k_, v in checks.items()},
        "attempted": len(answers), "failed": failed,
        "memory_peak_bytes": mem, "layer": layer,
    }
