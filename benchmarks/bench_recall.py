"""Recall-serving benchmark: brute force vs chunked top-k vs IVF by scale.

The retrieval stage is what the paper's §4.2 experiments (and any serving
deployment) actually pay for, so this bench measures the three
implementations of the same U2I-style retrieval — history-excluded top-k
over an item table — at 10k / 100k / 1M items (plus a 10M arm with
``--full``):

- ``seed``: the seed evaluation path — materialize the full (Q, I) score
  matrix and run a per-row numpy argpartition loop. O(Q·I) memory.
- ``chunked``: jitted streaming top-k (repro.retrieval.chunked_topk) —
  O(Q·chunk) memory, the exact production path.
- ``pallas``: the fused kernel, measured at the smallest arm only (it runs
  in interpret mode on CPU; TPU timing comes from the roofline, not here).
- ``ivf``: the device-resident quantized ANN index (int8 codes, packed CSR
  inverted lists, exact re-rank), with its measured recall vs the exact
  result and its per-rep speedup over ``chunked``.

The corpus is a **mixture of gaussians** (items scattered around shared
centers, queries drawn near the same centers): the geometry trained
embeddings actually have — users cluster by taste, items by genre — and
the regime a coarse partition exists for. An isotropic gaussian corpus has
no cell structure at all: every cell holds near-neighbors of every query,
so recall 0.95 forces probing ~a third of the table and *no* partition
scheme can beat the dense GEMM (docs/retrieval.md works the numbers). The
earlier isotropic version of this bench is how an always-losing IVF went
unnoticed: it measured a workload the index was never for.

Arms are measured INTERLEAVED per rep and speedups are per-rep ratios
(median reported) — same methodology as bench-engine, for the same reason:
on shared hosts absolute throughput drifts, ratios of back-to-back runs
don't. Results merge into ``BENCH_recall.json`` at the repo root (pinned
by tests/test_attribution.py, gated by benchmarks/regression.py). The
compiled chunked program's temp-buffer footprint (from XLA's
memory_analysis) is recorded per arm — flat across item counts, which is
the "no full similarity matrix" claim in machine-checkable form.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence

if __package__ in (None, ""):  # `python benchmarks/bench_recall.py`
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (_ROOT, os.path.join(_ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

import numpy as np

from benchmarks.common import emit
from repro.utils.compile_cache import enable_compile_cache

_JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_recall.json")

K = 100
DIM = 32
EXCLUDE_W = 16

QUICK_SIZES = (10_000, 100_000, 1_000_000)
FULL_SIZES = QUICK_SIZES + (10_000_000,)

# Per-arm IVF tuning (docs/retrieval.md derives the trade-offs). nlist
# tracks sqrt-ish growth so lists stay short; nprobe is the recall knob;
# balance_factor 1.25 keeps lpad (the fixed gather width) near the mean
# list length. The 10M arm drops the exact f32 table from device memory
# (keep_exact_device=False: only the ~320 MB of int8 codes stay resident)
# and re-ranks on host.
_IVF_ARMS: Dict[int, Dict] = {
    10_000: dict(nlist=128, nprobe=8, kmeans_iters=6, train_size=0),
    100_000: dict(nlist=512, nprobe=12, kmeans_iters=6, train_size=65_536),
    1_000_000: dict(nlist=2048, nprobe=12, kmeans_iters=4, train_size=131_072),
    10_000_000: dict(nlist=4096, nprobe=16, kmeans_iters=3,
                     train_size=262_144, keep_exact_device=False),
}
_BALANCE = 1.25


def clustered_corpus(rng: np.random.Generator, I: int, Q: int, d: int = DIM):
    """Mixture-of-gaussians item table + queries near the same centers."""
    C = int(max(16, min(1024, I // 2048)))
    centers = rng.normal(size=(C, d)).astype(np.float32) * 3.0
    it = (centers[rng.integers(0, C, I)]
          + rng.normal(size=(I, d)).astype(np.float32))
    q = (centers[rng.integers(0, C, Q)]
         + 0.5 * rng.normal(size=(Q, d)).astype(np.float32))
    return it, q


def seed_topk_loop(q: np.ndarray, it: np.ndarray, k: int,
                   exclude: np.ndarray) -> np.ndarray:
    """The seed's evaluation pattern: full score matrix + per-row
    argpartition loop (core/recall.py before this subsystem existed)."""
    sim = q @ it.T
    rows = np.repeat(np.arange(len(q)), exclude.shape[1])
    cols = exclude.reshape(-1)
    valid = cols >= 0
    sim[rows[valid], cols[valid]] = -np.inf
    out = np.empty((len(q), k), dtype=np.int64)
    for r in range(len(q)):
        row = sim[r]
        idx = np.argpartition(-row, k - 1)[:k]
        out[r] = idx[np.argsort(-row[idx])]
    return out


def chunked_temp_bytes(Q: int, I: int, item_chunk: int) -> int:
    """Temp-buffer bytes of the compiled streaming-top-k program."""
    import jax.numpy as jnp

    from repro.retrieval.topk import _chunked_topk_scan

    chunk = min(item_chunk, I)
    Ip = -(-I // chunk) * chunk
    lowered = _chunked_topk_scan.lower(
        jnp.zeros((Q, DIM), jnp.float32),
        jnp.zeros((Ip // chunk, chunk, DIM), jnp.float32),
        jnp.zeros((Q, EXCLUDE_W), jnp.int32),
        k=K, chunk=chunk, num_items=I,
    )
    return int(lowered.compile().memory_analysis().temp_size_in_bytes)


def _ivf_config(I: int):
    from repro.retrieval import IVFConfig

    kw = _IVF_ARMS.get(I) or dict(
        nlist=max(16, min(2048, I // 500)), nprobe=12, kmeans_iters=4,
        train_size=min(I, 131_072),
    )
    return IVFConfig(balance_factor=_BALANCE, seed=0, **kw)


def retrieval_bench(
    quick: bool = True,
    results: Optional[Dict] = None,
    sizes: Optional[Sequence[int]] = None,
) -> None:
    from repro.retrieval import IVFIndex, chunked_topk

    sizes = tuple(sizes) if sizes else (QUICK_SIZES if quick else FULL_SIZES)
    base_reps = 3 if quick else 5
    rng = np.random.default_rng(0)
    # merge-update: a partial --sizes run refreshes only its own arms
    out_all: Dict[str, Dict] = dict(
        (results or {}).get("retrieval", {}), k=K, dim=DIM
    )
    for I in sizes:
        Q = 32 if I >= 10_000_000 else (
            64 if I >= 1_000_000 else (256 if quick else 512)
        )
        reps = min(base_reps, 3) if I >= 10_000_000 else base_reps
        item_chunk = 16384
        it, q = clustered_corpus(rng, I, Q)
        ex = rng.integers(0, I, size=(Q, EXCLUDE_W)).astype(np.int32)
        ivf_cfg = _ivf_config(I)
        t0 = time.perf_counter()
        index = IVFIndex.build(it, ivf_cfg)
        build_s = time.perf_counter() - t0

        def run_seed():
            return seed_topk_loop(q, it, K, ex)

        def run_chunked():
            return chunked_topk(q, it, K, exclude=ex, item_chunk=item_chunk)[1]

        def run_ivf():
            return index.search(q, K, exclude=ex)[1]

        exact = run_chunked()  # warm + reference result
        run_ivf()
        run_seed()
        times: Dict[str, List[float]] = {"seed": [], "chunked": [], "ivf": []}
        for _ in range(reps):
            for name, fn in (("seed", run_seed), ("chunked", run_chunked),
                             ("ivf", run_ivf)):
                t0 = time.perf_counter()
                fn()
                times[name].append(time.perf_counter() - t0)
        ivf_ids = run_ivf()
        ivf_recall = float(np.mean([
            len(set(a.tolist()) & set(b.tolist())) / K
            for a, b in zip(exact, ivf_ids)
        ]))
        ratios = sorted(s / c for s, c in zip(times["seed"], times["chunked"]))
        med_speedup = ratios[len(ratios) // 2]
        ivf_ratios = sorted(
            c / v for c, v in zip(times["chunked"], times["ivf"])
        )
        ivf_speedup = ivf_ratios[len(ivf_ratios) // 2]
        arm: Dict = {"num_queries": Q, "item_chunk": item_chunk}
        for name in times:
            best = min(times[name])
            arm[f"{name}_qps"] = round(Q / best, 1)
            emit(f"recall/I{I}/{name}", best / Q * 1e6,
                 f"queries_per_sec={Q / best:.1f}")
        arm["chunked_speedup_median_vs_seed"] = round(med_speedup, 3)
        arm["ivf_speedup_median_vs_chunked"] = round(ivf_speedup, 3)
        arm["ivf_recall_at_k"] = round(ivf_recall, 4)
        arm["ivf_build_s"] = round(build_s, 3)
        arm["ivf_nlist"] = index.config.nlist
        arm["ivf_nprobe"] = index.config.nprobe
        arm["ivf_lpad"] = index.lpad
        arm["ivf_spilled_items"] = index.spilled_items
        arm["chunked_temp_bytes"] = chunked_temp_bytes(Q, I, item_chunk)
        emit(f"recall/I{I}/speedup", 0.0, f"chunked_vs_seed={med_speedup:.2f}x")
        emit(f"recall/I{I}/ivf", 0.0,
             f"recall={ivf_recall:.3f} build_s={build_s:.2f} "
             f"speedup_vs_chunked={ivf_speedup:.2f}x")
        out_all[f"I{I}"] = arm
        del it, q, index

    # pallas arm (interpret mode on CPU): correctness-path timing, smallest
    # size only — the lowered program is what runs on TPU, wall clock isn't
    I = 4096
    it = rng.normal(size=(I, DIM)).astype(np.float32)
    q = rng.normal(size=(64, DIM)).astype(np.float32)
    from repro.retrieval import chunked_topk as _ct

    _ct(q, it, K, item_chunk=1024, backend="pallas")  # warm
    t0 = time.perf_counter()
    _ct(q, it, K, item_chunk=1024, backend="pallas")
    pallas_s = time.perf_counter() - t0
    emit("recall/pallas_interpret", pallas_s / 64 * 1e6, f"I={I}")
    out_all["pallas_interpret_I4096_qps"] = round(64 / pallas_s, 1)
    if results is not None:
        results["retrieval"] = out_all


def eval_e2e_bench(quick: bool = True, results: Dict = None) -> None:
    """End-to-end evaluate_recall (U2I) on a synthetic 100k-item table:
    the device path vs the numpy oracle, interleaved."""
    from repro.core.recall import evaluate_recall

    I, U, d = 100_000, 512, DIM
    rng = np.random.default_rng(1)
    ue = rng.normal(size=(U, d)).astype(np.float32)
    ie = rng.normal(size=(I, d)).astype(np.float32)
    train = np.stack([rng.integers(0, U, 4096), rng.integers(0, I, 4096)], 1)
    evalp = np.stack([rng.integers(0, U, 1024), rng.integers(0, I, 1024)], 1)
    kw = dict(top_k=K, strategies=("u2i",), item_chunk=16384)
    evaluate_recall(ue, ie, train, evalp, method="device", **kw)  # warm jit
    reps = 3 if quick else 5
    times = {"bruteforce": [], "device": []}
    for _ in range(reps):
        for method in times:
            t0 = time.perf_counter()
            evaluate_recall(ue, ie, train, evalp, method=method, **kw)
            times[method].append(time.perf_counter() - t0)
    ratios = sorted(b / d for b, d in zip(times["bruteforce"], times["device"]))
    med = ratios[len(ratios) // 2]
    for m in times:
        emit(f"recall_eval/I{I}/{m}", min(times[m]) * 1e6,
             f"evals_per_sec={1 / min(times[m]):.2f}")
    emit(f"recall_eval/I{I}/speedup", 0.0, f"device_vs_bruteforce={med:.2f}x")
    if results is not None:
        results["eval_u2i_100k"] = {
            "num_users": U, "num_items": I,
            "bruteforce_s": round(min(times["bruteforce"]), 3),
            "device_s": round(min(times["device"]), 3),
            "device_speedup_median": round(med, 3),
        }


def run(
    quick: bool = True,
    sizes: Optional[Sequence[int]] = None,
    out: Optional[str] = None,
) -> Dict:
    try:
        with open(_JSON_PATH) as f:
            results = json.load(f)
    except (OSError, ValueError):
        results = {}
    results["quick"] = quick
    retrieval_bench(quick, results, sizes=sizes)
    if sizes is None:  # explicit --sizes runs are arm smokes, skip e2e
        eval_e2e_bench(quick, results)
    with open(out or _JSON_PATH, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    grp = ap.add_mutually_exclusive_group()
    grp.add_argument("--quick", action="store_true", default=True,
                     help="fewer reps/queries, no 10M arm (default)")
    grp.add_argument("--full", action="store_true",
                     help="more reps + the 10M-item arm")
    ap.add_argument("--sizes", type=int, nargs="+", default=None,
                    help="run only these item-count arms (merge-updates "
                         "the JSON; skips the e2e eval arm)")
    ap.add_argument("--out", default=None,
                    help="write results here instead of BENCH_recall.json")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(quick=not args.full, sizes=args.sizes, out=args.out)
