"""Vectorised generator for the UB deployment (Taobao UserBehavior scale).

Semantics follow ``repro.graph.generator`` (users and items in latent
interest clusters, items drawn Zipf-by-rank inside the user's cluster with
probability ``affinity`` and uniformly otherwise, a behaviour mix at fixed
totals, and a per-user temporal 80/10/10 split), written as whole-array
NumPy so that 100 million behaviours generate in about a minute:

- per-user activity is log-normal around the published mean, allocated so
  that the behaviour totals are exact;
- each behaviour's type comes from one permutation of the exact totals;
- a user's events are iid given the user, so ordering them by iid
  timestamps is a uniformly random order; the block order *is* that order,
  and the split takes the first 80% of each block as train, the next 10%
  as validation and the rest as test (``generator.generate``'s
  ``max(1, int(0.8 n))`` / ``int(0.1 n)`` rule);
- the Zipf rank is redrawn where it falls past the cluster's pool, so the
  in-cluster popularity is a Zipf law truncated at the pool size (the
  seed generator clamps such draws onto the last item instead, which
  piles about a tenth of a cluster's traffic there at this pool size).

Only the train split becomes graph edges. The CSR arrays are cached under
the harness's cache directory, keyed by the deployment's parameters and a
hash of this file, so a later run loads them instead of generating.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

BEHAVIOURS = ("click", "buy", "cart", "fav")
SPLIT = (0.8, 0.1)  # train, validation; test is the rest


def cache_key(deployment: Dict) -> str:
    src = Path(__file__).read_bytes()
    blob = json.dumps(deployment, sort_keys=True).encode() + src
    return hashlib.sha256(blob).hexdigest()[:16]


def _activity(rng: np.random.Generator, users: int, total: int,
              sigma: float) -> np.ndarray:
    """Events per user: log-normal weights, integer counts summing to
    ``total`` exactly (largest remainders), at least one per user."""
    w = rng.lognormal(0.0, sigma, users)
    share = w / w.sum() * (total - users)
    n = np.floor(share).astype(np.int64)
    short = (total - users) - int(n.sum())
    if short:
        n[np.argsort(share - n)[::-1][:short]] += 1
    return n + 1


def _truncated_zipf(rng: np.random.Generator, a: float,
                    limit: np.ndarray) -> np.ndarray:
    """Ranks r >= 0 with P(r) proportional to (r + 1)^-a, r < limit."""
    r = rng.zipf(a, size=limit.shape[0]) - 1
    bad = np.flatnonzero(r >= limit)
    while bad.size:
        r[bad] = rng.zipf(a, size=bad.size) - 1
        bad = bad[r[bad] >= limit[bad]]
    return r


def generate(dep: Dict) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Generate the deployment: (arrays, meta).

    ``arrays`` holds ``<relation>.indptr`` (int64, N + 1) and
    ``<relation>.indices`` (int32, global ids) for the forward ``u2<b>2i``
    and reverse ``i2<b>2u`` relation of every behaviour; users occupy
    global ids [0, U) and items [U, U + I).
    """
    t0 = time.perf_counter()
    rng = np.random.default_rng(int(dep["data_seed"]))
    U, I = int(dep["num_users"]), int(dep["num_items"])
    N = U + I
    C = int(dep["num_clusters"])
    totals = np.array([int(dep["behaviors"][b]) for b in BEHAVIOURS], np.int64)
    T = int(totals.sum())

    user_cluster = rng.integers(0, C, U).astype(np.int32)
    item_cluster = rng.integers(0, C, I).astype(np.int32)
    pool = np.argsort(item_cluster, kind="stable").astype(np.int32)
    pool_len = np.bincount(item_cluster, minlength=C)
    pool_start = np.zeros(C, np.int64)
    np.cumsum(pool_len[:-1], out=pool_start[1:])

    n_user = _activity(rng, U, T, float(dep["activity_sigma"]))
    user = np.repeat(np.arange(U, dtype=np.int32), n_user)
    beh = rng.permutation(np.repeat(np.arange(len(BEHAVIOURS), dtype=np.int8),
                                    totals))

    item = np.empty(T, np.int32)
    inside = rng.random(T, dtype=np.float32) < float(dep["affinity"])
    c = user_cluster[user[inside]]
    inside_ok = pool_len[c] > 0
    c = c[inside_ok]
    rank = _truncated_zipf(rng, float(dep["zipf_a"]), pool_len[c])
    pos_in = np.flatnonzero(inside)
    item[pos_in[inside_ok]] = pool[pool_start[c] + rank]
    del c, rank
    outside = np.ones(T, bool)
    outside[pos_in[inside_ok]] = False
    del pos_in, inside, inside_ok
    item[outside] = rng.integers(0, I, int(outside.sum()), dtype=np.int32)
    del outside

    # per-user temporal split over the block order (see module docstring)
    block_start = np.zeros(U, np.int64)
    np.cumsum(n_user[:-1], out=block_start[1:])
    n_tr = np.maximum(1, (SPLIT[0] * n_user).astype(np.int64))
    n_va = (SPLIT[1] * n_user).astype(np.int64)
    pos = np.arange(T, dtype=np.int64) - np.repeat(block_start, n_user)
    train = pos < np.repeat(n_tr, n_user)
    del pos

    arrays: Dict[str, np.ndarray] = {}
    per_user_train = np.zeros(U, np.int64)
    for bi, b in enumerate(BEHAVIOURS):
        m = train & (beh == bi)
        u_b, i_b = user[m], item[m]
        per_user_train += np.bincount(u_b, minlength=U)
        deg_u = np.bincount(u_b, minlength=U)
        fwd = np.zeros(N + 1, np.int64)
        np.cumsum(deg_u, out=fwd[1 : U + 1])
        fwd[U + 1 :] = fwd[U]
        arrays[f"u2{b}2i.indptr"] = fwd
        arrays[f"u2{b}2i.indices"] = (i_b + U).astype(np.int32)
        order = np.argsort(i_b, kind="stable")
        deg_i = np.bincount(i_b, minlength=I)
        rev = np.zeros(N + 1, np.int64)
        np.cumsum(deg_i, out=rev[U + 1 :])
        arrays[f"i2{b}2u.indptr"] = rev
        arrays[f"i2{b}2u.indices"] = u_b[order].astype(np.int32)
        del m, u_b, i_b, order

    meta = {
        "num_users": U, "num_items": I, "events": T,
        "train_events": int(train.sum()),
        "val_events": int(n_va.sum()),
        "behaviour_totals": {b: int(t) for b, t in zip(BEHAVIOURS, totals)},
        "max_user_events": int(n_user.max()),
        "max_user_train": int(per_user_train.max()),
        "generate_s": time.perf_counter() - t0,
    }
    meta["max_history"] = max_unique_history(arrays, U)
    return arrays, meta


def user_history(arrays: Dict[str, np.ndarray], U: int,
                 users: np.ndarray) -> list:
    """Sorted unique local item ids each user has in the train split, over
    every behaviour (what ``core.recall.evaluate_recall`` excludes)."""
    out = []
    for u in np.asarray(users, np.int64):
        parts = [
            arrays[f"u2{b}2i.indices"][
                arrays[f"u2{b}2i.indptr"][u] : arrays[f"u2{b}2i.indptr"][u + 1]
            ]
            for b in BEHAVIOURS
        ]
        out.append(np.unique(np.concatenate(parts)).astype(np.int64) - U)
    return out


def max_unique_history(arrays: Dict[str, np.ndarray], U: int) -> int:
    """Longest unique train history over all users. Users are visited in
    descending event count and the scan stops once no later user can
    exceed the best, so only the heaviest few are deduplicated."""
    n = sum(np.diff(arrays[f"u2{b}2i.indptr"][: U + 1]) for b in BEHAVIOURS)
    best = 0
    for u in np.argsort(n, kind="stable")[::-1]:
        if n[u] <= best:
            break
        best = max(best, len(user_history(arrays, U, [u])[0]))
    return best


def load_or_generate(dep: Dict, cache_root: Path,
                     log=print) -> Tuple[Dict[str, np.ndarray], Dict]:
    """The deployment's arrays from the cache, generating them on a miss.

    The cache entry is written under a temporary name and renamed into
    place, so a run that dies mid-write leaves no half entry behind.
    """
    d = Path(cache_root) / f"ub-{cache_key(dep)}"
    if not (d / "meta.json").exists():
        arrays, meta = generate(dep)
        tmp = d.with_name(d.name + f".tmp{os.getpid()}")
        shutil.rmtree(tmp, ignore_errors=True)
        tmp.mkdir(parents=True)
        for name, a in arrays.items():
            np.save(tmp / f"{name}.npy", a)
        (tmp / "meta.json").write_text(json.dumps(meta, indent=1))
        try:
            os.rename(tmp, d)
        except OSError:  # another run finished first: keep its entry
            shutil.rmtree(tmp, ignore_errors=True)
        log(f"[data] generated {d.name} in {meta['generate_s']:.3f}s")
        return arrays, meta
    meta = json.loads((d / "meta.json").read_text())
    arrays = {
        p.name[: -len(".npy")]: np.load(p) for p in sorted(d.glob("*.npy"))
    }
    return arrays, meta
