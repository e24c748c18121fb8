"""Exact top-k retrieval: numpy oracle + device chunked/streaming paths.

Three implementations of the same maximum-inner-product search, all under
one tie-break contract (higher score first; on equal scores the lower item
id wins) so they are interchangeable and testable against each other:

- ``brute_force_topk``: numpy reference — materializes the (Q, I) score
  block. O(Q·I) memory; retained as the test oracle and the seed-equivalent
  baseline arm of ``benchmarks/bench_recall.py``.
- ``chunked_topk``: jitted ``lax.scan`` over item chunks with a running
  (Q, k) best state — O(Q·(k + chunk)) device memory regardless of the item
  count, which is what lets recall evaluation scale to million-item tables.
- ``backend="pallas"``: the fused Pallas kernel (kernels/topk.py), same
  streaming structure with the chunk sweep as the inner grid axis.

``exclude`` is a (Q, E) padded id matrix (-1 = empty slot): per query, the
listed item ids score -inf — how a user's training history is dropped
during recall without a host-side post-filter.
"""
from __future__ import annotations

import functools
import weakref
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.lint.sanitizer import host_array

NEG_INF = np.float32(-np.inf)

# Device-resident copies of recently-searched item tables, keyed by the
# host array's identity (+ shape/layout knobs). Retrieval callers reuse one
# corpus across thousands of query batches; before this cache every call
# re-shipped the full table host->device (the BENCH_recall "IVF loses to
# brute force" bug had the same root). Entries are evicted when the host
# array is garbage-collected (weakref) and the table is bounded FIFO.
_DEVICE_TABLE_CACHE: dict = {}
_DEVICE_TABLE_CACHE_MAX = 8


def _cached_device_table(arr: np.ndarray, tag, make):
    """jax.device_put(make(arr)) memoized on the host array's identity."""
    key = (id(arr), arr.shape, arr.dtype.str, tag)
    hit = _DEVICE_TABLE_CACHE.get(key)
    if hit is not None:
        return hit[1]
    dev = jax.device_put(make(arr))
    ref = weakref.ref(arr, lambda _, k=key: _DEVICE_TABLE_CACHE.pop(k, None))
    _DEVICE_TABLE_CACHE[key] = (ref, dev)
    while len(_DEVICE_TABLE_CACHE) > _DEVICE_TABLE_CACHE_MAX:
        _DEVICE_TABLE_CACHE.pop(next(iter(_DEVICE_TABLE_CACHE)))
    return dev


def pad_id_rows(rows, width: int = 0, pad: int = -1) -> np.ndarray:
    """Ragged id lists -> (len(rows), width) padded int32 matrix."""
    width = max(width, 1, *(len(r) for r in rows)) if rows else max(width, 1)
    out = np.full((len(rows), width), pad, dtype=np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def _deterministic_topk_rows(scores: np.ndarray, k: int) -> np.ndarray:
    """Row-wise top-k positions, ties broken by ascending index."""
    n = scores.shape[-1]
    k = min(k, n)
    # argsort of -score is not tie-stable; lexsort on (index, -score) is.
    idx = np.lexsort(
        (np.broadcast_to(np.arange(n), scores.shape), -scores), axis=-1
    )
    return idx[..., :k]


def brute_force_topk(
    queries: np.ndarray,
    items: np.ndarray,
    k: int,
    exclude: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Numpy oracle: full (Q, I) scores, then deterministic row top-k.

    Returns ((Q, k) float32 scores, (Q, k) int32 ids). Shared filler
    contract with every device path: slots with no surviving item (k
    exceeds the non-excluded count) come back as (-inf, -1) — a -inf score
    never carries a real id, so consumers can filter on ``ids >= 0``.
    """
    q = host_array(queries, dtype=np.float32)
    it = host_array(items, dtype=np.float32)
    if not 0 < k <= it.shape[0]:
        raise ValueError(f"k={k} must be in [1, num_items={it.shape[0]}]")
    scores = q @ it.T
    if exclude is not None:
        ex = host_array(exclude)
        rows = np.repeat(np.arange(ex.shape[0]), ex.shape[1])
        cols = ex.reshape(-1)
        valid = cols >= 0
        scores[rows[valid], cols[valid]] = NEG_INF
    ids = _deterministic_topk_rows(scores, k)
    top = np.take_along_axis(scores, ids, axis=-1)
    return top, np.where(np.isneginf(top), -1, ids).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "num_items"))
def _chunked_topk_scan(queries, items3, exclude, *, k, chunk, num_items):
    """(Q, d) x (nchunks, chunk, d) -> streaming exact top-k (lax.scan).

    Per chunk: score the block, drop padded columns, -inf the excluded ids
    this chunk owns (a scatter — O(Q·E), not the O(Q·E·chunk) broadcast
    compare), reduce the chunk to its local top-k (top_k straight on the
    score block: no concat/gather on the wide axis), then fold it into the
    running (Q, k) best via a top-k over 2k candidates. Live memory is
    O(Q·(chunk + k)) — independent of the item count.

    Tie-break: ``lax.top_k`` keeps the first occurrence of a tied value, so
    in-chunk ties resolve to the lower id, and putting the running state
    first in the 2k merge makes earlier chunks (smaller ids) win globally —
    the same lower-id-wins contract as the numpy oracle.
    """
    Q = queries.shape[0]
    q32 = queries.astype(jnp.float32)
    rows = jnp.arange(Q, dtype=jnp.int32)[:, None]
    init = (
        jnp.full((Q, k), -jnp.inf, jnp.float32),
        jnp.full((Q, k), -1, jnp.int32),
    )

    def body(carry, inp):
        ci, chunk_items = inp
        best_s, best_i = carry
        base = ci * chunk
        # exact f32 dots: the TPU's default f32 matmul precision is reduced
        scores = jnp.matmul(
            q32, chunk_items.astype(jnp.float32).T,
            precision=jax.lax.Precision.HIGHEST,
        )  # (Q, chunk)
        gid = base + jnp.arange(chunk, dtype=jnp.int32)
        scores = jnp.where(gid[None, :] < num_items, scores, -jnp.inf)
        # excluded ids owned by this chunk -> -inf via a dropped scatter
        col = jnp.where(
            (exclude >= base) & (exclude < base + chunk), exclude - base, chunk
        )
        scores = scores.at[rows, col].set(-jnp.inf, mode="drop")
        c_s, pos = jax.lax.top_k(scores, k)  # chunk-local top-k
        all_s = jnp.concatenate([best_s, c_s], axis=1)  # (Q, 2k)
        all_i = jnp.concatenate([best_i, base + pos.astype(jnp.int32)], axis=1)
        best_s, mpos = jax.lax.top_k(all_s, k)
        return (best_s, jnp.take_along_axis(all_i, mpos, axis=1)), None

    n = items3.shape[0]
    (best_s, best_i), _ = jax.lax.scan(
        body, init, (jnp.arange(n, dtype=jnp.int32), items3)
    )
    return best_s, best_i


def chunked_topk(
    queries,
    items,
    k: int,
    exclude: Optional[np.ndarray] = None,
    item_chunk: int = 8192,
    query_chunk: int = 0,
    backend: str = "ref",
) -> Tuple[np.ndarray, np.ndarray]:
    """Device streaming top-k; bitwise-matching drop-in for the oracle.

    ``backend="ref"`` is the jitted ``lax.scan`` path; ``"pallas"`` routes
    through the fused kernel (interpret mode off-TPU). ``query_chunk`` > 0
    additionally sweeps queries in fixed-shape host-side blocks so one call
    never holds more than (query_chunk, k + item_chunk) scores — the shape
    the jit caches, padded on the last block.
    """
    q = host_array(queries, dtype=np.float32)
    it = host_array(items, dtype=np.float32)
    Q, I = q.shape[0], it.shape[0]
    if not 0 < k <= I:
        raise ValueError(f"k={k} must be in [1, num_items={I}]")
    if item_chunk <= 0:
        raise ValueError(f"item_chunk must be positive, got {item_chunk}")
    if query_chunk < 0:
        raise ValueError(
            f"query_chunk must be >= 0 (0 disables query chunking), "
            f"got {query_chunk}"
        )
    if exclude is not None:
        exclude = host_array(exclude, dtype=np.int32)

    if query_chunk and Q > query_chunk:
        out_s = np.empty((Q, k), np.float32)
        out_i = np.empty((Q, k), np.int32)
        for lo in range(0, Q, query_chunk):
            hi = min(lo + query_chunk, Q)
            qb = q[lo:hi]
            exb = exclude[lo:hi] if exclude is not None else None
            if hi - lo < query_chunk:  # pad to the cached jit shape
                pad = query_chunk - (hi - lo)
                qb = np.pad(qb, ((0, pad), (0, 0)))
                if exb is not None:
                    exb = np.pad(exb, ((0, pad), (0, 0)), constant_values=-1)
            s, i = chunked_topk(
                qb, it, k, exclude=exb, item_chunk=item_chunk, backend=backend
            )
            out_s[lo:hi], out_i[lo:hi] = s[: hi - lo], i[: hi - lo]
        return out_s, out_i

    if backend == "pallas":
        from repro.kernels import ops

        ex = None if exclude is None else jax.device_put(exclude)
        dit = _cached_device_table(it, "flat", lambda a: a)
        s, i = ops.streaming_topk(
            jax.device_put(q), dit, k, exclude=ex, item_chunk=item_chunk
        )
        s, i = host_array(s), host_array(i)
        return s, np.where(np.isneginf(s), -1, i)
    if backend != "ref":
        raise ValueError(f"unknown topk backend {backend!r}")

    chunk = max(min(item_chunk, I), k)  # phase-1 keeps k per chunk
    Ip = -(-I // chunk) * chunk

    def _blocks(a: np.ndarray) -> np.ndarray:
        if Ip != I:
            a = np.pad(a, ((0, Ip - I), (0, 0)))
        return a.reshape(Ip // chunk, chunk, -1)

    items3 = _cached_device_table(it, ("scan", chunk), _blocks)
    ex = (
        jnp.full((Q, 1), -1, jnp.int32)
        if exclude is None
        else jax.device_put(exclude)
    )
    s, i = _chunked_topk_scan(
        jax.device_put(q), items3, ex, k=k, chunk=chunk, num_items=I
    )
    s, i = host_array(s), host_array(i)
    return s, np.where(np.isneginf(s), -1, i)
