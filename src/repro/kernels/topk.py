"""Pallas TPU kernel: chunked matmul + streaming top-k (retrieval hot path).

Recall serving (U2I/UCF/ICF, paper §4.2) reduces to maximum-inner-product
search: score every query row against an item table and keep the K best.
Materializing the full (Q, I) similarity matrix is O(Q·I) HBM — 400 GB at
1M items × 100k users — so this kernel streams the item table through VMEM
in fixed chunks and carries a running (TQ, K) best-scores/best-ids state:
memory is O(TQ · (K + chunk)), independent of the item count.

Grid: (Q/TQ, I/chunk) with the chunk axis innermost. The output blocks for
a query tile map to the same (TQ, K) slab for every chunk step, so Pallas
keeps them VMEM-resident across the whole item sweep (the standard
revisited-output accumulation pattern); they double as the running state —
initialized at chunk 0, merged every step, final after the last chunk.

Selection is k rounds of (row max, first position holding it) over
[running best | current chunk]: ``lax.top_k`` has no Mosaic lowering, and
this reproduces its first-occurrence rule. Tie-break contract (shared with
the ``lax`` reference path and the numpy oracle in ``repro.retrieval.topk``):
on equal scores the lower item id wins — running entries hold earlier
(smaller) ids and come first.

``exclude`` masking: each query row carries a padded id list (-1 = empty
slot). The kernel keeps the best ``k + E`` candidates unmasked; XLA then
drops the row's excluded ids from them and keeps the best k — at most E
can go, so the survivors are exactly the top k of the non-excluded items.
This is how retrieval drops a user's training history on-device.

On CPU the kernel runs with interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# python float so the kernel body never captures a traced constant
NEG_INF = float("-inf")


def _topk_kernel(
    q_ref,  # (TQ, d)
    it_ref,  # (chunk, d)
    os_ref,  # (TQ, Kp) running / final best scores
    oi_ref,  # (TQ, Kp) running / final best item ids
    *,
    k: int,
    chunk: int,
    num_items: int,
):
    c = pl.program_id(1)
    kp = os_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        os_ref[...] = jnp.full_like(os_ref, NEG_INF)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    q = q_ref[...].astype(jnp.float32)
    it = it_ref[...].astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, it, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # exact f32, like the oracle
        preferred_element_type=jnp.float32,
    )  # (TQ, chunk)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    gid = c * chunk + col
    scores = jnp.where(gid < num_items, scores, NEG_INF)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)
    run_s, run_i = os_ref[...], oi_ref[...]
    tq = scores.shape[0]

    # Selection by k rounds of (max, first position holding it) over
    # [running | chunk] — the first-occurrence rule of ``lax.top_k``, which
    # Mosaic cannot lower. A taken slot leaves the alive masks, so -inf
    # slots are taken in order too, never twice.
    def pick(t, carry):
        alive_r, alive_c, best_s, best_i = carry  # int32 0/1 masks
        alive_r, alive_c = alive_r > 0, alive_c > 0
        sr = jnp.where(alive_r, run_s, NEG_INF)
        sc = jnp.where(alive_c, scores, NEG_INF)
        m = jnp.maximum(
            jnp.max(sr, axis=1, keepdims=True), jnp.max(sc, axis=1, keepdims=True)
        )  # (TQ, 1)
        pr = jnp.min(
            jnp.where(alive_r & (sr == m), kcol, kp), axis=1, keepdims=True
        )
        pc = jnp.min(
            jnp.where(alive_c & (sc == m), col, chunk), axis=1, keepdims=True
        )
        from_run = pr < kp
        rid = jnp.sum(jnp.where(kcol == pr, run_i, 0), axis=1, keepdims=True)
        ident = jnp.where(from_run, rid, c * chunk + pc)
        alive_r = alive_r & ~(from_run & (kcol == pr))
        alive_c = alive_c & ~(~from_run & (col == pc))
        best_s = jnp.where(kcol == t, m, best_s)
        best_i = jnp.where(kcol == t, ident, best_i)
        return (
            alive_r.astype(jnp.int32), alive_c.astype(jnp.int32), best_s, best_i
        )

    init = (
        jnp.broadcast_to((kcol < k).astype(jnp.int32), (tq, kp)),
        jnp.ones((tq, chunk), jnp.int32),
        jnp.full((tq, kp), NEG_INF, jnp.float32),
        jnp.full((tq, kp), -1, jnp.int32),
    )
    _, _, best_s, best_i = jax.lax.fori_loop(0, k, pick, init)
    os_ref[...] = best_s
    oi_ref[...] = best_i


def chunked_topk_pallas(
    queries: jnp.ndarray,  # (Q, d)
    items: jnp.ndarray,  # (I, d)
    k: int,
    exclude: jnp.ndarray = None,  # (Q, E) int32, -1 padded; None -> no masking
    item_chunk: int = 1024,
    tile_q: int = 128,
    interpret: bool = False,
):
    """Streaming top-k MIPS: (Q, k) float32 scores + (Q, k) int32 item ids.

    The kernel keeps the best ``k + E`` unmasked candidates; exclusion then
    drops at most E of them in XLA, so the best k survivors are exactly the
    top k of the non-excluded items, in the same tie order.
    """
    Q, d = queries.shape
    I = items.shape[0]
    if not 0 < k <= I:
        raise ValueError(f"k={k} must be in [1, num_items={I}]")
    E = 0 if exclude is None else exclude.shape[1]
    K = k + E
    Kp = -(-K // 128) * 128  # lane-dense running state
    tq = min(tile_q, -(-Q // 8) * 8)
    chunk = min(item_chunk, -(-I // 128) * 128)
    Qp = -(-Q // tq) * tq
    Ip = -(-I // chunk) * chunk
    if Qp != Q:
        queries = jnp.pad(queries, ((0, Qp - Q), (0, 0)))
    if Ip != I:
        items = jnp.pad(items, ((0, Ip - I), (0, 0)))
    grid = (Qp // tq, Ip // chunk)
    scores, ids = pl.pallas_call(
        functools.partial(_topk_kernel, k=K, chunk=chunk, num_items=I),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, c: (i, 0)),
            pl.BlockSpec((chunk, d), lambda i, c: (c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tq, Kp), lambda i, c: (i, 0)),
            pl.BlockSpec((tq, Kp), lambda i, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, Kp), jnp.float32),
            jax.ShapeDtypeStruct((Qp, Kp), jnp.int32),
        ],
        interpret=interpret,
    )(queries, items)
    scores, ids = scores[:Q, :K], ids[:Q, :K]
    if exclude is not None:
        hit = (ids[:, :, None] == exclude.astype(jnp.int32)[:, None, :]).any(-1)
        scores = jnp.where(hit, NEG_INF, scores)
        scores, pos = jax.lax.top_k(scores, k)
        ids = jnp.take_along_axis(ids, pos, axis=1)
    return scores, ids
