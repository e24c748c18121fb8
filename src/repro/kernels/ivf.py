"""Pallas TPU kernel: IVF gather-then-score over packed inverted lists.

The IVF search hot loop is "for each (query, probed cell): fetch that
cell's packed quantized rows and score them against the query". The list
offsets live in SMEM, so each grid step DMAs its queries' own list slices
of the int8 code table straight from HBM into VMEM scratch — no per-call
upload, no dense (nlist, max_len) padding, no (Q, C, d) candidate tensor.

Grid: (Q/TQ, nprobe) over query tiles of TQ = 8 rows (the f32 sublane
tile). Step (t, p) starts one DMA per query of the tile (the codes of its
p-th probed list, from the enclosing 8-row HBM tile on — Mosaic slices
whole tiles only), then scores each landed slab against its query on the
MXU and writes that query's lane-dense row of the ``(nprobe, Q, Lp)``
raw-dot slab. The codes stay int8 in HBM and VMEM; only the working
slab is ever widened to f32.

XLA finishes each block of queries: per-row dequant scale, the -inf mask
outside each list's true rows, and the selection (``lax.top_k`` has no
Mosaic lowering). Slots flatten in (probe, packed row) order, so
``top_k``'s first-occurrence rule breaks ties exactly like
``ref.ivf_list_topk_ref``'s flat top-k (the conformance oracle). The exact
re-rank stage above this kernel re-sorts survivors by item id, so the
end-to-end lower-id-wins contract never depends on probe order.

On CPU the kernel runs with interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
# query rows per grid step (f32 sublane tile)
TILE_Q = 8
# queries per kernel call: bounds the (nprobe, block, Lp) score slab
BLOCK_Q = 512


def dma_rows(lpad: int) -> int:
    """Rows each per-probe DMA moves. Mosaic only slices whole 8-row HBM
    tiles, so a list's slab starts at its offset rounded down to a tile
    and spans ``lpad`` plus that misalignment, rounded up to a tile. The
    code table must carry this many rows of padding past its last list."""
    return -(-lpad // 8) * 8 + 8


def lane_pad(codes: np.ndarray) -> np.ndarray:
    """Zero-pad the code table's columns to whole 128-lane tiles, once at
    upload: Mosaic DMAs only whole lane tiles, so a d = 64 int8 row cannot
    be sliced out of an unpadded table. Zero columns add nothing to a dot."""
    d = codes.shape[1]
    return np.pad(codes, ((0, 0), (0, -(-d // 128) * 128 - d)))


def _ivf_score_kernel(
    starts_ref,  # (TQ, P) SMEM: packed-row offset per (query, probe)
    q_ref,  # (TQ, d) query tile
    codes_ref,  # (Ip, d) int8 code table, HBM/ANY
    o_ref,  # (1, TQ, Lp) raw dots of probe p for the tile
    codes_vmem,  # (TQ, Lp, d) int8 scratch: the DMA landing slabs
    sems,  # (TQ,) DMA semaphores
    *,
    rows: int,
):
    p = pl.program_id(1)
    tq = codes_vmem.shape[0]
    copies = []
    for j in range(tq):
        start = pl.multiple_of(starts_ref[j, p] // 8 * 8, 8)
        cp = pltpu.make_async_copy(
            codes_ref.at[pl.ds(start, rows), :],
            codes_vmem.at[j, pl.ds(0, rows), :],
            sems.at[j],
        )
        cp.start()
        copies.append(cp)
    q = q_ref[...].astype(jnp.float32)  # (TQ, d)
    for j, cp in enumerate(copies):
        cp.wait()
        # scratch rows past ``rows`` hold stale data; the caller's length
        # mask drops every slot past the list's true length
        o_ref[0, j : j + 1, :] = jax.lax.dot_general(
            q[j : j + 1], codes_vmem[j].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # (1, Lp)


def _score_block(q, starts, codes, *, lpad, lp, interpret):
    """(B, d) queries, (B, P) probed offsets -> (P, B, Lp) raw dots."""
    B, d = q.shape
    P = starts.shape[1]
    assert B % TILE_Q == 0, (B, TILE_Q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(B // TILE_Q, P),
        in_specs=[
            pl.BlockSpec((TILE_Q, P), lambda t, p: (t, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE_Q, d), lambda t, p: (t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, TILE_Q, lp), lambda t, p: (p, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((TILE_Q, lp, d), codes.dtype),
            pltpu.SemaphoreType.DMA((TILE_Q,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_ivf_score_kernel, rows=dma_rows(lpad)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, B, lp), jnp.float32),
        interpret=interpret,
    )(starts, q, codes)


def ivf_list_topk_pallas(
    queries: jnp.ndarray,  # (Q, d) float32
    codes: jnp.ndarray,  # (Ip, d) int8; Ip >= max(starts) + dma_rows(lpad)
    scales: jnp.ndarray,  # (Ip, 1) float32
    starts: jnp.ndarray,  # (Q, P) int32
    lengths: jnp.ndarray,  # (Q, P) int32, <= lpad
    *,
    lpad: int,
    shortlist: int,
    interpret: bool = False,
):
    """Gather-then-score kernel + XLA selection -> per-query shortlist.

    Returns ((Q, S) f32 approx scores, (Q, S) i32 packed-row indices, -1
    for empty slots). Contract matches ``ref.ivf_list_topk_ref``; the
    builder guarantees the code table carries ``dma_rows(lpad)`` rows of
    zero padding so the fixed-width DMA slice never reads out of bounds.
    Queries run in blocks of ``BLOCK_Q``, so the scored slab stays
    O(BLOCK_Q * nprobe * lpad) whatever the query count.
    """
    Q, d = queries.shape
    P = starts.shape[1]
    dc = codes.shape[1]  # >= d: lane-padded tables score zeros past d
    lp = -(-dma_rows(lpad) // 128) * 128
    bq = min(BLOCK_Q, -(-Q // TILE_Q) * TILE_Q)
    Qp = -(-Q // bq) * bq
    pad = ((0, Qp - Q), (0, 0))
    # padded query rows probe nothing (length 0) and are sliced off
    q = jnp.pad(queries.astype(jnp.float32), ((0, Qp - Q), (0, dc - d)))
    q = q.reshape(Qp // bq, bq, dc)
    st = jnp.pad(starts.astype(jnp.int32), pad).reshape(Qp // bq, bq, P)
    ln = jnp.pad(lengths.astype(jnp.int32), pad).reshape(Qp // bq, bq, P)
    off = jnp.arange(lp, dtype=jnp.int32)
    sc = scales.reshape(-1)

    def block(args):
        qb, sb, lb = args
        raw = _score_block(qb, sb, codes, lpad=lpad, lp=lp, interpret=interpret)
        raw = jnp.transpose(raw, (1, 0, 2))  # (B, P, Lp)
        # slot c of a slab is packed row (start rounded down to a tile) + c
        rows = (sb // 8 * 8)[:, :, None] + off[None, None, :]
        rel = rows - sb[:, :, None]
        valid = (rel >= 0) & (rel < lb[:, :, None])
        rows = jnp.where(valid, rows, -1)
        # dequant after the dot, as the oracle does: (codes . q) * scale
        s = jnp.where(valid, raw * sc[jnp.maximum(rows, 0)], NEG_INF)
        best, pos = jax.lax.top_k(s.reshape(bq, P * lp), shortlist)
        return best, jnp.take_along_axis(rows.reshape(bq, P * lp), pos, axis=1)

    s, r = jax.lax.map(block, (q, st, ln))
    return s.reshape(Qp, shortlist)[:Q], r.reshape(Qp, shortlist)[:Q]
