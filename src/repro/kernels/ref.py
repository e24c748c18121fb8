"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Tests sweep shapes/dtypes and assert the kernels (interpret=True on CPU)
match these references.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ------------------------------------------------------------------ seg_aggr
def seg_aggr_ref(
    x: jnp.ndarray,  # (N, F, D) neighbor features
    mask: jnp.ndarray,  # (N, F) bool validity
    mode: str = "mean",
) -> jnp.ndarray:
    """Masked segment aggregation over the neighbor axis -> (N, D)."""
    m = mask[..., None].astype(x.dtype)
    if mode == "sum":
        return (x * m).sum(axis=1)
    if mode == "mean":
        s = (x * m).sum(axis=1)
        c = jnp.maximum(m.sum(axis=1), 1.0)
        return s / c
    if mode == "max":
        neg = jnp.where(mask[..., None], x, NEG_INF)
        out = neg.max(axis=1)
        any_valid = mask.any(axis=1, keepdims=True)
        return jnp.where(any_valid, out, 0.0)
    raise ValueError(mode)


# ---------------------------------------------------------- window pairs
def window_pair_ids_ref(
    paths: jnp.ndarray,  # (B, L) int paths, PAD = -1
    positions,  # static (npos, 2) (src_col, dst_col) table
):
    """Skip-gram pair gather oracle -> ((B, npos) src, (B, npos) dst)."""
    pos = np.asarray(positions, dtype=np.int64).reshape(-1, 2)
    paths = paths.astype(jnp.int32)
    src = paths[:, pos[:, 0]]
    dst = paths[:, pos[:, 1]]
    valid = (src != -1) & (dst != -1)
    return jnp.where(valid, src, -1), jnp.where(valid, dst, -1)


# -------------------------------------------------------------- inbatch loss
def inbatch_loss_ref(
    h_src: jnp.ndarray, h_dst: jnp.ndarray, temperature: float = 1.0
) -> jnp.ndarray:
    """In-batch softmax CE with diagonal positives -> scalar mean loss."""
    logits = (h_src @ h_dst.T).astype(jnp.float32) / temperature
    labels = jnp.arange(h_src.shape[0])
    logz = jax.nn.logsumexp(logits, axis=-1)
    return (logz - logits[labels, labels]).mean()


def inbatch_loss_rows_ref(
    h_src: jnp.ndarray, h_dst: jnp.ndarray, temperature: float = 1.0
) -> jnp.ndarray:
    logits = (h_src @ h_dst.T).astype(jnp.float32) / temperature
    labels = jnp.arange(h_src.shape[0])
    logz = jax.nn.logsumexp(logits, axis=-1)
    return logz - logits[labels, labels]


# -------------------------------------------------------------- attention
def attention_ref(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Skv, K, hd)
    v: jnp.ndarray,  # (B, Skv, K, hd)
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
) -> jnp.ndarray:
    """GQA attention oracle with causal and sliding-window masking."""
    B, Sq, H, hd = q.shape
    Kh = k.shape[2]
    G = H // Kh
    qg = q.reshape(B, Sq, Kh, G, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) / np.sqrt(hd)
    qi = jnp.arange(Sq)[:, None] + q_offset
    ki = jnp.arange(k.shape[1])[None, :]
    ok = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    logits = jnp.where(ok[None, None, None], logits, NEG_INF)
    att = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", att, v)
    return out.reshape(B, Sq, H, hd)


# kernels/flash_attn.py exports the same attention contract under the flash
# name; the oracle is identical.
flash_attention_ref = attention_ref


# ----------------------------------------------------------------- topk MIPS
def chunked_topk_ref(
    queries: jnp.ndarray,  # (Q, d)
    items: jnp.ndarray,  # (I, d)
    k: int,
    exclude: Optional[jnp.ndarray] = None,  # (Q, E) int32, -1 padded
):
    """Dense top-k MIPS oracle -> ((Q, k) f32 scores, (Q, k) i32 ids).

    Tie-break matches the streaming kernel: on equal scores the lower item
    id wins (``lax.top_k`` keeps the first occurrence and ids ascend).
    """
    scores = jnp.dot(
        queries.astype(jnp.float32),
        items.astype(jnp.float32).T,
        preferred_element_type=jnp.float32,
    )  # (Q, I)
    if exclude is not None:
        gid = jnp.arange(items.shape[0], dtype=jnp.int32)
        hit = (exclude[:, :, None] == gid[None, None, :]).any(axis=1)
        scores = jnp.where(hit, float("-inf"), scores)
    best_s, best_i = jax.lax.top_k(scores, k)
    return best_s, best_i.astype(jnp.int32)


# ------------------------------------------------------------ IVF list topk
def ivf_list_topk_ref(
    queries: jnp.ndarray,  # (Q, d) float32
    codes: jnp.ndarray,  # (Ip, d) int8 cell-sorted quantized rows (DMA-padded)
    scales: jnp.ndarray,  # (Ip, 1) float32 per-row dequant scales
    starts: jnp.ndarray,  # (Q, P) int32 packed-row offset of each probed list
    lengths: jnp.ndarray,  # (Q, P) int32 true list lengths
    *,
    lpad: int,  # max list length: the fixed slice width gathered per probe
    shortlist: int,  # survivors kept per query (S)
    batch_size: int = 32,
):
    """Gather-then-score over CSR inverted lists -> per-query shortlist.

    For each (query, probe): slice ``lpad`` packed rows at ``starts``,
    dequantize (asymmetric distance: f32 query x int8 codes x per-row
    scale), mask slots past ``lengths`` to -inf, and keep the ``shortlist``
    best across all probes. Returns ((Q, S) f32 approx scores, (Q, S) i32
    packed-row indices, -1 for empty slots).

    Tie-break: candidates rank in flat (probe, within-list) order and
    ``lax.top_k`` keeps the first occurrence — the same order the Pallas
    kernel's [running | new chunk] merge preserves inductively. Lists
    longer than ``lpad`` are truncated to ``lpad`` entries (the builder
    guarantees ``lengths <= lpad``).

    This is also the production XLA path on non-TPU backends (``lax.map``
    over ``batch_size`` query blocks bounds the gather working set), not
    just the kernel oracle.
    """
    off = jnp.arange(lpad, dtype=jnp.int32)

    def one(args):
        q, st, ln = args  # (d,), (P,), (P,)
        rows = st[:, None] + off[None, :]  # (P, lpad)
        valid = off[None, :] < ln[:, None]
        safe = jnp.where(valid, rows, 0)
        c = codes[safe].astype(jnp.float32)  # (P, lpad, d)
        sc = scales[safe][..., 0]  # (P, lpad)
        s = jnp.einsum("pld,d->pl", c, q.astype(jnp.float32)) * sc
        s = jnp.where(valid, s, float("-inf")).reshape(-1)
        r = jnp.where(valid, rows, -1).reshape(-1)
        best, pos = jax.lax.top_k(s, shortlist)
        return best, r[pos]
    return jax.lax.map(
        one, (queries, starts, lengths),
        batch_size=min(batch_size, queries.shape[0]),
    )


# ---------------------------------------------------------------- table rows
def gather_cols_ref(table_t: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Columns ``ids`` of a (D, N) table transpose -> (D, B); zeros where an
    id is PAD (< 0) or lies in the last, partial block of 128 rows."""
    ok = (ids >= 0) & (ids < table_t.shape[1] // 128 * 128)
    cols = jnp.take(table_t, jnp.where(ok, ids, 0), axis=1)
    return jnp.where(ok[None, :], cols, 0)


def scatter_cols_ref(
    table_t: jnp.ndarray, ids: jnp.ndarray, cols_t: jnp.ndarray
) -> jnp.ndarray:
    """``table_t`` with column ``ids[j]`` set to column j of ``cols_t``; PAD
    ids and ids in the last, partial block of 128 rows are skipped."""
    n = table_t.shape[1]
    ok = (ids >= 0) & (ids < n // 128 * 128)
    return table_t.at[:, jnp.where(ok, ids, n)].set(cols_t, mode="drop")
