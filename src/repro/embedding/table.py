"""Sharded embedding table — the TPU-native "parameter server" (§3.6).

The paper's parameter server is a key-value store of sparse embeddings:
workers *pull* rows at step start and *push* gradients for asynchronous
updates. Two SPMD equivalents coexist here:

- **Sharded pull/push**: the table's vocab axis is partitioned across the
  ``model`` mesh axis. ``ps_lookup`` under ``shard_map`` is the pull (masked
  local take + ``psum``), and its autodiff transpose is the push (scatter-add
  into the owning shard). No code needed — JAX differentiates ``ps_lookup``.
- **Gather→step→scatter** (the training hot path): per batch, the trainer
  deduplicates the touched ids host-side (``unique_pad_ids`` — PAD-padded in
  front to a power-of-two bucket so jit shapes stay stable), remaps the
  batch's ids onto rows of the gathered sub-table (``remap_ids``), pulls only
  those rows (``gather_rows``), differentiates w.r.t. the sub-table, and
  pushes the row-wise-AdaGrad-updated rows back with ``scatter_rows`` under
  buffer donation. Every step is O(unique ids), never O(num_nodes) — the
  faithful port of the PS's sparse pull/push (see
  ``embedding/optimizer.py`` for the update rule and
  ``train/trainer.py`` for the jitted step).

Lazy initialization is replaced by pre-allocated sharded tables (TPU memory
is statically planned); an optional ``init_mask`` preserves the "row never
seen" semantics for cold-start experiments.

Side information (§3.5): configurable sparse slots, each with multiple
values per node (texts/tags), embedded and **summed** with the ID embedding,
exactly as the paper trains side info. Slot tables participate in the same
gather→step→scatter contract: the unique slot-value ids of a batch are
bucketed and remapped exactly like node ids.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.utils.ragged import ragged_row_offsets


@dataclasses.dataclass(frozen=True)
class SlotSpec:
    name: str
    vocab_size: int
    max_values: int  # fixed-width padding of the ragged slot


@dataclasses.dataclass(frozen=True)
class EmbeddingConfig:
    num_nodes: int
    dim: int
    slots: Tuple[SlotSpec, ...] = ()
    dtype: str = "float32"
    pad_id: int = -1


def init_params(key: jax.Array, cfg: EmbeddingConfig) -> Dict[str, jnp.ndarray]:
    """Node-ID table plus one table per side-info slot."""
    keys = jax.random.split(key, 1 + len(cfg.slots))
    scale = 1.0 / np.sqrt(cfg.dim)
    params = {
        "node": jax.random.normal(keys[0], (cfg.num_nodes, cfg.dim), cfg.dtype) * scale
    }
    for k, slot in zip(keys[1:], cfg.slots):
        params[f"slot:{slot.name}"] = (
            jax.random.normal(k, (slot.vocab_size, cfg.dim), cfg.dtype) * scale
        )
    return params


def abstract_params(cfg: EmbeddingConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    out = {"node": jax.ShapeDtypeStruct((cfg.num_nodes, cfg.dim), cfg.dtype)}
    for slot in cfg.slots:
        out[f"slot:{slot.name}"] = jax.ShapeDtypeStruct(
            (slot.vocab_size, cfg.dim), cfg.dtype
        )
    return out


def param_specs(cfg: EmbeddingConfig, model_axis: str = "model") -> Dict[str, P]:
    """PS sharding: vocab rows over the model axis, dim replicated."""
    specs = {"node": P(model_axis, None)}
    for slot in cfg.slots:
        specs[f"slot:{slot.name}"] = P(model_axis, None)
    return specs


# ----------------------------------------------------------------- lookups
def lookup(table: jnp.ndarray, ids: jnp.ndarray, pad_id: int = -1) -> jnp.ndarray:
    """Plain masked gather (single-device / auto-sharded path).

    PAD ids return zero rows. Under pjit with a row-sharded table, XLA lowers
    this to the same gather+all-reduce pattern ``ps_lookup`` makes explicit.
    """
    safe = jnp.where(ids >= 0, ids, 0)
    rows = jnp.take(table, safe, axis=0)
    return jnp.where((ids >= 0)[..., None], rows, 0.0)


# ------------------------------------------------- unique-id (sparse) path
def unique_pad_ids(
    id_arrays: Sequence[np.ndarray], bucket: int = 0, min_bucket: int = 8
) -> np.ndarray:
    """Deduplicated touched ids, PAD-padded *in front* to a stable bucket.

    Host-side prologue of the gather→step→scatter contract: the returned
    array holds ``width - n`` leading PADs (-1) followed by the ``n`` unique
    non-PAD ids in ascending order. ``width`` is ``max(min_bucket, bucket)``
    doubled until it fits, so a caller that persists the width across batches
    recompiles the jitted step at most O(log n) times and then shapes are
    stable. PADs lead (rather than trail), so the real ids keep one fixed
    position per bucket width.
    """
    arrays = [np.asarray(a).reshape(-1) for a in id_arrays]
    flat = np.concatenate(arrays) if arrays else np.empty(0, np.int64)
    real = np.unique(flat)
    real = real[real >= 0]
    width = max(int(min_bucket), int(bucket))
    while width < len(real):
        width *= 2
    out = np.full(width, -1, dtype=np.int64)
    if len(real):
        out[width - len(real):] = real
    return out


def remap_ids(uniq: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Global ids -> row indices into ``gather_rows(table, uniq)``.

    Every non-PAD id must be present in ``uniq`` (guaranteed when ``uniq``
    came from ``unique_pad_ids`` over arrays that include ``ids``); PAD stays
    PAD so downstream masking is unchanged.
    """
    ids = np.asarray(ids, dtype=np.int64)
    real = uniq[uniq >= 0]
    if len(real) == 0:
        return np.full(ids.shape, -1, dtype=np.int64)
    offset = len(uniq) - len(real)
    loc = np.searchsorted(real, np.clip(ids, real[0], real[-1]))
    return np.where(ids >= 0, loc + offset, -1)


def gather_rows(table: jnp.ndarray, uniq: jnp.ndarray) -> jnp.ndarray:
    """Pull the touched rows: (bucket, dim). PAD slots clamp to row 0; their
    contents are never referenced by remapped ids and their updates are
    dropped by ``scatter_rows``."""
    return jnp.take(table, jnp.maximum(uniq, 0), axis=0)


def scatter_rows(
    table: jnp.ndarray, uniq: jnp.ndarray, rows: jnp.ndarray
) -> jnp.ndarray:
    """Push updated rows back: ``table[uniq] = rows`` with PAD slots dropped.

    PAD ids are remapped to ``num_rows`` (one past the end) because negative
    scatter indices wrap in JAX; ``mode="drop"`` then discards them. Under
    buffer donation this lowers to an in-place row write — O(bucket), not
    O(num_rows).
    """
    idx = jnp.where(uniq >= 0, uniq, table.shape[0])
    return table.at[idx].set(rows, mode="drop")


def slot_count_matrix(
    slot_indptr: np.ndarray,
    slot_values: np.ndarray,
    num_nodes: int,
    vocab_size: int,
    max_values: int,
) -> np.ndarray:
    """(num_nodes, vocab) float32 matrix of each node's slot-value counts.

    Row n counts the node's first ``max_values`` ragged values — the exact
    set ``pad_slot_values`` would emit — so ``counts[n] @ table`` equals the
    padded gather-and-sum. Built host-side once per table (vectorized
    ``np.add.at``); see ``embed_nodes_bag`` for how it replaces the per-value
    device gather.
    """
    counts = np.zeros((num_nodes, vocab_size), dtype=np.float32)
    starts = np.asarray(slot_indptr[:-1], dtype=np.int64)
    lens = np.minimum(slot_indptr[1:] - starts, max_values).astype(np.int64)
    if lens.sum():
        node_of, off = ragged_row_offsets(lens)
        np.add.at(counts, (node_of, slot_values[starts[node_of] + off]), 1.0)
    return counts


def ps_lookup(
    table: jnp.ndarray,
    ids: jnp.ndarray,
    mesh: Mesh,
    model_axis: str = "model",
    pad_id: int = -1,
) -> jnp.ndarray:
    """Explicit parameter-server pull via shard_map.

    ``table`` is row-sharded over ``model_axis``; ``ids`` replicated along it.
    Each shard serves the rows it owns; psum assembles the full rows. The VJP
    of this function is the "push": scatter-add of grads onto the owner shard.
    """
    num_shards = mesh.shape[model_axis]
    rows_per = table.shape[0] // num_shards

    def _local(local_table: jnp.ndarray, ids_: jnp.ndarray) -> jnp.ndarray:
        shard = jax.lax.axis_index(model_axis)
        lo = shard * rows_per
        local_idx = ids_ - lo
        owned = (ids_ >= lo) & (ids_ < lo + rows_per)
        safe = jnp.clip(local_idx, 0, rows_per - 1)
        out = jnp.take(local_table, safe, axis=0)
        out = jnp.where(owned[..., None], out, 0.0)
        return jax.lax.psum(out, model_axis)

    mapped = jax.shard_map(
        _local, mesh=mesh, in_specs=(P(model_axis, None), P()), out_specs=P(),
        check_vma=False,
    )
    return mapped(table, jnp.where(ids >= 0, ids, 0)) * (ids >= 0)[..., None]


def embed_nodes(
    params: Mapping[str, jnp.ndarray],
    ids: jnp.ndarray,
    slot_values: Optional[Mapping[str, jnp.ndarray]] = None,
    pad_id: int = -1,
) -> jnp.ndarray:
    """ID embedding + sum of side-info slot embeddings (paper §4.4 RQ3).

    ``slot_values[name]``: (..., max_values) padded value ids aligned with
    ``ids``. Multi-value slots are sum-pooled (bag-of-features).
    """
    h = lookup(params["node"], ids, pad_id)
    if slot_values:
        for name, vals in slot_values.items():
            tab = params[f"slot:{name}"]
            h = h + lookup(tab, vals, pad_id).sum(axis=-2)
    return h


def embed_nodes_bag(
    params: Mapping[str, jnp.ndarray],
    ids: jnp.ndarray,
    slot_counts: Mapping[str, jnp.ndarray],
    pad_id: int = -1,
) -> jnp.ndarray:
    """Side-info embedding via per-node value counts (embedding-bag form).

    ``slot_counts[name]``: (num_nodes, vocab) from ``slot_count_matrix``.
    Exactly equivalent to ``embed_nodes`` over the padded value lists the
    counts were built from — the gathered count row is zero for PAD ids, and
    ``counts_row @ table`` is the same truncated sum — but the per-value
    gather and its backward scatter-add become two GEMMs, which is much
    faster whenever dense count rows are affordable. Large-vocab slots
    should stay on ``embed_nodes`` (counts are dense per node here).
    """
    h = lookup(params["node"], ids, pad_id)
    for name, cmat in slot_counts.items():
        c = lookup(cmat, ids, pad_id)  # (..., vocab); zero row for PAD ids
        h = h + c @ params[f"slot:{name}"]
    return h


def embed_nodes_mixed(
    params: Mapping[str, jnp.ndarray],
    ids: jnp.ndarray,
    slot_values: Optional[Mapping[str, jnp.ndarray]] = None,
    slot_counts: Optional[Mapping[str, jnp.ndarray]] = None,
    pad_id: int = -1,
) -> jnp.ndarray:
    """ID embedding + side info with a per-slot bag/values split.

    Slots may arrive through either representation simultaneously: small
    vocabs as count-matrix GEMMs (``slot_counts``, the 'bag' form), large
    vocabs as padded value lists (``slot_values``) — the fallback the bag
    vocab guard (``core.model.Graph4RecConfig.bag_vocab_limit``) selects so
    no O(num_nodes x vocab) count matrix is ever materialized. A slot must
    appear in at most one of the two mappings.
    """
    h = lookup(params["node"], ids, pad_id)
    if slot_counts:
        for name, cmat in slot_counts.items():
            c = lookup(cmat, ids, pad_id)  # (..., vocab); zero row for PAD ids
            h = h + c @ params[f"slot:{name}"]
    if slot_values:
        for name, vals in slot_values.items():
            h = h + lookup(params[f"slot:{name}"], vals, pad_id).sum(axis=-2)
    return h


# --------------------------------------------------------------- side info
def pad_slot_values(
    slot_indptr: np.ndarray,
    slot_values: np.ndarray,
    ids: np.ndarray,
    max_values: int,
    pad_id: int = -1,
) -> np.ndarray:
    """Host-side: ragged slot values -> (len(ids), max_values) padded.

    Fully vectorized ragged-to-padded scatter: every (row, column) output
    position and its source position in ``slot_values`` are computed as flat
    index arrays, so the copy is one fancy-indexed assignment regardless of
    how many ids are requested.
    """
    ids = np.asarray(ids).reshape(-1)
    out = np.full((len(ids), max_values), pad_id, dtype=np.int64)
    valid = np.flatnonzero(ids >= 0)
    if len(valid) == 0:
        return out
    vids = ids[valid]
    starts = np.asarray(slot_indptr[vids], dtype=np.int64)
    lens = np.minimum(slot_indptr[vids + 1] - starts, max_values).astype(np.int64)
    if lens.sum() == 0:
        return out
    row_of, col = ragged_row_offsets(lens)
    out[valid[row_of], col] = slot_values[starts[row_of] + col]
    return out


def _pad_slot_values_loop(
    slot_indptr: np.ndarray,
    slot_values: np.ndarray,
    ids: np.ndarray,
    max_values: int,
    pad_id: int = -1,
) -> np.ndarray:
    """Reference per-node loop (seed implementation) for equivalence tests
    and the serial arm of benchmarks/bench_throughput.py."""
    ids = np.asarray(ids).reshape(-1)
    out = np.full((len(ids), max_values), pad_id, dtype=np.int64)
    for k, node in enumerate(ids):
        if node < 0:
            continue
        vals = slot_values[slot_indptr[node] : slot_indptr[node + 1]][:max_values]
        out[k, : len(vals)] = vals
    return out


# -------------------------------------------------------------- warm start
def save_table(path: str, params: Mapping[str, jnp.ndarray]) -> None:
    np.savez(path, **{k: np.asarray(v) for k, v in params.items()})


def load_table(path: str) -> Dict[str, np.ndarray]:
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def warm_start(
    params: Dict[str, jnp.ndarray], pretrained: Mapping[str, np.ndarray]
) -> Dict[str, jnp.ndarray]:
    """Inherit pre-trained sparse tables (paper §3.6 warm start).

    Any table present in ``pretrained`` with a matching shape replaces the
    fresh initialization; everything else (dense GNN weights) is untouched.
    """
    out = dict(params)
    for k, v in pretrained.items():
        if k in out and tuple(out[k].shape) == tuple(v.shape):
            out[k] = jnp.asarray(v, dtype=out[k].dtype)
    return out
