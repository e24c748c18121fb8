"""Row-wise sparse optimizers for the embedding tables (PS-side updates).

The paper's parameter server pulls the rows a batch touches and pushes only
their gradients back. This module implements the PS-side update rule —
row-wise AdaGrad, one accumulator per row (the standard PS trick: 1/dim the
memory of full AdaGrad) — in the two forms the trainer uses:

- **Scatter form** (``rowwise_adagrad_scatter_update``) — the
  gather→step→scatter contract: gradients arrive as (bucket, dim) blocks
  w.r.t. the *gathered sub-table* (``embedding.table.gather_rows`` over the
  batch's unique ids), the per-row accumulators for the same rows are
  gathered, updated and scattered back alongside the parameter rows, and PAD
  bucket slots (id < 0, zero grads) are dropped at the scatter. O(unique
  ids) per step regardless of table size; with buffer donation the scatter
  is an in-place row write.
- **Dense form** (``rowwise_adagrad_update`` here, and the optax-style
  ``train.optimizer.rowwise_adagrad``) — the same rule applied to a full
  (num_nodes, dim) gradient. Untouched rows have zero grads (the scatter-add
  cotangent of the gather), so the dense form is mathematically identical to
  the scatter form at O(num_nodes) cost; it remains as the reference /
  fallback path (``TrainerConfig.sparse_updates=False``) and the equivalence
  oracle for tests.
"""
from __future__ import annotations

from typing import Callable, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.embedding.table import gather_rows, scatter_rows


class RowAdagradState(NamedTuple):
    accum: Dict[str, jnp.ndarray]  # per-table (rows, 1) accumulators


def rowwise_adagrad_init(
    params: Mapping[str, jnp.ndarray], init_accum: float = 0.0
) -> RowAdagradState:
    return RowAdagradState(
        accum={
            k: jnp.full((v.shape[0], 1), init_accum, v.dtype)
            for k, v in params.items()
        }
    )


def rowwise_adagrad_update(
    params: Mapping[str, jnp.ndarray],
    grads: Mapping[str, jnp.ndarray],
    state: RowAdagradState,
    lr: float = 0.1,
    eps: float = 1e-8,
) -> Tuple[Dict[str, jnp.ndarray], RowAdagradState]:
    """Dense reference form: full-table grads, every row updated."""
    new_params: Dict[str, jnp.ndarray] = {}
    new_accum: Dict[str, jnp.ndarray] = {}
    for k, p in params.items():
        g = grads[k]
        acc = state.accum[k] + jnp.mean(g * g, axis=-1, keepdims=True)
        new_params[k] = p - lr * g / (jnp.sqrt(acc) + eps)
        new_accum[k] = acc
    return new_params, RowAdagradState(accum=new_accum)


def rowwise_adagrad_scatter_update(
    params: Mapping[str, jnp.ndarray],
    sub_grads: Mapping[str, jnp.ndarray],
    uniq: Mapping[str, jnp.ndarray],
    state: RowAdagradState,
    lr: float = 0.1,
    eps: float = 1e-8,
    rows: Optional[Mapping[str, jnp.ndarray]] = None,
    scatter: Optional[Mapping[str, Callable]] = None,
) -> Tuple[Dict[str, jnp.ndarray], RowAdagradState]:
    """Scatter form: apply the row-wise rule to the touched rows only.

    ``sub_grads[k]``: (bucket, dim) gradient w.r.t.
    ``gather_rows(params[k], uniq[k])``. Parameter and accumulator rows at
    ``uniq[k]`` are gathered, stepped, and scattered back; PAD slots
    (``uniq[k] < 0``) carry zero grads by construction (no remapped id points
    at them) and are dropped by the scatter, so padded buckets never perturb
    the table. ``rows`` passes the gathered rows where the caller holds
    them already, and ``scatter`` a table's own row scatter (default
    ``scatter_rows``; ``scatter_rows_cm`` for a column-major table).
    """
    new_params: Dict[str, jnp.ndarray] = {}
    new_accum: Dict[str, jnp.ndarray] = {}
    for k, p in params.items():
        ids = uniq[k]
        g = sub_grads[k]
        acc_rows = gather_rows(state.accum[k], ids) + jnp.mean(
            g * g, axis=-1, keepdims=True
        )
        old = rows[k] if rows is not None else gather_rows(p, ids)
        push = (scatter or {}).get(k, scatter_rows)
        new_params[k] = push(p, ids, old - lr * g / (jnp.sqrt(acc_rows) + eps))
        new_accum[k] = scatter_rows(state.accum[k], ids, acc_rows)
    return new_params, RowAdagradState(accum=new_accum)
