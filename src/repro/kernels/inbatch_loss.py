"""Pallas TPU kernel: fused in-batch negative-sampling loss (paper §3.6/RQ4).

Computes, per row tile of P positives, the (TP, P) similarity block against
all in-batch destinations, a numerically-stable log-sum-exp, and the
diagonal positive score — in one VMEM pass, never materializing the P×P
logits in HBM. For P=8192, d=256 the logits would be 256 MiB in HBM; the
kernel streams them through VMEM in (TP, P) stripes instead.

Tiling: grid (P/TP,); each step holds the (TP, d) source tile plus the full
(P, d) destination block in VMEM (P*d*4B — up to ~8 MiB at P=8192, d=256;
larger batches would add a second grid axis with online LSE, not needed at
recsys batch sizes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _inbatch_kernel(src_ref, dst_ref, o_ref, *, temperature: float, tp: int, p_valid: int):
    i = pl.program_id(0)
    src = src_ref[...]  # (TP, d)
    dst = dst_ref[...]  # (P, d)
    logits = jnp.dot(
        src.astype(jnp.float32), dst.astype(jnp.float32).T,
        preferred_element_type=jnp.float32,
    ) / temperature  # (TP, P)
    # mask padded columns
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < p_valid, logits, -1e30)
    m = logits.max(axis=-1, keepdims=True)
    lse = jnp.log(jnp.exp(logits - m).sum(axis=-1, keepdims=True)) + m
    # the positive of row r is column r: a masked row sum, not a gather
    # (Mosaic has no general in-kernel gather)
    rows = i * tp + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    diag = jnp.where(col == rows, logits, 0.0).sum(axis=-1, keepdims=True)
    o_ref[...] = lse - diag  # (TP, 1)


def inbatch_loss_rows_pallas(
    h_src: jnp.ndarray,  # (P, d)
    h_dst: jnp.ndarray,  # (P, d)
    temperature: float = 1.0,
    tile_p: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-row losses (P,). Mean-reduce (over valid rows) in the wrapper."""
    P, d = h_src.shape
    tp = min(tile_p, P)
    Pp = -(-P // tp) * tp
    if Pp != P:
        h_src = jnp.pad(h_src, ((0, Pp - P), (0, 0)))
        h_dst = jnp.pad(h_dst, ((0, Pp - P), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_inbatch_kernel, temperature=temperature, tp=tp, p_valid=P),
        grid=(Pp // tp,),
        in_specs=[
            pl.BlockSpec((tp, d), lambda i: (i, 0)),
            pl.BlockSpec((Pp, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tp, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
        interpret=interpret,
    )(h_src, h_dst)
    return out[:P, 0]
