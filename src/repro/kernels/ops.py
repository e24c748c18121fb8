"""Jitted public wrappers for the Pallas kernels.

On CPU (this container) kernels execute with interpret=True — the kernel
body runs in Python on CPU, validating the exact program that lowers to TPU.
On a TPU backend interpret is off and the kernels compile to Mosaic.

``inbatch_loss`` carries a custom VJP (softmax-CE closed-form gradients in
jnp) so the fused forward is usable inside ``jax.grad`` training steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attn import flash_attention_pallas
from repro.kernels.inbatch_loss import inbatch_loss_rows_pallas
from repro.kernels.ivf import ivf_list_topk_pallas
from repro.kernels.seg_aggr import seg_aggr_pallas
from repro.kernels.topk import chunked_topk_pallas
from repro.kernels.window_pairs import window_pair_ids_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------- retrieval
def streaming_topk(
    queries: jnp.ndarray,
    items: jnp.ndarray,
    k: int,
    exclude: Optional[jnp.ndarray] = None,
    item_chunk: int = 1024,
    tile_q: int = 128,
):
    """Chunked-matmul streaming top-k (kernels/topk.py): O(chunk) memory
    maximum-inner-product search. Returns ((Q, k) f32 scores, (Q, k) i32 ids);
    same tie-break contract as ``repro.retrieval.topk``."""
    return chunked_topk_pallas(
        queries, items, k, exclude=exclude, item_chunk=item_chunk,
        tile_q=tile_q, interpret=_interpret(),
    )


def ivf_list_topk(
    queries: jnp.ndarray,
    codes: jnp.ndarray,
    scales: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    lpad: int,
    shortlist: int,
):
    """IVF gather-then-score over CSR inverted lists (kernels/ivf.py):
    scalar-prefetched list offsets drive per-probe HBM->VMEM DMAs of the
    int8 code table. Returns ((Q, S) f32 approx scores, (Q, S) i32
    packed-row indices); contract matches ``ref.ivf_list_topk_ref``. Called
    from inside ``retrieval.ivf``'s jitted search, so no jit wrapper here.
    """
    return ivf_list_topk_pallas(
        queries, codes, scales, starts, lengths,
        lpad=lpad, shortlist=shortlist, interpret=_interpret(),
    )


# ------------------------------------------------------------ window pairs
def window_pair_ids(paths: jnp.ndarray, positions):
    """(B, L) walk paths -> ((B, npos) src, (B, npos) dst) skip-gram pairs.

    ``positions`` is the static (src_col, dst_col) table from
    ``sampling.pairs.window_positions``; pairs touching a PAD node come back
    with BOTH sides PAD. Called from inside the fused sampler's jitted
    program, so no jit wrapper here.
    """
    return window_pair_ids_pallas(paths, positions, interpret=_interpret())


# ------------------------------------------------------------------ seg_aggr
@functools.partial(jax.jit, static_argnames=("mode",))
def seg_aggr(x: jnp.ndarray, mask: jnp.ndarray, mode: str = "mean") -> jnp.ndarray:
    """(N, F, D), (N, F) -> (N, D) masked segment aggregation."""
    return seg_aggr_pallas(x, mask, mode=mode, interpret=_interpret())


# -------------------------------------------------------------- inbatch loss
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def inbatch_loss(h_src: jnp.ndarray, h_dst: jnp.ndarray, temperature: float = 1.0):
    rows = inbatch_loss_rows_pallas(
        h_src, h_dst, temperature=temperature, interpret=_interpret()
    )
    return rows.mean()


def _inbatch_fwd(h_src, h_dst, temperature):
    return inbatch_loss(h_src, h_dst, temperature), (h_src, h_dst)


def _inbatch_bwd(temperature, res, g):
    h_src, h_dst = res
    P = h_src.shape[0]
    logits = (h_src @ h_dst.T).astype(jnp.float32) / temperature
    soft = jax.nn.softmax(logits, axis=-1)
    dlogits = (soft - jnp.eye(P)) * (g / (P * temperature))
    dsrc = (dlogits @ h_dst.astype(jnp.float32)).astype(h_src.dtype)
    ddst = (dlogits.T @ h_src.astype(jnp.float32)).astype(h_dst.dtype)
    return dsrc, ddst


inbatch_loss.defvjp(_inbatch_fwd, _inbatch_bwd)


# ---------------------------------------------------------------- attention
def flash_attention(
    q: jnp.ndarray,  # (B, S, H, hd) — model layout
    k: jnp.ndarray,  # (B, S, K, hd)
    v: jnp.ndarray,  # (B, S, K, hd)
    causal: bool = True,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Flash attention in the model's (B, S, H, hd) layout."""
    qh = jnp.swapaxes(q, 1, 2)  # (B, H, S, hd)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = flash_attention_pallas(
        qh, kh, vh, causal=causal, window=window, interpret=_interpret()
    )
    return jnp.swapaxes(out, 1, 2)
