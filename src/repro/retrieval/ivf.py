"""IVF (inverted-file) ANN for million-item recall tables, device-resident.

Exact streaming top-k (retrieval/topk.py) is O(I) compute per query batch.
For million-item corpora the standard serving trick is a coarse quantizer:
cluster the item table into ``nlist`` cells (spherical k-means — the items
are scored by inner product on normalized embeddings, so centroids live on
the same sphere), store each cell's members as an inverted list, and at
query time score only the ``nprobe`` nearest cells' lists.

The index is built around the hardware, not around numpy:

- **Packed CSR lists.** Items are sorted by cell; ``offsets`` (nlist+1)
  delimits each cell's contiguous row range and ``order`` maps packed row
  -> original item id. No dense (nlist, max_len) pad is ever gathered —
  the per-probe slice width is ``lpad`` (the max list length, bounded by
  ``balance_factor`` via hot-cell spilling), and slots past a list's true
  length are masked, not materialized.
- **int8 scalar quantization, asymmetric distance.** Packed rows are
  stored as per-row absmax-scaled int8 codes scored against the f32 query
  (``score = (codes . q) * scale``) — a 10M x 32 table is ~320 MB of codes
  instead of 1.3 GB of f32, so it fits device memory next to the exact
  table (or without it: ``keep_exact_device=False`` re-ranks on host).
- **Device residency.** Centroids, codes, scales, CSR arrays, and (by
  default) the exact table are uploaded once at build via
  ``jax.device_put`` and reused by every ``search()``; the only per-call
  transfers are the queries/exclusion lists in and the (Q, k) results out
  (tested under ``jax.transfer_guard``).
- **Gather-then-score kernel.** The shortlist stage runs the Pallas kernel
  (kernels/ivf.py: scalar-prefetched list offsets driving HBM->VMEM DMAs)
  on TPU, or its jitted XLA oracle (``kernels.ref.ivf_list_topk_ref``) on
  CPU — one contract, conformance-tested.
- **Exact re-rank.** The top ``shortlist`` approximate candidates are
  re-scored with exact f32 dots and re-sorted by ascending item id before
  the final top-k, so the shared lower-id-wins tie-break contract of
  retrieval/topk.py holds end to end. ``nprobe == nlist`` sizes the
  shortlist to the full candidate budget, so exhaustive probing returns
  exactly the oracle's ids (quantization only reorders the shortlist,
  never the exact re-rank; tested).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.ivf import dma_rows, lane_pad
from repro.kernels.ref import ivf_list_topk_ref
from repro.lint.sanitizer import host_array
from repro.retrieval.topk import _deterministic_topk_rows

_INT32_MAX = np.iinfo(np.int32).max
# auto assignment mode switches to hierarchical above this many
# item x centroid score pairs (the full-table assignment GEMM cost)
_HIER_AUTO_THRESHOLD = 2_000_000_000
# truncated spill preference depth: a full (n_spill, nlist) stable argsort
# is tens of GB at the 10M arm; 32 next-best cells place everything in
# practice, with a full-ranking fallback for the rare leftovers
_SPILL_PREF_RANKS = 32


@dataclasses.dataclass(frozen=True)
class IVFConfig:
    nlist: int = 64  # coarse cells
    nprobe: int = 8  # cells scored per query
    kmeans_iters: int = 8
    # k-means training subsample (0 = fit on every item). Million-item
    # tables fit centroids on a sample, then assign the full table once.
    train_size: int = 0
    # Cap each inverted list at this multiple of the mean cell size by
    # spilling a hot cell's weakest members to their next-best centroid.
    # ``lpad`` (the fixed per-probe gather width) — and with it the
    # per-query candidate budget O(nprobe * lpad) — is then bounded even
    # when k-means lands a skewed clustering; every item still lives in
    # exactly one list, so nprobe == nlist stays exhaustive. 0 disables.
    balance_factor: float = 4.0
    # Row-chunk width of the full-table assignment pass (memory bound:
    # O(assign_chunk x nlist) scores live at once).
    assign_chunk: int = 65536
    seed: int = 0
    # Exact-dot re-rank depth: how many approximate-score survivors are
    # re-scored exactly per query. 0 -> auto (max(4k, 128)); the effective
    # shortlist adds the exclusion width and clamps to the probe budget.
    rerank: int = 0
    # Keep the exact f32 table device-resident for the re-rank gather.
    # False re-ranks on host from the builder's numpy table — the 10M-item
    # mode where only the int8 codes fit device memory.
    keep_exact_device: bool = True
    # Full-table assignment pass: "exact" scores all nlist centroids per
    # item; "hier" routes each item through ~sqrt(nlist) centroid groups
    # first (a build-time approximation — cheaper by ~nlist/sqrt(nlist),
    # conformance-tested); "auto" picks hier only when I*nlist is large
    # enough for the exact GEMM to dominate the build.
    assign_mode: str = "auto"
    # Shortlist stage: "pallas" = the gather-then-score kernel,
    # "ref" = its jitted XLA oracle, "auto" = pallas on TPU else ref.
    backend: str = "auto"

    def validate(self) -> None:
        if self.nlist <= 0:
            raise ValueError(f"nlist must be positive, got {self.nlist}")
        if self.nprobe <= 0:
            raise ValueError(f"nprobe must be positive, got {self.nprobe}")
        if self.assign_chunk <= 0:
            raise ValueError(
                f"assign_chunk must be positive, got {self.assign_chunk} "
                "(a non-positive chunk width would silently assign nothing)"
            )
        if self.rerank < 0:
            raise ValueError(f"rerank must be >= 0, got {self.rerank}")
        if self.assign_mode not in ("auto", "exact", "hier"):
            raise ValueError(
                f"assign_mode must be auto|exact|hier, got {self.assign_mode!r}"
            )
        if self.backend not in ("auto", "ref", "pallas"):
            raise ValueError(
                f"backend must be auto|ref|pallas, got {self.backend!r}"
            )


# ------------------------------------------------------------- build helpers
def _quantize_rows(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row absmax int8 quantization -> (codes int8, scales (R, 1) f32)."""
    scales = np.maximum(
        np.abs(x).max(axis=1, keepdims=True) / 127.0, 1e-12
    ).astype(np.float32)
    codes = np.rint(x / scales).astype(np.int8)
    return codes, scales


def _assign_exact(norm: np.ndarray, cent: np.ndarray, chunk: int) -> np.ndarray:
    """Chunked full-table argmax assignment (O(chunk x nlist) live scores)."""
    out = np.empty(norm.shape[0], dtype=np.int64)
    for lo in range(0, norm.shape[0], chunk):
        out[lo : lo + chunk] = np.argmax(norm[lo : lo + chunk] @ cent.T, axis=1)
    return out


def _assign_hier(
    norm: np.ndarray,
    cent: np.ndarray,
    chunk: int,
    rng: np.random.Generator,
    probe_groups: int = 4,
) -> np.ndarray:
    """Two-level assignment: route items through centroid groups.

    The centroids themselves are clustered into ~sqrt(nlist) groups (a tiny
    exact k-means over the centroid set); each item scores the group
    centers, then only the member centroids of its ``probe_groups`` best
    groups — O(sqrt(nlist) + probe_groups * nlist/sqrt(nlist)) scores per
    item instead of O(nlist). A deliberate build-time approximation: an
    item whose true argmax centroid lives outside its probed groups lands
    on its best *probed* centroid instead. Deterministic for a fixed seed;
    search-time contracts (one cell per item, exhaustive-probe exactness)
    are assignment-agnostic.
    """
    nlist, d = cent.shape
    G = max(1, int(round(np.sqrt(nlist))))
    if G < 2:
        return _assign_exact(norm, cent, chunk)
    gc = cent[rng.choice(nlist, size=G, replace=False)].copy()
    for _ in range(4):
        ga = np.argmax(cent @ gc.T, axis=1)
        sums = np.zeros((G, d), np.float32)
        np.add.at(sums, ga, cent)
        nrm = np.linalg.norm(sums, axis=1, keepdims=True)
        ok = nrm[:, 0] > 1e-12
        gc[ok] = (sums / np.maximum(nrm, 1e-12))[ok]
    ga = np.argmax(cent @ gc.T, axis=1)
    members = [np.flatnonzero(ga == g) for g in range(G)]
    gcount = np.bincount(ga, minlength=G)
    pg = min(probe_groups, G)
    assign = np.zeros(norm.shape[0], dtype=np.int64)
    for lo in range(0, norm.shape[0], chunk):
        blk = norm[lo : lo + chunk]
        gs = blk @ gc.T  # (c, G)
        gs[:, gcount == 0] = -np.inf  # a memberless group buys nothing
        topg = np.argpartition(-gs, pg - 1, axis=1)[:, :pg]
        best = np.full(len(blk), -np.inf, dtype=np.float32)
        aa = np.zeros(len(blk), dtype=np.int64)
        # ascending group order + strict > keeps the update deterministic
        for g in range(G):
            mem = members[g]
            if not len(mem):
                continue
            sel = np.flatnonzero((topg == g).any(axis=1))
            if not len(sel):
                continue
            sc = blk[sel] @ cent[mem].T  # (n_sel, |mem|)
            am = sc.argmax(axis=1)
            mx = sc[np.arange(len(sel)), am]
            upd = mx > best[sel]
            hit = sel[upd]
            best[hit] = mx[upd]
            aa[hit] = mem[am[upd]]
        assign[lo : lo + chunk] = aa
    return assign


def _place_rank_rounds(
    spill: np.ndarray,
    prefs: np.ndarray,
    assign: np.ndarray,
    counts: np.ndarray,
    cap: int,
) -> np.ndarray:
    """One admission round per preference rank: round r places every
    still-unplaced spilled item whose r-th-preference cell has room,
    admitting by ascending item id when a cell can't take all claimants.
    Mutates ``assign``/``counts``; returns the placed mask."""
    nlist = len(counts)
    placed = np.zeros(len(spill), dtype=bool)
    for r in range(prefs.shape[1]):
        active = np.flatnonzero(~placed)
        if not len(active):
            break
        tgt = prefs[active, r]
        room = np.maximum(cap - counts, 0)
        # group claimants by target cell, id ascending; admit the first
        # ``room[cell]`` of each group
        lex = np.lexsort((spill[active], tgt))
        tg = tgt[lex]
        grp_start = np.flatnonzero(np.r_[True, np.diff(tg) > 0])
        within = np.arange(len(tg)) - np.repeat(
            grp_start, np.diff(np.r_[grp_start, len(tg)])
        )
        ok = within < room[tg]
        sel = active[lex[ok]]
        assign[spill[sel]] = tg[ok]
        counts += np.bincount(tg[ok], minlength=nlist)
        placed[sel] = True
    return placed


def _spill_hot_cells(
    norm: np.ndarray, cent: np.ndarray, assign: np.ndarray, cap: int
) -> np.ndarray:
    """Move the weakest members of over-``cap`` cells to their next-best
    centroid with room. Every item keeps exactly one cell (exhaustive
    probing stays exact); cap * nlist >= num_items whenever the cap is at
    least the mean cell size, so a slot always exists.

    Vectorized rank rounds (the seed implementation walked spilled items
    one at a time with an O(nlist) inner scan — the loop that dominated
    the 1M-item build): round r places every still-unplaced item whose
    r-th-preference cell has room. Preference lists are truncated to the
    top ``_SPILL_PREF_RANKS`` cells per item, computed chunked — the full
    (n_spill, nlist) argsort is O(10s of GB) at the 10M arm — and the
    rare items whose whole truncated list is full fall back to their full
    ranking. Deterministic for fixed inputs; a deliberate
    conformance-tested change from the sequential greedy order (same cap
    bound, same one-cell-per-item permutation guarantee).
    """
    assign = assign.copy()
    nlist = len(cent)
    counts = np.bincount(assign, minlength=nlist)
    hot = np.flatnonzero(counts > cap)
    if not len(hot):
        return assign
    # weakest members per hot cell, via one cell-sorted pass (a per-cell
    # ``assign == c`` scan is O(n_hot * I) — minutes at the 10M arm)
    by_cell = np.argsort(assign, kind="stable")
    offs = np.zeros(nlist + 1, np.int64)
    offs[1:] = np.cumsum(counts)
    own_aff = np.einsum("ij,ij->i", norm, cent[assign])
    spill_parts = []
    for c in hot:
        members = by_cell[offs[c] : offs[c + 1]]
        weakest = np.argsort(own_aff[members], kind="stable")[
            : counts[c] - cap
        ]
        spill_parts.append(members[weakest])
    spill = np.concatenate(spill_parts)
    counts[hot] = cap  # spilled members vacate their source cells
    R = int(min(nlist, _SPILL_PREF_RANKS))
    prefs = np.empty((len(spill), R), np.int64)
    for lo in range(0, len(spill), 65536):
        sc = norm[spill[lo : lo + 65536]] @ cent.T
        part = np.argpartition(-sc, R - 1, axis=1)[:, :R]
        row = np.arange(len(part))[:, None]
        ordr = np.argsort(-sc[row, part], axis=1, kind="stable")
        prefs[lo : lo + 65536] = part[row, ordr]
    placed = _place_rank_rounds(spill, prefs, assign, counts, cap)
    left = np.flatnonzero(~placed)
    if len(left):  # truncated list exhausted: full ranking for the few
        sp = spill[left]
        full = np.argsort(-(norm[sp] @ cent.T), axis=1, kind="stable")
        _place_rank_rounds(sp, full, assign, counts, cap)
    return assign


# ------------------------------------------------------------ search program
@functools.partial(
    jax.jit, static_argnames=("nprobe", "shortlist", "lpad", "backend")
)
def _ivf_shortlist(
    q, ex, centroids, codes, scales, order, offsets,
    *, nprobe, shortlist, lpad, backend,
):
    """Probe + gather-then-score + exclusion -> (Q, S) approximate shortlist.

    Returns (approx scores, item ids, total candidates scored). Excluded
    and empty slots come back (-inf, -1); candidates are unique per query
    (cells are disjoint and ``top_k`` probes distinct cells), which the
    exact re-rank relies on.
    """
    qf = q.astype(jnp.float32)
    cscores = qf @ centroids.astype(jnp.float32).T  # (Q, nlist)
    _, probes = jax.lax.top_k(cscores, nprobe)  # (Q, nprobe)
    starts = offsets[probes]
    lens = offsets[probes + 1] - starts
    if backend == "pallas":
        s, rows = ops.ivf_list_topk(
            qf, codes, scales, starts, lens, lpad=lpad, shortlist=shortlist
        )
    else:
        s, rows = ivf_list_topk_ref(
            qf, codes, scales, starts, lens, lpad=lpad, shortlist=shortlist
        )
    ids = jnp.where(rows >= 0, order[jnp.maximum(rows, 0)], -1)
    hit = (ex[:, :, None] == ids[:, None, :]).any(axis=1)
    masked = hit | (ids < 0)
    s = jnp.where(masked, -jnp.inf, s)
    ids = jnp.where(masked, -1, ids)
    return s, ids, jnp.sum(jnp.minimum(lens, lpad))


@functools.partial(jax.jit, static_argnames=("k",))
def _rerank_exact_device(q, s, ids, table, *, k):
    """Exact-dot re-rank of the shortlist under the lower-id tie-break.

    Survivors are re-scored against the exact f32 table and re-sorted by
    ascending item id before ``top_k`` (first occurrence of a tied value
    wins), so equal exact scores resolve to the lower id — the contract
    shared with retrieval/topk.py regardless of probe or shortlist order.
    """
    qf = q.astype(jnp.float32)
    masked = jnp.isneginf(s) | (ids < 0)
    vecs = table[jnp.maximum(ids, 0)].astype(jnp.float32)  # (Q, S, d)
    # exact f32 dots: the TPU's default f32 matmul precision is reduced
    es = jnp.einsum(
        "qd,qsd->qs", qf, vecs, precision=jax.lax.Precision.HIGHEST
    )
    es = jnp.where(masked, -jnp.inf, es)
    by_id = jnp.argsort(jnp.where(ids >= 0, ids, _INT32_MAX), axis=1)
    ids2 = jnp.take_along_axis(ids, by_id, axis=1)
    es2 = jnp.take_along_axis(es, by_id, axis=1)
    best, pos = jax.lax.top_k(es2, k)
    bi = jnp.take_along_axis(ids2, pos, axis=1)
    return best, jnp.where(jnp.isneginf(best), -1, bi)


def _rerank_exact_host(q, s, ids, table, k):
    """Host twin of ``_rerank_exact_device`` for ``keep_exact_device=False``:
    the exact table never leaves host memory; only the (Q, S) shortlist is
    pulled back. Same id-ascending pre-sort + tie-stable top-k, so the
    results match the device re-rank (conformance-tested)."""
    masked = np.isneginf(s) | (ids < 0)
    vecs = table[np.maximum(ids, 0)]  # (Q, S, d)
    es = np.einsum("qd,qsd->qs", q, vecs).astype(np.float32)
    es = np.where(masked, -np.inf, es).astype(np.float32)
    by_id = np.argsort(np.where(ids >= 0, ids, _INT32_MAX), axis=1, kind="stable")
    ids2 = np.take_along_axis(ids, by_id, axis=1)
    es2 = np.take_along_axis(es, by_id, axis=1)
    pos = _deterministic_topk_rows(es2, k)  # ascending index == ascending id
    best = np.take_along_axis(es2, pos, axis=1)
    bi = np.take_along_axis(ids2, pos, axis=1)
    return best, np.where(np.isneginf(best), -1, bi)


@dataclasses.dataclass
class IVFIndex:
    """Built coarse index over one item table (ids are row indices).

    Device residency contract: ``build()`` (and any direct construction —
    ``__post_init__``) uploads centroids, codes, scales, and the CSR
    arrays once via ``jax.device_put``; ``search()`` only ever transfers
    queries in and results out.
    """

    config: IVFConfig
    centroids: np.ndarray  # (nlist, d) float32
    order: np.ndarray  # (I,) int32 — packed row -> original item id
    offsets: np.ndarray  # (nlist + 1,) int32 CSR bounds into packed rows
    codes: np.ndarray  # (I + dma_rows(lpad), d) int8 cell-sorted rows (+ DMA pad)
    scales: np.ndarray  # (I + dma_rows(lpad), 1) float32 per-row dequant scales
    items: np.ndarray  # (I, d) float32 — the exact table (host copy)
    lpad: int = 1  # max list length: fixed per-probe gather width
    # items moved off their argmax cell by hot-cell balancing at build
    # time: the recall-vs-balance price the BENCH_recall ANN-rebuild item
    # needs to see (each spilled item is findable only via its second-best
    # cell, exactly the population nprobe misses first)
    spilled_items: int = 0

    def __post_init__(self):
        # accurate per-search telemetry, read by core.recall's counters
        self.last_cells_probed = 0
        self.last_candidates_scored = 0
        self._upload()

    def _upload(self) -> None:
        """One-time host->device residency (the only table-sized H2D)."""
        backend = self.config.backend
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "ref"
        self._backend = backend
        dp = jax.device_put
        self._dev = {
            "centroids": dp(self.centroids),
            # the kernel DMAs whole 128-lane tiles: pad columns once, here
            "codes": dp(lane_pad(self.codes) if backend == "pallas" else self.codes),
            "scales": dp(self.scales),
            "order": dp(self.order),
            "offsets": dp(self.offsets),
        }
        if self.config.keep_exact_device:
            self._dev["items"] = dp(self.items)

    @classmethod
    def build(cls, items: np.ndarray, config: IVFConfig = IVFConfig()) -> "IVFIndex":
        config.validate()
        it = host_array(items, dtype=np.float32)
        I, d = it.shape
        nlist = min(config.nlist, I)
        rng = np.random.default_rng(config.seed)
        norm = it / np.maximum(np.linalg.norm(it, axis=1, keepdims=True), 1e-12)
        train = norm
        if config.train_size and config.train_size < I:
            train = norm[
                rng.choice(I, size=max(config.train_size, nlist), replace=False)
            ]
        cent = train[rng.choice(len(train), size=nlist, replace=False)].copy()
        for _ in range(max(1, config.kmeans_iters)):
            t_assign = _assign_exact(train, cent, config.assign_chunk)
            sums = np.zeros((nlist, d), np.float32)
            np.add.at(sums, t_assign, train)
            counts = np.bincount(t_assign, minlength=nlist)
            nrm = np.linalg.norm(sums, axis=1, keepdims=True)
            ok = (counts > 0) & (nrm[:, 0] > 1e-12)
            cent[ok] = (sums / np.maximum(nrm, 1e-12))[ok]
            dead = np.flatnonzero(counts == 0)
            if len(dead):  # re-seed empty cells so every list stays non-trivial
                cent[dead] = train[rng.integers(0, len(train), size=len(dead))]
        mode = config.assign_mode
        if mode == "auto":
            mode = "hier" if I * nlist > _HIER_AUTO_THRESHOLD else "exact"
        if mode == "hier":
            assign = _assign_hier(norm, cent, config.assign_chunk, rng)
        else:
            assign = _assign_exact(norm, cent, config.assign_chunk)
        spilled = 0
        if config.balance_factor:
            cap = max(1, int(np.ceil(config.balance_factor * I / nlist)))
            before = assign
            assign = _spill_hot_cells(norm, cent, assign, cap)
            spilled = int((assign != before).sum())
        order = np.argsort(assign, kind="stable").astype(np.int32)
        counts = np.bincount(assign, minlength=nlist)
        offsets = np.zeros(nlist + 1, np.int32)
        offsets[1:] = np.cumsum(counts).astype(np.int32)
        lpad = max(1, int(counts.max()))
        codes, scales = _quantize_rows(it[order])
        # zero padding so the kernel's fixed-width, tile-aligned DMA slice
        # never reads past the table
        pad = dma_rows(lpad)
        codes = np.concatenate([codes, np.zeros((pad, d), np.int8)])
        scales = np.concatenate([scales, np.zeros((pad, 1), np.float32)])
        return cls(
            config=dataclasses.replace(config, nlist=nlist),
            centroids=cent.astype(np.float32), order=order, offsets=offsets,
            codes=codes, scales=scales, items=it, lpad=lpad,
            spilled_items=spilled,
        )

    # ------------------------------------------------------------- derived
    @property
    def lists(self) -> np.ndarray:
        """Back-compat dense (nlist, lpad) view of the CSR lists, -1 padded.

        Purely derived for inspection/tests — nothing at search time ever
        materializes or gathers this matrix.
        """
        lens = np.diff(self.offsets)
        out = np.full((len(lens), self.lpad), -1, np.int32)
        out[np.arange(self.lpad)[None, :] < lens[:, None]] = self.order
        return out

    @property
    def candidates_per_query(self) -> int:
        """Upper bound on candidates scored per query (probe budget)."""
        return min(self.config.nprobe, self.config.nlist) * self.lpad

    # -------------------------------------------------------------- search
    def search(
        self,
        queries: np.ndarray,
        k: int,
        exclude: Optional[np.ndarray] = None,
        nprobe: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """((Q, k) f32 scores, (Q, k) i32 ids); unfilled slots are (-inf, -1).

        ``k`` may exceed the probed candidate count only up to the table
        size; slots beyond the candidates surface as id -1. Scores are
        exact dots (the quantized scores only pick the shortlist); with
        ``nprobe == nlist`` the shortlist covers every candidate and the
        result equals the exhaustive oracle.
        """
        if nprobe is not None and nprobe <= 0:
            raise ValueError(f"nprobe must be positive, got {nprobe}")
        nprobe = min(
            self.config.nlist, self.config.nprobe if nprobe is None else nprobe
        )
        I = self.items.shape[0]
        if not 0 < k <= I:
            raise ValueError(f"k={k} must be in [1, {I}]")
        q = host_array(queries, dtype=np.float32)
        Q = q.shape[0]
        if exclude is None:
            ex = np.full((Q, 1), -1, np.int32)
        else:
            ex = host_array(exclude, dtype=np.int32)
        budget = nprobe * self.lpad
        if nprobe >= self.config.nlist:
            # exhaustive: every item lives in exactly one list, so I slots
            # hold every candidate (the rest of the budget is padding)
            shortlist = min(budget, I)
        else:
            want = self.config.rerank or max(4 * k, 128)
            shortlist = min(max(want, k) + ex.shape[1], budget)
        dev = self._dev
        dq = jax.device_put(q)
        s, ids, n_scored = _ivf_shortlist(
            dq, jax.device_put(ex), dev["centroids"], dev["codes"],
            dev["scales"], dev["order"], dev["offsets"],
            nprobe=nprobe, shortlist=shortlist, lpad=self.lpad,
            backend=self._backend,
        )
        kk = min(k, shortlist)
        if self.config.keep_exact_device:
            bs, bi = _rerank_exact_device(dq, s, ids, dev["items"], k=kk)
            bs, bi = host_array(bs), host_array(bi)
        else:
            bs, bi = _rerank_exact_host(
                q, host_array(s), host_array(ids), self.items, kk
            )
        self.last_cells_probed = Q * nprobe
        self.last_candidates_scored = int(host_array(n_scored))
        if kk < k:
            bs = np.pad(bs, ((0, 0), (0, k - kk)), constant_values=-np.inf)
            bi = np.pad(bi, ((0, 0), (0, k - kk)), constant_values=-1)
        return bs, bi
