"""Fused on-device walk -> pair -> ego sampling (the device-resident pipeline).

PRs 1-4 made every pipeline *stage* fast but left the stage boundaries on
the host: walks, window pairs, and ego gathers are produced by NumPy against
the graph engine and shipped to the device per batch. For small/medium
graphs whose padded adjacency fits in device memory that round-trip is the
dominant cost, so this module runs the whole sampling front end as ONE
jitted program over device-resident tables:

- **walk**: ``walk.metapath.jax_walk_multi`` over a stacked (R, N, max_deg)
  padded adjacency, with a per-walk metapath draw (uniform over the
  configured metapaths) and per-metapath start-type ranges;
- **pair**: the static skip-gram window gather
  (``kernels.window_pairs`` Pallas kernel / jnp reference), then a uniform
  inverse-CDF draw of ``batch_pairs`` valid pairs;
- **ego**: relation-wise K-hop gathers from the same padded adjacency,
  PAD-propagating exactly like ``sampling.ego.sample_ego_batch``;
- **side info**: value slots as a device-resident (N, max_values) padded
  table, bag slots as the same (N, vocab) count matrices the host 'bag'
  path uses.

The emitted batch has exactly the fixed-shape PAD-padded structure
``core.model.loss_fn`` consumes (``device_batch`` layout, global ids), so
the trainer can fuse sampling INTO its jitted grad step — zero host work
per step. Distribution contract vs the host pipeline: identical walk, pair
and ego-child distributions (uniform neighbor draws over the same
adjacency, same window table, same uniform negatives); what differs is
bookkeeping only — batches are drawn per-step rather than carried across
rounds, and repeated pair endpoints get fresh ego samples (the host
``walk_pair_ego`` diversity semantics). ``tests/test_fused_sampling.py``
pins this contract backend-against-backend.

Eligibility: the device tables cost
``R * N * (max_degree + 1)`` int32s plus slot/count tables;
``fused_eligibility`` sizes them against a configurable budget so callers
(train.trainer) can fall back to the host pipeline for graphs that do not
fit (that regime belongs to the multi-process engine anyway).
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.embedding import table as emb
from repro.graph.hetero_graph import HeteroGraph, Relation
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref
from repro.sampling.pairs import window_positions
from repro.sampling.pipeline import PipelineConfig
from repro.walk.metapath import jax_walk_multi, parse_metapath

PAD = -1


@dataclasses.dataclass(frozen=True)
class FusedConfig:
    """Knobs of the fused device sampler (threaded from TrainerConfig)."""

    # Padded-adjacency width: rows wider than this are uniformly subsampled
    # once at build time (HeteroGraph.padded_adjacency).
    max_degree: int = 32
    # Device-table budget for the eligibility check, in MiB.
    budget_mb: float = 256.0
    # Route the pair gather through the Pallas kernel (interpret mode off
    # TPU) instead of the jnp reference.
    use_kernel_pairs: bool = True
    # Candidate pairs generated per emitted pair (safety factor against
    # PAD-invalidated candidates). Walks per batch =
    # ceil(oversample * batch_pairs / window_positions).
    oversample: float = 2.0


def _union_relations(config: PipelineConfig) -> List[str]:
    rels = {r for mp in config.walk.metapaths for r in parse_metapath(mp)}
    if config.ego is not None:
        rels |= set(config.ego.relations)
    return sorted(rels)


def fused_device_bytes(
    graph: HeteroGraph,
    config: PipelineConfig,
    value_slots: Sequence[emb.SlotSpec] = (),
    bag_slots: Sequence[emb.SlotSpec] = (),
    max_degree: int = 32,
) -> int:
    """Bytes of device-resident tables the fused sampler would build."""
    N = graph.num_nodes
    R = len(_union_relations(config))
    total = R * N * (max_degree + 1) * 4  # adjacency + degrees, int32
    for spec in value_slots:
        total += N * spec.max_values * 4  # padded value table, int32
    for spec in bag_slots:
        total += N * spec.vocab_size * 4  # count matrix, float32
    return total


def fused_eligibility(
    graph: HeteroGraph,
    config: PipelineConfig,
    value_slots: Sequence[emb.SlotSpec] = (),
    bag_slots: Sequence[emb.SlotSpec] = (),
    fused: FusedConfig = FusedConfig(),
    measured_bytes: Optional[int] = None,
) -> Tuple[bool, str]:
    """(eligible?, human-readable reason) for the memory-based gate.

    Without ``measured_bytes`` the gate runs on the shape-derived
    *estimate* (``fused_device_bytes`` — nothing is resident yet). Once a
    sampler exists, callers re-check with ``measured_bytes=
    sampler.device_table_bytes()`` — the actual footprint of the arrays
    ``jax.device_put`` shipped — so the logged budget decision names
    measured bytes, not predicted ones (the trainer does this in
    ``_build_fused``).
    """
    if measured_bytes is not None:
        need, kind = int(measured_bytes), "measured"
    else:
        need = fused_device_bytes(
            graph, config, value_slots, bag_slots, max_degree=fused.max_degree
        )
        kind = "estimated"
    budget = int(fused.budget_mb * (1 << 20))
    if need > budget:
        return False, (
            f"padded device tables need {need / (1 << 20):.1f} MiB "
            f"({kind}) > budget {fused.budget_mb:.1f} MiB"
        )
    return True, f"device tables fit: {need / (1 << 20):.1f} MiB ({kind})"


class FusedSampler:
    """Device-resident walk->pair->ego sampler with a single jittable entry.

    ``sample(key)`` is a pure function of the PRNG key (all tables are built
    at construction), so callers can jit it alone or inline it into a larger
    jitted step (the trainer fuses it with the grad step). Shapes are fully
    static: every batch carries exactly ``config.batch_pairs`` pairs.

    A jitted ``sample`` closes over the device tables, which JAX then copies
    back to the host and embeds in the program as constants — a transfer
    ``jax.transfer_guard("disallow")`` refuses on an accelerator. Jitted
    callers take ``tables()`` as an argument and sample through
    ``with_tables(tables)`` instead.
    """

    def __init__(
        self,
        graph: HeteroGraph,
        config: PipelineConfig,
        value_slots: Sequence[emb.SlotSpec] = (),
        bag_slots: Sequence[emb.SlotSpec] = (),
        fused: FusedConfig = FusedConfig(),
        bag_counts: Optional[Mapping[str, jnp.ndarray]] = None,
        seed: int = 0,
    ):
        if config.order not in ("walk_ego_pair", "walk_pair_ego"):
            raise ValueError(f"unknown order {config.order!r}")
        self.graph = graph
        self.config = config
        self.fused = fused
        self.value_slots = tuple(value_slots)
        self.bag_slots = tuple(bag_slots)
        self.ego = config.ego
        # Build-time seed for the padded-adjacency hub subsample: two
        # samplers built with the same seed share bitwise-identical tables.
        self.seed = seed

        # All H2D shipping below is explicit jax.device_put (lint rule H002):
        # the one transfer spelling jax.transfer_guard("disallow") certifies,
        # and the visible-in-profiles hook for the ROADMAP's double-buffered
        # device_put item.

        # ---------------- relation tables: one stacked padded adjacency
        self._rel_names = _union_relations(config)
        rel_id = {r: i for i, r in enumerate(self._rel_names)}
        adjs, degs = [], []
        for r in self._rel_names:
            a, d = graph.padded_adjacency(
                r, fused.max_degree, pad_id=PAD, seed=seed
            )
            adjs.append(a.astype(np.int32))
            degs.append(d.astype(np.int32))
        self._adj = jax.device_put(np.stack(adjs))  # (R, N, max_degree)
        self._deg = jax.device_put(np.stack(degs))  # (R, N)

        # ---------------- walk schedule + per-metapath start ranges
        paths = [parse_metapath(mp) for mp in config.walk.metapaths]
        if not paths:
            raise ValueError("need at least one metapath")
        L = config.walk.walk_len
        sched = np.zeros((len(paths), max(L - 1, 1)), dtype=np.int32)
        start_lo = np.zeros(len(paths), dtype=np.int32)
        start_cnt = np.zeros(len(paths), dtype=np.int32)
        for pi, rels in enumerate(paths):
            for s in range(max(L - 1, 1)):
                sched[pi, s] = rel_id[rels[s % len(rels)]]
            lo, cnt = graph.node_type_ranges[Relation.parse(rels[0]).src_type]
            start_lo[pi], start_cnt[pi] = lo, cnt
        self.num_paths = len(paths)
        self._sched = jax.device_put(sched)
        self._start_lo = jax.device_put(start_lo)
        self._start_cnt = jax.device_put(start_cnt)

        # ---------------- pair stage: static window table + walk count
        self._positions = window_positions(L, config.pair.win_size)
        npos = max(len(self._positions), 1)
        self.num_walks = max(
            1, int(np.ceil(fused.oversample * config.batch_pairs / npos))
        )
        self._spos = jax.device_put(self._positions[:, 0].astype(np.int32))
        self._dpos = jax.device_put(self._positions[:, 1].astype(np.int32))

        # ---------------- ego relation ids (indices into the stacked adj)
        if self.ego is not None:
            self._ego_rel_ids = [rel_id[r] for r in self.ego.relations]

        # ---------------- side-info tables
        self._slot_pad: Dict[str, jnp.ndarray] = {}
        for spec in self.value_slots:
            sf = graph.slots[spec.name]
            self._slot_pad[spec.name] = jax.device_put(
                emb.pad_slot_values(
                    sf.indptr, sf.values,
                    np.arange(graph.num_nodes, dtype=np.int64),
                    spec.max_values, pad_id=PAD,
                ).astype(np.int32)
            )
        self._bag_counts: Dict[str, jnp.ndarray] = {}
        if self.bag_slots:
            if bag_counts is not None:
                self._bag_counts = {
                    s.name: jax.device_put(bag_counts[s.name])
                    for s in self.bag_slots
                }
            else:
                self._bag_counts = {
                    s.name: jax.device_put(
                        emb.slot_count_matrix(
                            graph.slots[s.name].indptr, graph.slots[s.name].values,
                            graph.num_nodes, s.vocab_size, s.max_values,
                        )
                    )
                    for s in self.bag_slots
                }

    def tables(self) -> Dict[str, Any]:
        """Every array the constructor shipped with ``jax.device_put``, as
        one pytree."""
        return {
            "adj": self._adj, "deg": self._deg, "sched": self._sched,
            "start_lo": self._start_lo, "start_cnt": self._start_cnt,
            "spos": self._spos, "dpos": self._dpos,
            "slot_pad": dict(self._slot_pad), "bag_counts": dict(self._bag_counts),
        }

    def with_tables(self, tables: Dict[str, Any]) -> "FusedSampler":
        """A copy that samples from ``tables`` (inside a trace: the jitted
        function's own arguments) in place of the resident arrays."""
        out = copy.copy(self)
        for name, value in tables.items():
            setattr(out, f"_{name}", value)
        return out

    def device_table_bytes(self) -> int:
        """Measured footprint of the resident device tables.

        Sums ``.nbytes`` of every array the constructor shipped with
        ``jax.device_put`` — what ``fused_eligibility(measured_bytes=...)``
        gates on once the sampler exists, replacing the shape-derived
        estimate with ground truth.
        """
        return int(sum(int(t.nbytes) for t in jax.tree.leaves(self.tables())))

    # ------------------------------------------------------------- stages
    def _slot_values(self, ids: jnp.ndarray) -> Optional[Dict[str, jnp.ndarray]]:
        """Device equivalent of ``core.model._slots_for_ids``: PAD ids map
        to all-PAD value rows; shape ids.shape + (max_values,)."""
        if not self.value_slots:
            return None
        out = {}
        for spec in self.value_slots:
            tab = self._slot_pad[spec.name]
            vals = tab[jnp.maximum(ids, 0)]
            out[spec.name] = jnp.where((ids >= 0)[..., None], vals, PAD)
        return out

    def _ego_levels(self, key: jax.Array, centers: jnp.ndarray) -> List[jnp.ndarray]:
        """Relation-wise K-hop gather; PAD frontier slots propagate PAD —
        level layout identical to ``sampling.ego.sample_ego_batch``."""
        cfg = self.ego
        levels = [centers[:, None]]
        frontier = levels[0]
        R = len(self._ego_rel_ids)
        for k, fanout in enumerate(cfg.fanouts):
            B, W = frontier.shape
            # one bits draw per hop (threefry calls dominate small hops on
            # CPU); bits % degree has negligible O(max_degree/2^32) bias
            bits = jax.random.bits(
                jax.random.fold_in(key, k), (B, W, R, fanout), jnp.uint32
            )
            safe = jnp.maximum(frontier, 0)
            outs = []
            for ri, rid in enumerate(self._ego_rel_ids):
                deg = self._deg[rid][safe]  # (B, W)
                off = (
                    bits[:, :, ri]
                    % jnp.maximum(deg, 1).astype(jnp.uint32)[..., None]
                ).astype(deg.dtype)
                child = self._adj[rid][safe[..., None], off]  # (B, W, fanout)
                ok = (frontier >= 0) & (deg > 0)
                outs.append(jnp.where(ok[..., None], child, PAD))
            nxt = jnp.stack(outs, axis=2)  # (B, W, R, fanout)
            levels.append(nxt.reshape(B, W * R * fanout))
            frontier = levels[-1]
        return levels

    def _part(self, key: jax.Array, ids: jnp.ndarray):
        """One batch part in ``device_batch`` layout: (ids, slots) for
        walk-based models, (levels, per-level slots) for GNNs."""
        if self.ego is None:
            return (ids, self._slot_values(ids))
        levels = self._ego_levels(key, ids)
        slots = None
        if self.value_slots:
            slots = [self._slot_values(l) for l in levels]
        return (levels, slots)

    # ------------------------------------------------------------- sample
    def sample(self, key: jax.Array) -> Dict:
        """One fixed-shape training batch from one PRNG key (jit-safe)."""
        cfg = self.config
        P = cfg.batch_pairs
        k_path, k_start, k_walk, k_sel, k_neg, k_se, k_de, k_ne = (
            jax.random.split(key, 8)
        )
        W = self.num_walks

        # walk: per-walk metapath draw, then the fused multi-metapath scan
        # (bits % n instead of randint: one threefry draw, negligible bias)
        path_of = (
            jax.random.bits(k_path, (W,), jnp.uint32) % self.num_paths
        ).astype(jnp.int32)
        starts = self._start_lo[path_of] + (
            jax.random.bits(k_start, (W,), jnp.uint32)
            % self._start_cnt[path_of].astype(jnp.uint32)
        ).astype(jnp.int32)
        paths = jax_walk_multi(
            k_walk, self._adj, self._deg, starts,
            self._sched, path_of, cfg.walk.walk_len,
        )

        # pair: static window gather, then draw batch_pairs valid candidates
        if self.fused.use_kernel_pairs:
            src_all, dst_all = kernel_ops.window_pair_ids(paths, self._positions)
        else:
            src_all, dst_all = kernel_ref.window_pair_ids_ref(
                paths, self._positions
            )
        src_f, dst_f = src_all.reshape(-1), dst_all.reshape(-1)
        valid = src_f != PAD
        # Uniform draw of batch_pairs candidates from the VALID ones by
        # inverse CDF: cumsum(valid) + searchsorted is far cheaper than a
        # shuffle (argsort dominates the whole program on CPU). The draw is
        # with replacement — the marginal pair distribution is identical to
        # the host pipeline's (which also repeats a pair appearing in
        # several walks); only within-batch duplicate statistics differ.
        cum = jnp.cumsum(valid.astype(jnp.int32))
        n_valid = cum[-1]
        r = (
            jax.random.bits(k_sel, (P,), jnp.uint32)
            % jnp.maximum(n_valid, 1).astype(jnp.uint32)
        ).astype(jnp.int32)
        idx = jnp.minimum(
            jnp.searchsorted(cum, r + 1), src_f.shape[0] - 1
        )
        src, dst = src_f[idx], dst_f[idx]
        # an all-dead round keeps the pairs PAD: they embed to zero rows
        all_dead = n_valid == 0
        src = jnp.where(all_dead, PAD, src)
        dst = jnp.where(all_dead, PAD, dst)

        out: Dict = {}
        if self.ego is not None and cfg.order == "walk_ego_pair":
            # §3.6 order exchange, fused form: ONE ego per (walk, position)
            # — O(W·L) gathers — and the selected pairs index into the
            # shared levels, exactly like the host ego-first pipeline.
            npos = len(self._positions)
            L = cfg.walk.walk_len
            flat_levels = self._ego_levels(k_se, paths.reshape(-1))
            # all-dead rounds PAD the shared towers themselves — every
            # pair indexes into them, so this matches PADding each side
            flat_levels = [
                jnp.where(all_dead, PAD, l) for l in flat_levels
            ]
            slots = (
                [self._slot_values(l) for l in flat_levels]
                if self.value_slots else None
            )
            # Shared-tower layout: the GNN embeds each of the W*L unique
            # (walk, position) towers ONCE; the loss gathers the per-pair
            # src/dst embeddings by index afterwards. Per-tower encoder
            # compute is row-independent, so this is numerically identical
            # to gathering duplicated towers first — but skips embedding
            # each shared ego up to window-size times.
            out["shared"] = (flat_levels, slots)
            row = idx // npos
            pcol = idx % npos
            out["src_sel"] = row * L + self._spos[pcol]
            out["dst_sel"] = row * L + self._dpos[pcol]
        else:
            out["src"] = self._part(k_se, src)
            out["dst"] = self._part(k_de, dst)
        if cfg.pair.neg_mode == "random":
            neg = jax.random.randint(
                k_neg, (P, cfg.pair.num_negatives), 0, self.graph.num_nodes,
                dtype=src.dtype,
            )
            out["neg"] = self._part(k_ne, neg.reshape(-1))
        if self._bag_counts:
            out["slot_counts"] = dict(self._bag_counts)
        return out
