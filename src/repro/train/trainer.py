"""Graph4Rec trainer: streams pipeline batches through a jitted grad step.

The trainer wires together the paper's five pipeline stages (walk -> ego ->
pair -> GNN -> loss) with the sparse/dense optimizer split and the recall
evaluation. It is the engine behind examples/train_recsys.py and every
RQ benchmark.

Throughput design: host-side sampling + host-batch assembly run in a
bounded background prefetch thread (``prefetch_batches`` deep), the one
explicit H2D transfer per batch happens in a consumer-side double-buffered
stager (``jax.device_put`` of batch k+1 overlaps the in-flight step k, and
the next device batch is always resident before its dispatch) — or, with
``sampling_backend="fused"`` on an eligible graph, sampling moves onto the
device entirely: walk, window-pair and ego gather run inside the jitted
grad step (sampling/fused.py) and the prefetcher/stager are bypassed. The
loop never forces a device sync per step: losses stay on device and are
drained in windows through a *started-ahead* async readback
(``host_floats_async``), so the fetch of window k resolves while window
k+1's steps dispatch; set ``sync_every_step=True`` for the strictly serial
sample->sync->step loop, e.g. as a benchmark baseline.

Backend selection is measured, not guessed (``auto_backend``, default on):
at the first ``train()`` a short calibration phase times per-batch host
sampling/assembly, the jitted step, the prefetch handoff, and (when
``sampling_backend="auto"``) the fused step, then picks
serial-vs-prefetch-vs-fused from those numbers. Explicit settings always
win; the decision and its measurements are recorded in
``TrainResult.plan``. ``TrainerConfig.attribution`` threads a sync-free
``train.attribution.PhaseTimer`` through the loop (sample / assemble /
batch_wait / h2d / dispatch / loss_fetch) — `make bench-attr` records the
per-combination breakdown into BENCH_throughput.json.

Sparse updates (``sparse_updates=True``, the default — the paper's PS
pull/push, §3.6): the prefetch thread deduplicates each batch's touched ids
per embedding table and remaps the batch onto gathered sub-tables
(core/model.py:sparse_device_batch); the jitted step differentiates w.r.t.
the gathered rows only, applies row-wise AdaGrad to them, and scatters the
updated rows back into the donated tables — O(unique ids) per step instead
of the dense path's O(num_nodes). ``sparse_updates=False`` keeps the dense
full-table grad step (same row-wise AdaGrad rule via
train.optimizer.rowwise_adagrad, so the two paths are numerically
equivalent).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import queue
import threading
import time
import warnings
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from repro.core import model as model_lib
from repro.core.recall import evaluate_recall
from repro.embedding import optimizer as emb_opt
from repro.embedding import table as emb
from repro.graph.generator import RecsysDataset
from repro.lint.sanitizer import (
    device_barrier,
    host_floats,
    host_floats_async,
    host_scalar,
    transfer_sanitizer,
)
from repro.obs.trace import span_scope
from repro.train.attribution import (
    PhaseTimer,
    measure_handoff_overhead,
    median,
    phase_scope,
)
from repro.sampling.fused import FusedConfig, fused_eligibility
from repro.sampling.pipeline import (
    PipelineConfig, SamplePipeline, make_train_sampler,
)
from repro.train import optimizer as opt_lib
from repro.utils import get_logger

log = get_logger("repro.train")

# The sparse step donates its batch so the stager's H2D buffers recycle into
# the update outputs. A batch's int32 id arrays can never alias the float
# outputs, so XLA reports them "not usable" on every (re)compile — expected,
# not actionable; the float buffers (bag-mode count matrices) do alias.
warnings.filterwarnings(
    "ignore", message=r"Some donated buffers were not usable.*int32.*"
)


@dataclasses.dataclass
class TrainerConfig:
    num_steps: int = 200
    sparse_lr: float = 0.2
    dense_lr: float = 1e-3
    eval_every: int = 0  # 0 -> only at end
    eval_top_k: int = 100
    # Similar-neighbor pool size for the ICF/UCF strategies (paper §4.2) —
    # previously hard-coded inside core/recall.py.
    eval_top_n: int = 20
    # 0 evaluates EVERY held-out user (no subsampling — the device retrieval
    # path makes that affordable); >0 restores the old capped behavior.
    eval_max_users: int = 0
    # Retrieval implementation for evaluate(): "device" (chunked streaming
    # top-k, exact), "ivf" (coarse-partition approximate), or "bruteforce"
    # (numpy oracle — the seed path, O(U·I) memory).
    eval_method: str = "device"
    # Fixed chunk width for full-graph inference (infer.embed_all_nodes).
    eval_batch_size: int = 1024
    eval_at_end: bool = True
    log_every: int = 50
    seed: int = 0
    # Depth of the background host->device prefetch queue. 0 disables the
    # prefetch thread and runs the serial sample->step loop; an explicit
    # int always wins. None defers the serial-vs-prefetch decision to the
    # auto-backend calibration (or the legacy default of 2 when
    # ``auto_backend`` is off / the run is too short to calibrate).
    prefetch_batches: Optional[int] = None
    # Measured backend selection: at the first train() a short calibration
    # phase times per-batch host cost, the jitted step and the prefetch
    # handoff, then resolves every knob left at its "auto" default
    # (prefetch_batches=None, sampling_backend="auto"). Explicit settings
    # are never overridden. Calibration is skipped (legacy defaults apply)
    # when num_steps < calibrate_min_steps — short smoke runs shouldn't
    # pay a measurement phase longer than the run itself.
    auto_backend: bool = True
    # Batches sampled / steps timed during calibration (first one warms
    # caches / compiles and is excluded from the medians).
    calibrate_batches: int = 3
    calibrate_min_steps: int = 32
    # Force a device sync (float(loss)) after every step — the seed's serial
    # behavior; benchmarks use it as the baseline arm.
    sync_every_step: bool = False
    # Route GNN aggregation through the Pallas seg_aggr kernel. None leaves
    # the model config (HeteroGNNConfig.use_kernel_aggr) untouched.
    use_kernel_aggr: Optional[bool] = None
    # Gather→step→scatter training (O(unique ids) per step). False falls back
    # to dense full-table grads + row-wise AdaGrad over every row (O(N)).
    sparse_updates: bool = True
    # Sparse/dense crossover: below this node-table row count the sparse
    # path's dedup+gather+scatter overhead exceeds what it saves
    # (BENCH_throughput.json grad_step: 0.45x dense at 10k rows, 1.66x at
    # 100k), so ``sparse_updates=True`` routes through the dense step for
    # small tables. Both paths are bitwise-equivalent (PR-2 suite); set 0
    # to force the sparse path regardless of size.
    sparse_min_rows: int = 32768
    # Initial unique-id bucket width per table (0 = start at 8). Buckets grow
    # to the next power of two on overflow (one jit recompile per width).
    unique_bucket: int = 0
    # Row-wise AdaGrad accumulator init (shared by both update paths).
    adagrad_init_accum: float = 0.1
    # Drain completed on-device losses to host floats every this many steps
    # (keeps only the in-flight tail on device). 0 defers to the end of run.
    loss_fetch_every: int = 64
    # Graph engine backend. "inproc" samples from the engine object passed to
    # the trainer; "mp" wraps its graph in a graph/service.GraphClient —
    # partition CSR shards in POSIX shared memory served by worker processes
    # — so the prefetch producer is never sampling-bound on this process's
    # core. Both backends are bitwise-identical under a fixed seed.
    engine_backend: str = "inproc"  # inproc | mp
    # Worker processes for the "mp" backend (clamped to num_partitions).
    # 0 sizes the fleet automatically: half the visible cores (leaving the
    # rest for the trainer process and XLA's own thread pool), capped by
    # the partition count.
    num_engine_workers: int = 0
    # Partition count when the "mp" trainer is handed a bare HeteroGraph
    # (the memory-frugal setup: no in-process partition copies are ever
    # built). Ignored when an engine is passed — its partitioning wins.
    num_engine_partitions: int = 4
    # Hybrid serving threshold for the "mp" backend: a sampling round whose
    # total node count is at or below this is answered in-process by the
    # GraphClient over zero-copy views of its own shard segments (bitwise
    # identical to a worker reply — same core, same seeding). Small rounds
    # are latency-bound, so skipping the pipe round-trip wins whenever
    # workers contend with the trainer for cores; big rounds still go to
    # the fleet. 0 disables (every round crosses the process boundary).
    engine_local_threshold: int = 8192
    # Sampling front end. "host" streams batches from the NumPy pipeline
    # (walker + ego sampler against the graph engine, prefetch thread,
    # sparse dedup); "fused" runs walk->pair->ego as ONE jitted device
    # program (sampling/fused.py) inlined into the grad step — zero host
    # work per step — whenever the padded device tables fit
    # ``fused_budget_mb`` (otherwise it falls back to "host" with a
    # warning). Fused mode bypasses the prefetcher (nothing to prefetch)
    # and always applies the dense-table update — numerically identical
    # to the sparse path's row-wise AdaGrad (tests/test_sparse_updates).
    # "auto" lets the calibration phase choose: fused when the measured
    # fused step beats the best host-pipeline estimate (and the graph
    # passes the memory gate), host otherwise.
    sampling_backend: str = "host"  # host | fused | auto
    # Padded-adjacency width for the fused sampler's device tables.
    fused_max_degree: int = 32
    # Device-table budget (MiB) for the fused eligibility check.
    fused_budget_mb: float = 256.0
    # Candidate pairs generated per emitted pair in fused mode.
    fused_oversample: float = 2.0
    # Route the fused pair gather through the Pallas window-pair kernel.
    fused_use_kernel_pairs: bool = True
    # Run every jitted step dispatch under jax.transfer_guard("disallow")
    # (repro.lint.sanitizer): an implicit host<->device transfer in the hot
    # loop raises instead of silently serializing the pipeline. Explicit
    # jax.device_put/device_get stay legal; the guard is thread-local, so
    # the prefetch producer is covered by lint rule H002 instead.
    sanitize_transfers: bool = True
    # Record a per-phase time breakdown (sample/assemble/batch_wait/h2d/
    # dispatch/loss_fetch) into TrainResult.attribution via the sync-free
    # ring-buffer PhaseTimer (train/attribution.py). Off by default: zero
    # hot-loop cost beyond a None check.
    attribution: bool = False
    # Unified telemetry (repro.obs.Telemetry, default None = disabled): span
    # tracing across the step loop, prefetcher, GraphClient rounds, graph
    # workers, and retrieval, plus the metrics registry — exported as a
    # Perfetto-loadable Chrome trace (telemetry.write_trace) or text
    # summary. Disabled costs one is-None test per instrumented site
    # (`make bench-trace` pins the overhead at noise level).
    telemetry: Optional[object] = None
    # Run-health guardrails (repro.obs.health.HealthConfig, default None =
    # off): a watchdog thread that flight-records and fails the run on
    # stalls (no step within stall_timeout_s -> Perfetto snapshot +
    # all-thread stack dump + worker last-stats under flightrec/, then
    # RunStalledError), checks the async loss drain for NaN/Inf and EWMA
    # z-score divergence (no extra host sync), and folds in graph-worker
    # liveness from bounded heartbeat rounds. Off is a true no-op on the
    # step loop: losses are bitwise identical either way
    # (tests/test_health.py pins it).
    health: Optional[object] = None


@dataclasses.dataclass
class TrainResult:
    params: Dict
    losses: List[float]
    eval_history: List[Dict[str, float]]  # appended at each eval point
    wall_time_s: float
    pairs_seen: int
    # Resolved execution plan (sampling backend, prefetch depth, and — when
    # calibrated — the per-phase measurements the choice was made from).
    plan: Optional[Dict] = None
    # PhaseTimer summary when TrainerConfig.attribution is on.
    attribution: Optional[Dict] = None


_DONE = object()


class _Prefetcher:
    """Bounded background-thread prefetch between the host pipeline and the
    device loop. Producer exceptions re-raise in the consumer (original
    traceback preserved), and the consumer never blocks indefinitely: it
    polls the queue so a producer that dies without delivering its sentinel
    (hard crash, killed interpreter thread) surfaces as an error instead of
    hanging ``train()`` forever."""

    def __init__(
        self,
        it: Iterator,
        depth: int,
        queue_gauge=None,
        telemetry=None,
        health_check=None,
    ):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        # optional obs gauge tracking the queue's fill level (a persistently
        # empty queue = starved consumer, persistently full = device-bound)
        self._gauge = queue_gauge
        # wedged-producer incidents become a counter + an instant trace
        # mark (degraded runs visible in Perfetto, not just stderr)
        self._c_wedged = (
            telemetry.metrics.counter("prefetch.wedged_producer")
            if telemetry is not None else None
        )
        self._tracer = telemetry.tracer if telemetry is not None else None
        # optional HealthMonitor.check: a consumer polling an empty queue
        # still observes a watchdog-armed fault instead of spinning on a
        # producer that will never deliver
        self._health_check = health_check
        self._thread = threading.Thread(
            target=self._fill, args=(it,), name="repro-prefetch", daemon=True
        )
        self._thread.start()

    def _fill(self, it: Iterator) -> None:
        try:
            for item in it:
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        if self._gauge is not None:
                            self._gauge.set(self._q.qsize())
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # surfaced via __next__
            self._err = e
        finally:
            # The sentinel must land even when the queue is full, or the
            # consumer would block forever — keep trying until it fits or
            # the consumer has already closed us.
            while not self._stop.is_set():
                try:
                    self._q.put(_DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self) -> "_Prefetcher":
        return self

    def __next__(self):
        while True:
            try:
                item = self._q.get(timeout=0.5)
            except queue.Empty:
                if self._health_check is not None:
                    self._health_check()
                if self._thread.is_alive():
                    continue
                # Producer is gone. It may have enqueued its final batches
                # and sentinel in the window between our timeout and the
                # aliveness check — drain once more before declaring it dead
                # without a sentinel (killed mid-put / crashed outside the
                # guarded region).
                try:
                    item = self._q.get_nowait()
                except queue.Empty:
                    if self._err is not None:
                        raise self._err
                    raise RuntimeError(
                        "prefetch producer thread died without delivering a "
                        "batch or its error"
                    )
            if item is _DONE:
                self._thread.join(timeout=5.0)
                if self._thread.is_alive():
                    # Mirrors close(): a producer that delivered its sentinel
                    # but wedged before returning would otherwise leak into
                    # the next train() call unannounced.
                    log.warning(
                        "prefetch producer still running after its "
                        "end-of-stream sentinel; it is a daemon and will "
                        "exit with the process"
                    )
                    self._mark_wedged("after-sentinel")
                if self._err is not None:
                    # Same exception object -> original producer traceback.
                    raise self._err
                raise StopIteration
            return item

    def close(self) -> None:
        """Unblock and retire the producer (early consumer exit).

        The producer only observes the stop flag between queue puts, so a
        thread deep inside one sampling round can outlive the join timeout;
        it is a daemon and will die with the process, but warn so overlapping
        engine use (e.g. an immediate retrain) is explainable.
        """
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            log.warning(
                "prefetch producer still running after close(); it will exit "
                "after its current sampling round"
            )
            self._mark_wedged("close")

    def _mark_wedged(self, where: str) -> None:
        if self._c_wedged is not None:
            self._c_wedged.inc()
        if self._tracer is not None:
            self._tracer.mark("prefetch.wedged_producer", where=where)


def _round_spikes(durs: List[float]) -> List[int]:
    """Indices of round-paying batches in a per-batch duration series.

    Carry batches drain the round buffer in microseconds; a batch 4x over
    the median paid a sampling round. When every batch pays a round the
    median IS the round cost, nothing clears the threshold, and the caller
    falls back to the plain mean (which is then exact anyway).
    """
    if len(durs) < 2:
        return []
    thr = 4.0 * median(durs)
    return [i for i, d in enumerate(durs) if d > thr]


def _staged_batches(
    it: Iterator,
    timer: Optional[PhaseTimer] = None,
    double_buffer: bool = True,
    staged_gauge=None,
) -> Iterator:
    """Consumer-side H2D stager: the one explicit ``jax.device_put`` per
    batch, double-buffered.

    With ``double_buffer`` on (any prefetching run), batch k+1's host->device
    transfer is issued BEFORE batch k is yielded to the step loop, so the
    transfer overlaps the in-flight grad step k and the next device batch is
    always resident by the time its dispatch needs it — two device batches
    rotate, never more. The serial path (``double_buffer=False``) stages
    batches one at a time: pulling batch k+1 early there would just move
    inline sampling around, not overlap anything.

    Phases: "batch_wait" is time blocked on the upstream iterator (queue
    starvation under prefetch, inline sampling+assembly when serial);
    "h2d" is the device_put itself. Producer errors propagate unchanged.
    """
    it = iter(it)
    if not double_buffer:
        while True:
            with phase_scope(timer, "batch_wait"):
                item = next(it, _DONE)
            if item is _DONE:
                return
            with phase_scope(timer, "h2d"):
                staged = (jax.device_put(item[0]), item[1])
            if staged_gauge is not None:
                staged_gauge.set(1)
            yield staged
    with phase_scope(timer, "batch_wait"):
        item = next(it, _DONE)
    if item is _DONE:
        return
    with phase_scope(timer, "h2d"):
        pending = (jax.device_put(item[0]), item[1])
    while True:
        with phase_scope(timer, "batch_wait"):
            item = next(it, _DONE)
        if item is _DONE:
            if staged_gauge is not None:
                staged_gauge.set(1)
            yield pending
            return
        with phase_scope(timer, "h2d"):
            staged = (jax.device_put(item[0]), item[1])
        if staged_gauge is not None:
            staged_gauge.set(2)  # two device batches resident (double buffer)
        yield pending
        pending = staged


class Graph4RecTrainer:
    def __init__(
        self,
        dataset: RecsysDataset,
        engine,
        model_cfg: model_lib.Graph4RecConfig,
        pipe_cfg: PipelineConfig,
        cfg: TrainerConfig = TrainerConfig(),
    ):
        self.dataset = dataset
        # "mp" backend: move the partitions out of this process. The client
        # reuses the given engine's partitioning, so switching backends never
        # changes sampling semantics; passing a bare HeteroGraph instead
        # avoids ever materializing in-process partition copies (the client
        # then partitions straight into shared memory,
        # cfg.num_engine_partitions ways).
        self._owned_client = None
        # Auto worker sizing (num_engine_workers=0): half the visible cores —
        # the other half stays with the trainer process and XLA's own thread
        # pool. The client additionally clamps to its partition count.
        self._engine_workers = (
            cfg.num_engine_workers
            if cfg.num_engine_workers > 0
            else max(1, (os.cpu_count() or 2) // 2)
        )
        if cfg.engine_backend == "mp":
            from repro.graph.service import GraphClient

            if hasattr(engine, "graph"):  # a built engine: inherit its layout
                engine = GraphClient(
                    engine,
                    num_workers=self._engine_workers,
                    local_threshold=cfg.engine_local_threshold,
                    telemetry=cfg.telemetry,
                )
            else:
                engine = GraphClient(
                    engine,
                    num_partitions=cfg.num_engine_partitions,
                    num_workers=self._engine_workers,
                    local_threshold=cfg.engine_local_threshold,
                    telemetry=cfg.telemetry,
                )
            self._owned_client = engine
        elif cfg.engine_backend != "inproc":
            raise ValueError(f"unknown engine_backend {cfg.engine_backend!r}")
        self.engine = engine
        if cfg.use_kernel_aggr is not None and model_cfg.gnn is not None:
            model_cfg = dataclasses.replace(
                model_cfg,
                gnn=dataclasses.replace(
                    model_cfg.gnn, use_kernel_aggr=cfg.use_kernel_aggr
                ),
            )
        self.model_cfg = model_cfg
        self.pipe_cfg = pipe_cfg
        self.cfg = cfg
        # Both paths step embedding tables with the same row-wise AdaGrad
        # rule; dense applies it to every row, sparse to the gathered rows.
        self.opt = opt_lib.masked(
            opt_lib.rowwise_adagrad(
                cfg.sparse_lr, init_accum=cfg.adagrad_init_accum
            ),
            opt_lib.adam(cfg.dense_lr),
            select_a=lambda k: k.startswith("emb/"),
        )
        self._dense_opt = opt_lib.adam(cfg.dense_lr)
        # Per-table unique-id bucket widths; grown (and persisted) by
        # sparse_device_batch so the jitted sparse step keeps stable shapes.
        self._buckets: Dict[str, int] = {}
        if cfg.unique_bucket:
            self._buckets["node"] = cfg.unique_bucket
            for slot in model_cfg.embedding.slots:
                self._buckets[f"slot:{slot.name}"] = cfg.unique_bucket
        # Sparse/dense crossover (satellite of the throughput PR): on tables
        # below ``sparse_min_rows`` the sparse path's dedup+gather+scatter
        # overhead exceeds what it saves, so sparse_updates routes through
        # the dense step there. Bitwise-equivalent either way (PR-2 suite).
        num_nodes = dataset.graph.num_nodes
        self._sparse_on = cfg.sparse_updates and (
            cfg.sparse_min_rows <= 0 or num_nodes >= cfg.sparse_min_rows
        )
        if cfg.sparse_updates and not self._sparse_on:
            log.info(
                "sparse_updates requested but num_nodes=%d < sparse_min_rows="
                "%d; using the (equivalent, faster-at-this-size) dense step",
                num_nodes, cfg.sparse_min_rows,
            )
        # 'bag' side info: one count matrix per slot, built once and shared
        # by every batch (see embedding/table.py:embed_nodes_bag). The sparse
        # path instead ships a per-batch sub count matrix and never builds
        # the O(num_nodes x vocab) one.
        self._slot_counts = (
            model_lib.slot_count_arrays(dataset.graph, self.model_cfg)
            if (
                model_lib.bag_slot_specs(self.model_cfg)
                and not self._sparse_on
            )
            else None
        )
        # Fused device sampling: built eagerly for an explicit
        # sampling_backend="fused" (memory-gate fallback to host with a
        # warning), lazily by the calibration phase for "auto".
        self._fused_sampler = None
        self._fused_step = None
        # Measured device-table footprint once a fused sampler was built
        # (fed back through fused_eligibility; surfaced in the plan).
        self._fused_measured_bytes: Optional[int] = None
        # Per-train() observability state (run-health monitor + memory
        # accountant), kept for tests and post-mortem inspection.
        self._health_monitor = None
        self._memory = None
        self._plan: Optional[Dict] = None
        if cfg.sampling_backend == "fused":
            ok, why = self._build_fused()
            if ok:
                log.info("fused sampling backend active (%s)", why)
            else:
                log.warning(
                    "sampling_backend='fused' ineligible: %s; falling back "
                    "to the host pipeline", why,
                )
                if cfg.telemetry is not None:
                    cfg.telemetry.metrics.counter(
                        "trainer.fused_fallback"
                    ).inc()
                    cfg.telemetry.tracer.mark(
                        "trainer.fused_fallback", reason=why
                    )
        elif cfg.sampling_backend not in ("host", "auto"):
            raise ValueError(f"unknown sampling_backend {cfg.sampling_backend!r}")
        self._grad_step = jax.jit(self._make_grad_step())
        # The sparse step additionally donates its (single-use, per-step)
        # device batch — the stager's H2D buffers are recycled into the
        # update outputs. The dense step must NOT donate batches: dense
        # bag-mode batches alias the shared slot_count_arrays cache.
        self._sparse_step = jax.jit(
            self._make_sparse_step(), donate_argnums=(0, 1, 2)
        )
        self._train_pairs = np.concatenate(
            [np.stack([u, i], 1) for (u, i) in dataset.train_edges.values()],
            axis=0,
        )

    def _build_fused(self) -> Tuple[bool, str]:
        """Build the fused sampler + combined sample/grad step if the graph
        passes the memory gate. Idempotent; returns (built, reason)."""
        if self._fused_sampler is not None:
            return True, "already built"
        cfg = self.cfg
        fused_cfg = FusedConfig(
            max_degree=cfg.fused_max_degree,
            budget_mb=cfg.fused_budget_mb,
            oversample=cfg.fused_oversample,
            use_kernel_pairs=cfg.fused_use_kernel_pairs,
        )
        bspecs = model_lib.bag_slot_specs(self.model_cfg)
        vspecs = model_lib.value_slot_specs(self.model_cfg)
        ok, why = fused_eligibility(
            self.dataset.graph, self.pipe_cfg, vspecs, bspecs, fused_cfg
        )
        if not ok:
            return False, why
        self._fused_sampler = make_train_sampler(
            self.dataset.graph, self.pipe_cfg, backend="fused",
            seed=cfg.seed, value_slots=vspecs, bag_slots=bspecs,
            fused_cfg=fused_cfg,
            bag_counts=(
                model_lib.slot_count_arrays(self.dataset.graph, self.model_cfg)
                if bspecs else None
            ),
        )
        # The estimate admitted us; re-gate on the MEASURED footprint of
        # the tables the sampler actually shipped, so the logged decision
        # (and the plan) names real bytes. An estimate that undershot
        # enough to bust the budget tears the sampler back down.
        measured = self._fused_sampler.device_table_bytes()
        self._fused_measured_bytes = measured
        ok, why = fused_eligibility(
            self.dataset.graph, self.pipe_cfg, vspecs, bspecs, fused_cfg,
            measured_bytes=measured,
        )
        log.info(
            "fused eligibility: %s (measured %.1f MiB, budget %.1f MiB)",
            why, measured / (1 << 20), cfg.fused_budget_mb,
        )
        if not ok:
            self._fused_sampler = None
            return False, why
        self._fused_step = jax.jit(
            self._make_fused_step(), donate_argnums=(0, 1)
        )
        return True, why

    def _make_grad_step(self):
        mc = self.model_cfg

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(model_lib.loss_fn)(params, mc, batch)
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = opt_lib.apply_updates(params, updates)
            return params, opt_state, loss

        return step

    def _make_fused_step(self):
        """Sampling fused INTO the jitted grad step (sampling_backend=
        "fused"): the batch is produced on device from the PRNG key and the
        sampler's resident tables (arguments, never baked-in constants),
        so one dispatch per step covers walk, pair, ego, forward, backward
        and the update — the host only advances the key. Tables update
        through the dense full-table rule (identical numerics to the
        sparse path's row-wise AdaGrad) under buffer donation."""
        mc = self.model_cfg
        sampler = self._fused_sampler

        def step(params, opt_state, key, tables):
            batch = sampler.with_tables(tables).sample(key)
            loss, grads = jax.value_and_grad(model_lib.loss_fn)(params, mc, batch)
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = opt_lib.apply_updates(params, updates)
            return params, opt_state, loss

        return step

    def _make_sparse_step(self):
        """The gather→compute→scatter step (jitted with donated buffers).

        ``batch`` arrives id-remapped from ``sparse_device_batch``: its
        ``uniq`` entry names each table's touched global rows, and every id
        in the model inputs indexes the gathered sub-table. Gradients are
        taken w.r.t. the (bucket, dim) sub-tables only, so nothing in the
        step — forward, backward, or optimizer — is O(num_nodes).
        """
        mc = self.model_cfg
        cfg = self.cfg
        dense_opt = self._dense_opt

        def step(params, opt_state, batch):
            uniq = {f"emb/{k}": v for k, v in batch["uniq"].items()}
            model_batch = {k: v for k, v in batch.items() if k != "uniq"}
            sparse_p, dense_p = model_lib.sparse_dense_split(params)
            row_state, dense_state = opt_state
            # Tables the batch never touches (e.g. slot tables with side info
            # disabled) pass straight through — no gather, no grads.
            touched = {k: v for k, v in sparse_p.items() if k in uniq}
            sub = {k: emb.gather_rows(v, uniq[k]) for k, v in touched.items()}

            def loss_of(sub_tables, dense):
                return model_lib.loss_fn({**dense, **sub_tables}, mc, model_batch)

            loss, (g_sub, g_dense) = jax.value_and_grad(loss_of, argnums=(0, 1))(
                sub, dense_p
            )
            d_updates, dense_state = dense_opt.update(g_dense, dense_state, dense_p)
            dense_p = opt_lib.apply_updates(dense_p, d_updates)
            new_touched, touched_state = emb_opt.rowwise_adagrad_scatter_update(
                touched, g_sub, uniq, row_state,
                lr=cfg.sparse_lr, eps=1e-8,
            )
            row_state = emb_opt.RowAdagradState(
                accum={**row_state.accum, **touched_state.accum}
            )
            params = {**dense_p, **sparse_p, **new_touched}
            return params, (row_state, dense_state), loss

        return step

    def _init_sparse_opt_state(self, params: Dict):
        sparse_p, dense_p = model_lib.sparse_dense_split(params)
        return (
            emb_opt.rowwise_adagrad_init(
                sparse_p, init_accum=self.cfg.adagrad_init_accum
            ),
            self._dense_opt.init(dense_p),
        )

    def init_params(self, key: Optional[jax.Array] = None) -> Dict:
        key = key if key is not None else jax.random.PRNGKey(self.cfg.seed)
        return model_lib.init_model_params(key, self.model_cfg)

    # ------------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Reap engine worker processes (mp backend). Idempotent; also runs
        automatically when ``train()`` raises and on context-manager exit."""
        if self._owned_client is not None:
            self._owned_client.shutdown()

    def __enter__(self) -> "Graph4RecTrainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def evaluate(self, params, split: str = "val") -> Dict[str, float]:
        """Full recall evaluation: full-graph inference (repro.infer) +
        device-side retrieval (repro.core.recall / repro.retrieval). Every
        knob the old path hard-coded (top_n, user subsampling, method) is
        TrainerConfig-exposed; by default every held-out user is scored."""
        from repro.infer import embed_all_nodes

        ds = self.dataset
        tel = self.cfg.telemetry
        tracer = tel.tracer if tel is not None else None
        rng = np.random.default_rng(self.cfg.seed + 7)
        with span_scope(tracer, "infer.embed_all_nodes", cat="eval"):
            all_emb = embed_all_nodes(
                params, self.model_cfg, self.engine, ds.graph,
                batch_size=self.cfg.eval_batch_size, rng=rng,
            )
        user_emb = all_emb[: ds.num_users]
        item_emb = all_emb[ds.num_users : ds.num_users + ds.num_items]
        eval_pairs = ds.val_pairs if split == "val" else ds.test_pairs
        return evaluate_recall(
            user_emb, item_emb, self._train_pairs, eval_pairs,
            top_k=self.cfg.eval_top_k, top_n=self.cfg.eval_top_n,
            max_users=self.cfg.eval_max_users, method=self.cfg.eval_method,
            telemetry=tel,
        )

    def _host_batches(
        self, pipeline: SamplePipeline, num: int, timer=None
    ) -> Iterator[Tuple[Dict, int]]:
        """Host pipeline -> (HOST numpy batch pytree, num pairs); runs
        inside the prefetch thread so assembly — and, on the sparse path,
        the unique-id dedup + remap — overlaps device compute. The one H2D
        transfer per batch happens later, in the consumer-side
        ``_staged_batches`` stager, never hidden in this thread."""
        for batch in pipeline.batches(num):
            with phase_scope(timer, "assemble"):
                if self._sparse_on:
                    host = model_lib.sparse_host_batch(
                        self.dataset.graph, batch, self.model_cfg,
                        buckets=self._buckets,
                    )
                else:
                    host = model_lib.host_batch(
                        self.dataset.graph, batch, self.model_cfg,
                        slot_counts=self._slot_counts,
                    )
            yield host, len(batch.src_ids)

    def _fused_batch_iter(self) -> Iterator[Tuple[jax.Array, int]]:
        """Fused mode's stand-in for the batch stream: the "batch" fed to
        the jitted step is just the per-step PRNG key (sampling happens
        inside the step), so the prefetcher/stager have nothing to do and
        are bypassed entirely."""
        # One batched split, materialized eagerly (before the timed loop
        # starts): per-step fold_in dispatches would cost more than the
        # fused sample itself, and a lazy split would bill the first step.
        keys = list(
            jax.random.split(
                jax.random.PRNGKey(self.cfg.seed), max(self.cfg.num_steps, 1)
            )
        )
        npairs = self.pipe_cfg.batch_pairs
        return iter([(k, npairs) for k in keys[: self.cfg.num_steps]])

    # ------------------------------------------------------ backend planning
    def _copy_params(self, params: Dict) -> Dict:
        """Fresh device copies of a param pytree (donation-safe). device_put
        is the explicit H2D spelling (no-op on already-device leaves)."""
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x).copy(), params
        )

    def _calibrate(self, params: Dict) -> Dict:
        """Measure per-batch host cost, the jitted step, the prefetch
        handoff, and (sampling_backend="auto") the fused step.

        Every measurement runs on throwaway state: a SEPARATE same-seed
        pipeline instance (the training pipeline's stream is untouched, so
        a calibrated run is bitwise-identical to an explicitly-configured
        one) and fresh param/opt-state copies per step rep (the sparse and
        fused steps donate their inputs). The first rep of each series pays
        compile/warmup and is excluded from the medians.
        """
        cfg = self.cfg
        n = max(2, cfg.calibrate_batches)
        pipeline = make_train_sampler(
            self.engine, self.pipe_cfg, backend="host", seed=cfg.seed
        )
        # The host pipeline produces batches in ROUNDS: one walk+ego round
        # fills a carry buffer that the next several batches drain in
        # microseconds. Timing individual batches therefore bimodally mixes
        # round-paying spikes with near-free carries — the meaningful number
        # is the amortized cost over whole rounds. Pull batches until two
        # round spikes are visible and average the window between them;
        # when no second spike appears inside the budget (huge rounds, or
        # every batch pays a round so there are no spikes), fall back to
        # the plain mean, which then over- (never under-) estimates the
        # host cost and so can only bias toward prefetching — the safe
        # direction for an expensive sampler.
        cap, budget_s = 64, 0.5
        host_it = self._host_batches(pipeline, cap)
        durs: List[float] = []
        host_batches: List[Dict] = []
        elapsed = 0.0
        for i in range(cap):
            t0 = time.perf_counter()
            try:
                host, _np_ = next(host_it)
            except StopIteration:
                break
            d = time.perf_counter() - t0
            durs.append(d)
            elapsed += d
            if len(host_batches) < n:
                host_batches.append(host)
            if i + 1 < n:
                continue
            spikes = _round_spikes(durs)
            if len(spikes) >= 2 or elapsed >= budget_s:
                break
        spikes = _round_spikes(durs)
        if len(spikes) >= 2:
            host_s = sum(durs[spikes[0]:spikes[-1]]) / (spikes[-1] - spikes[0])
        else:
            host_s = elapsed / max(1, len(durs))
        meas: Dict = {"host_batch_s": host_s}
        step_times: List[float] = []
        for i in range(n):
            p = self._copy_params(params)
            if self._sparse_on:
                st = self._init_sparse_opt_state(p)
                fn = self._sparse_step
            else:
                st = self.opt.init(p)
                fn = self._grad_step
            dev = jax.device_put(host_batches[i % len(host_batches)])
            t0 = time.perf_counter()
            out = fn(p, st, dev)
            device_barrier(out[2])
            step_times.append(time.perf_counter() - t0)
        meas["step_s"] = median(step_times[1:])
        meas["handoff_s"] = measure_handoff_overhead()
        if cfg.sampling_backend == "auto":
            ok, why = self._build_fused()
            if ok:
                keys = jax.random.split(jax.random.PRNGKey(cfg.seed), n)
                fused_times: List[float] = []
                for i in range(n):
                    p = self._copy_params(params)
                    st = self.opt.init(p)
                    t0 = time.perf_counter()
                    out = self._fused_step(
                        p, st, keys[i], self._fused_sampler.tables()
                    )
                    device_barrier(out[2])
                    fused_times.append(time.perf_counter() - t0)
                meas["fused_step_s"] = median(fused_times[1:])
            else:
                meas["fused_ineligible"] = why
                if cfg.telemetry is not None:
                    cfg.telemetry.metrics.counter(
                        "trainer.fused_fallback"
                    ).inc()
                    cfg.telemetry.tracer.mark(
                        "trainer.fused_fallback", reason=why
                    )
        return meas

    def _resolve_plan(self, params: Dict) -> Dict:
        """Resolve the run's execution plan: sampling backend + prefetch
        depth. Explicit settings always win; knobs left at their "auto"
        defaults are decided from the calibration measurements (or legacy
        defaults when calibration is off / the run is too short). Cached —
        repeated train() calls on one trainer calibrate once."""
        if self._plan is not None:
            return self._plan
        cfg = self.cfg
        auto_prefetch = cfg.prefetch_batches is None
        auto_sampling = cfg.sampling_backend == "auto"
        plan: Dict = {
            "engine_backend": cfg.engine_backend,
            "engine_workers": (
                self._engine_workers if cfg.engine_backend == "mp" else None
            ),
            "calibrated": False,
        }
        calibrate = (
            cfg.auto_backend
            and (auto_prefetch or auto_sampling)
            and cfg.num_steps >= cfg.calibrate_min_steps
        )
        if not calibrate:
            plan["sampling"] = (
                "fused" if self._fused_sampler is not None
                and cfg.sampling_backend == "fused" else "host"
            )
            plan["prefetch"] = (
                0 if plan["sampling"] == "fused"
                else (2 if auto_prefetch else cfg.prefetch_batches)
            )
            plan["reason"] = (
                "explicit settings" if not (auto_prefetch or auto_sampling)
                else (
                    "auto_backend off" if not cfg.auto_backend
                    else f"run too short to calibrate "
                         f"(num_steps={cfg.num_steps} < "
                         f"{cfg.calibrate_min_steps}); legacy defaults"
                )
            )
            plan["fused_measured_bytes"] = self._fused_measured_bytes
            self._plan = plan
            return plan
        meas = self._calibrate(params)
        plan["calibrated"] = True
        plan["measurements"] = {k: round(v, 6) if isinstance(v, float) else v
                                for k, v in meas.items()}
        host_s, step_s = meas["host_batch_s"], meas["step_s"]
        handoff_s = meas["handoff_s"]
        # Prefetch pays only when BOTH sides have enough work to hide the
        # queue handoff: the pipelined step time is bounded below by the
        # slower side plus the handoff, and what the overlap can save is at
        # most the cheaper side. Require a clear (>10%) predicted win —
        # the probe can't see GIL contention between the producer's NumPy
        # work and the consumer's dispatches, which is exactly what made
        # prefetching a cheap walk-based sampler a 0.85x regression.
        serial_est = host_s + step_s
        prefetch_est = max(host_s, step_s) + handoff_s
        want_prefetch = serial_est > 1.1 * prefetch_est
        sampling = cfg.sampling_backend if not auto_sampling else "host"
        if auto_sampling and "fused_step_s" in meas:
            if meas["fused_step_s"] < min(serial_est, prefetch_est):
                sampling = "fused"
        if sampling == "fused" and self._fused_sampler is None:
            sampling = "host"  # explicit "fused" that failed the memory gate
        plan["sampling"] = sampling
        if sampling == "fused":
            plan["prefetch"] = 0
            plan["reason"] = (
                f"fused step {meas.get('fused_step_s', 0) * 1e3:.2f}ms < host "
                f"pipeline est {min(serial_est, prefetch_est) * 1e3:.2f}ms"
            )
        elif not auto_prefetch:
            plan["prefetch"] = cfg.prefetch_batches
            plan["reason"] = "explicit prefetch_batches"
        elif want_prefetch:
            plan["prefetch"] = 2
            plan["reason"] = (
                f"prefetch: serial est {serial_est * 1e3:.2f}ms > 1.1x "
                f"pipelined est {prefetch_est * 1e3:.2f}ms (host "
                f"{host_s * 1e3:.2f}ms, step {step_s * 1e3:.2f}ms, handoff "
                f"{handoff_s * 1e6:.0f}us)"
            )
        else:
            plan["prefetch"] = 0
            plan["reason"] = (
                f"serial: pipelining would save <10% (serial est "
                f"{serial_est * 1e3:.2f}ms vs pipelined est "
                f"{prefetch_est * 1e3:.2f}ms) — the queue handoff would "
                "cost more than the overlap hides"
            )
        log.info("backend plan: %s", plan["reason"])
        plan["fused_measured_bytes"] = self._fused_measured_bytes
        self._plan = plan
        return plan

    def train(self, params: Optional[Dict] = None) -> TrainResult:
        cfg = self.cfg
        params = params if params is not None else self.init_params()
        plan = self._resolve_plan(params)
        tel = cfg.telemetry
        tracer = tel.tracer if tel is not None else None
        # Run-health guardrails (cfg.health = a HealthConfig): the monitor
        # watches beats/pulses from its own watchdog thread and observes
        # only already-drained host losses, so enabling it never changes
        # the training stream. The instance is kept on self for tests and
        # post-mortems (trainer._health_monitor.fault, .degraded).
        monitor = None
        if cfg.health is not None:
            from repro.obs.health import HealthMonitor

            monitor = HealthMonitor(
                cfg.health, telemetry=tel, client=self._owned_client
            )
        self._health_monitor = monitor
        # Phase-boundary device-memory accounting (telemetry runs only):
        # live-array peaks per lifecycle phase, surfaced in the metrics
        # summary and the bench 'memory' section (trainer._memory).
        mem = None
        if tel is not None:
            from repro.obs.memory import MemoryAccountant

            mem = MemoryAccountant(tel.metrics)
        self._memory = mem
        # Tracing rides the attribution instrumentation: PhaseTimer with a
        # tracer emits every phase interval as a span (per-thread tracks in
        # the exported trace). The pinned TrainResult.attribution summary
        # stays gated on cfg.attribution alone.
        timer = (
            PhaseTimer(
                tracer=tracer,
                pulse=monitor.pulse if monitor is not None else None,
            )
            if (cfg.attribution or tracer is not None or monitor is not None)
            else None
        )
        use_fused = plan["sampling"] == "fused"
        if use_fused:
            # The fused step donates its param buffers; copy like the
            # sparse path so a caller-held pytree survives.
            params = self._copy_params(params)
            opt_state = self.opt.init(params)
            step_fn = functools.partial(
                self._fused_step, tables=self._fused_sampler.tables()
            )
        elif self._sparse_on:
            # The sparse step donates its param buffers; copy once so a
            # caller-held pytree (e.g. for a later cold-start eval) survives.
            params = self._copy_params(params)
            opt_state = self._init_sparse_opt_state(params)
            step_fn = self._sparse_step
        else:
            opt_state = self.opt.init(params)
            step_fn = self._grad_step
        loss_hist: List[jax.Array] = []  # in-flight on-device tail
        losses: List[float] = []  # drained, completed losses
        pending_drains: List = []  # started async readbacks, FIFO
        depth = plan["prefetch"]
        # Keep at least the prefetch window on device before draining; the
        # drained prefix is steps behind the last dispatch, so the readback
        # barely blocks — and it is started async and resolved a full
        # window later anyway.
        drain_tail = max(1, depth + 1)
        evals: List[Dict[str, float]] = []
        pairs_seen = 0
        steps_done = 0
        prefetcher: Optional[_Prefetcher] = None
        if use_fused:
            batch_iter: Iterator = self._fused_batch_iter()
        else:
            pipeline = make_train_sampler(
                self.engine, self.pipe_cfg, backend="host", seed=cfg.seed,
                timer=timer,
            )
            host_iter: Iterator = self._host_batches(
                pipeline, cfg.num_steps, timer
            )
            if depth > 0:
                prefetcher = _Prefetcher(
                    host_iter, depth,
                    queue_gauge=(
                        tel.metrics.gauge("prefetch.queue_depth")
                        if tel is not None else None
                    ),
                    telemetry=tel,
                    health_check=(
                        monitor.check if monitor is not None else None
                    ),
                )
                host_iter = prefetcher
            batch_iter = _staged_batches(
                host_iter, timer, double_buffer=depth > 0,
                staged_gauge=(
                    tel.metrics.gauge("stager.device_batches")
                    if tel is not None else None
                ),
            )
        if mem is not None:
            # everything long-lived is resident by now: params, opt state,
            # engine shards, and (fused runs) the device sampling tables
            mem.sample("fused" if use_fused else "tables")
        t0 = time.perf_counter()
        if monitor is not None:
            monitor.start()
        try:
            for step, (dev, npairs) in enumerate(batch_iter):
                # Every dispatch runs under the transfer guard: batches were
                # staged by an explicit device_put (or ARE device values —
                # fused keys), so any transfer here is a regression.
                with phase_scope(timer, "dispatch"):
                    with transfer_sanitizer(cfg.sanitize_transfers):
                        params, opt_state, loss = step_fn(
                            params, opt_state, dev
                        )
                loss_hist.append(loss)
                pairs_seen += npairs
                steps_done += 1
                if monitor is not None:
                    monitor.beat(step)
                if cfg.sync_every_step:
                    with phase_scope(timer, "loss_fetch"):
                        v = host_scalar(loss)
                    if monitor is not None:
                        monitor.observe_losses((v,))
                if (
                    cfg.loss_fetch_every
                    and len(loss_hist) >= cfg.loss_fetch_every + drain_tail
                ):
                    done, loss_hist = (
                        loss_hist[:-drain_tail], loss_hist[-drain_tail:]
                    )
                    with phase_scope(timer, "loss_fetch"):
                        # Resolve the PREVIOUS window (its copies have had a
                        # full window of dispatches to complete — near-free)
                        # and start this window's readback without blocking.
                        if pending_drains:
                            drained = pending_drains.pop(0).resolve()
                            losses.extend(drained)
                            if monitor is not None:
                                monitor.observe_losses(drained)
                        pending_drains.append(host_floats_async(done))
                if cfg.log_every and (step + 1) % cfg.log_every == 0:
                    log.info("step %d loss %.4f", step + 1, host_scalar(loss))
                if cfg.eval_every and (step + 1) % cfg.eval_every == 0:
                    evals.append(self.evaluate(params))
        except BaseException:
            # The run is aborted (producer error — possibly a dead engine
            # worker — or a caller interrupt): reap worker processes so
            # nothing outlives the failed train() call.
            self.close()
            raise
        finally:
            if monitor is not None:
                monitor.stop()
            if prefetcher is not None:
                prefetcher.close()
        if loss_hist:
            device_barrier(loss_hist[-1])
        wall = time.perf_counter() - t0
        # Everything is complete past the barrier: resolving the started
        # readbacks (FIFO — loss order is the dispatch order) and the tail
        # costs only the copies.
        observed = len(losses)  # mid-run drains already went past the monitor
        for drain in pending_drains:
            losses.extend(drain.resolve())
        losses.extend(host_floats(loss_hist))
        if monitor is not None:
            # the suffix never went through a mid-run drain window: a run
            # that diverged in its last steps still fails loudly
            monitor.observe_losses(losses[observed:])
        if mem is not None:
            mem.sample("steady")
        if cfg.eval_at_end:
            evals.append(self.evaluate(params))
            if mem is not None:
                mem.sample("eval")
        if tracer is not None and self._owned_client is not None:
            # pull worker serve spans recorded since the last stats round
            # into the tracer before the caller exports the trace
            self._owned_client.drain_worker_spans()
        return TrainResult(
            params=params, losses=losses, eval_history=evals,
            wall_time_s=wall, pairs_seen=pairs_seen, plan=dict(plan),
            attribution=(
                timer.summary(wall, steps_done)
                if (timer is not None and cfg.attribution) else None
            ),
        )
