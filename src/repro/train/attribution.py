"""Per-step time attribution for the training loop (`make bench-attr`).

BENCH_throughput.json showed the components flying and the pipeline
crawling (fused sampling 2.7-2.9x yet the fused pipeline 1.15x, the mp
engine 2.4x yet mp end-to-end 0.78x): the trainer loop, not the samplers,
had become the bottleneck, and nothing measured *where* a step's wall time
went. This module is the measuring half of the fix: a sync-free phase
timer the trainer threads through the hot loop, plus the handoff-overhead
probe the auto backend calibration uses.

Design constraints (the H001/H002 lint contract):

- **Sync-free on the hot path.** ``PhaseTimer`` records
  ``time.perf_counter_ns()`` durations into preallocated ring buffers
  (``repro.obs.trace.DurationRing`` — the timer is a thin layer over the
  telemetry subsystem, and optionally mirrors each phase interval as an
  obs span) — no device sync, no allocation, no locks per step. The one
  ``device_barrier`` lives at the end of the measured window (the trainer
  already drains there), never per step.
- **Dispatch != execution.** The "dispatch" phase measures enqueue cost
  of the async jitted step, not device execution. Device time shows up as
  the residual ``wall - consumer-side phases`` (and as blocking inside
  "loss_fetch"/"batch_wait" when the device is the straggler).
- **Single writer per phase.** The producer thread records
  "sample"/"assemble", the consumer thread "h2d"/"batch_wait"/
  "dispatch"/"loss_fetch"; phase buffers are independent so no
  synchronization is needed. Producer-side totals can legitimately exceed
  wall time fractions when overlapped with device compute — that overlap
  is exactly what the report makes visible.

Phases:

- ``sample``   — walker + ego sampling rounds (host pipeline, producer side)
- ``walk``, ``pairs``, ``ego`` — the stages of a round, nested in ``sample``:
  ``walker.generate``; window pairs and their shuffle; ego sampling
- ``assemble`` — TrainBatch -> host numpy pytree (dedup/remap/padding)
- ``batch_wait`` — consumer blocked on the prefetch queue (starvation)
- ``h2d``      — explicit ``jax.device_put`` staging of a host batch
- ``dispatch`` — enqueue of the jitted grad step (async)
- ``loss_fetch`` — draining completed loss scalars to host

Counters (``count``), summed per batch by the producer on the sparse path:

- ``rows.unique`` — non-PAD rows of the node bucket (rows really updated)
- ``rows.bucket`` — the bucket's width (rows gathered and scattered)
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Dict, Iterable, Optional

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import DurationRing, Tracer

PHASES = ("sample", "walk", "pairs", "ego", "assemble", "batch_wait", "h2d",
          "dispatch", "loss_fetch")
COUNTERS = ("rows.unique", "rows.bucket")


class PhaseTimer:
    """Ring-buffered wall-clock attribution of trainer-loop phases.

    ``with timer.phase("dispatch"): ...`` appends one duration to the
    phase's ring buffer (an ``obs.trace.DurationRing``). Buffers are
    fixed-size (``capacity`` per phase); when a run exceeds capacity the
    retained window is extrapolated by count in :meth:`summary`, so long
    runs stay O(capacity) memory with no hot-loop branching.

    Rebase note (telemetry PR): the timer is now a thin aggregation layer
    over ``repro.obs`` — durations land in obs duration rings, and when an
    optional ``tracer`` is wired each phase interval is additionally
    emitted as a span, so the attribution phases appear on the Perfetto
    timeline with per-thread tracks for free. The public API and the
    ``summary()`` schema (the pinned ``step_attribution`` benchmark
    format) are unchanged.
    """

    def __init__(
        self,
        capacity: int = 8192,
        tracer: Optional[Tracer] = None,
        pulse=None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        self._cap = int(capacity)
        self._dur: Dict[str, DurationRing] = {
            p: DurationRing(self._cap) for p in PHASES
        }
        self._tracer = tracer
        # optional sub-step liveness callback (HealthMonitor.pulse): fired
        # at every phase exit, so the stall watchdog can tell "steps are
        # slow but phases still move" from "everything froze"
        self._pulse = pulse
        # counters live in the telemetry's registry when one is wired; the
        # summary reports what this timer's run added to them
        registry = metrics if metrics is not None else MetricsRegistry()
        self._counters = {c: registry.counter(c) for c in COUNTERS}
        self._base = {c: k.value for c, k in self._counters.items()}

    def add(self, name: str, seconds: float) -> None:
        self._dur[name].add(seconds)

    def count(self, name: str, n: int) -> None:
        self._counters[name].inc(n)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            dur_ns = time.perf_counter_ns() - t0
            self._dur[name].add(dur_ns * 1e-9)
            if self._tracer is not None:
                self._tracer.add_span(name, "phase", t0, dur_ns)
            if self._pulse is not None:
                self._pulse()

    def total(self, name: str) -> float:
        """Total seconds attributed to ``name`` (ring window extrapolated)."""
        return self._dur[name].total()

    def summary(
        self, wall_s: Optional[float] = None, steps: Optional[int] = None
    ) -> Dict:
        """Per-phase totals/means + consumer-side accounting vs wall time.

        ``host_visible_s`` sums the phases that run on the consumer thread
        and therefore directly extend the step loop; ``device_residual_s``
        is the remaining wall time — device execution plus anything not
        instrumented. Producer phases ("sample"/"assemble") overlap device
        compute when prefetching, so their fractions are reported against
        wall but may legitimately sum past it. ``counters`` holds what this
        timer added to its counters, when it added any.
        """
        phases: Dict[str, Dict] = {}
        for p in PHASES:
            n = self._dur[p].count
            if n == 0:
                continue
            tot = self.total(p)
            entry = {"count": n, "total_s": round(tot, 6),
                     "per_call_us": round(tot / n * 1e6, 2)}
            if wall_s:
                entry["frac_of_wall"] = round(tot / wall_s, 4)
            phases[p] = entry
        out: Dict = {"phases": phases}
        counters = {c: k.value - self._base[c]
                    for c, k in self._counters.items()}
        if any(counters.values()):
            out["counters"] = counters
        if wall_s is not None:
            out["wall_s"] = round(wall_s, 6)
            consumer = ("batch_wait", "h2d", "dispatch", "loss_fetch")
            host_vis = sum(
                self.total(p) for p in consumer if self._dur[p].count
            )
            out["host_visible_s"] = round(host_vis, 6)
            out["device_residual_s"] = round(max(0.0, wall_s - host_vis), 6)
        if steps:
            out["steps"] = int(steps)
            if wall_s is not None:
                out["wall_us_per_step"] = round(wall_s / steps * 1e6, 2)
        return out


def phase_scope(timer: Optional[PhaseTimer], name: Optional[str]):
    """``timer.phase(name)`` when attribution is wired, else a no-op
    context — call sites thread one optional timer without branching."""
    if timer is None or name is None:
        return contextlib.nullcontext()
    return timer.phase(name)


def measure_handoff_overhead(items: int = 512, depth: int = 2) -> float:
    """Measured per-item cost (seconds) of the prefetch queue handoff.

    Spins a producer thread pushing ``items`` tokens through a bounded
    ``queue.Queue`` (the exact structure ``_Prefetcher`` uses) while the
    caller consumes them, and returns wall / items. This is the floor a
    host sampler must clear for prefetching to pay: when a batch costs
    less to *produce* than to *hand over*, the serial path wins
    (BENCH_throughput.json's 0.85x walk-based prefetch regression). The
    auto backend calibration compares this number against the measured
    per-batch host cost.
    """
    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    token = object()

    def produce() -> None:
        for _ in range(items):
            q.put(token)

    t = threading.Thread(
        target=produce, name="repro-handoff-probe", daemon=True
    )
    t0 = time.perf_counter()
    t.start()
    for _ in range(items):
        q.get()
    wall = time.perf_counter() - t0
    t.join()
    return wall / items


def median(xs: Iterable[float]) -> float:
    """Median of a small sample (calibration helper; no numpy dtype games)."""
    s = sorted(xs)
    if not s:
        raise ValueError("median of empty sample")
    mid = len(s) // 2
    if len(s) % 2:
        return s[mid]
    return 0.5 * (s[mid - 1] + s[mid])
