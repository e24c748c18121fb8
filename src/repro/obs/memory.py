"""Device-memory accounting: per-phase high-water gauges over live arrays.

The fused-sampling budget gate (``fused_budget_mb``) has so far run on an
*estimate* — ``fused_device_bytes`` multiplies shapes before anything is
resident. This module closes the loop with two measured sources:

- :func:`live_array_bytes` — ``jax.live_arrays()`` summed by ``.nbytes``:
  every array the process currently holds alive on any device. Exact on
  all backends (CPU included), but enumeration walks a global registry,
  so it is a *phase-boundary* probe, never a per-step one.
- :func:`device_memory_stats` — the backend allocator's own counters
  (``device.memory_stats()``), which exist on real accelerators (and are
  required there) and return ``None`` on the CPU backend.

:class:`MemoryAccountant` samples those at coarse lifecycle boundaries
(tables built, fused adjacency resident, steady-state loop, eval) into
``memory.<phase>_bytes`` gauges whose high-water mark is the per-phase
peak, and ``summary()`` feeds the ``memory`` section of
``BENCH_throughput.json``. The trainer separately asks the
``FusedSampler`` for its *actual* device-table footprint
(``device_table_bytes()`` — the sum of the resident adjacency/schedule/
slot arrays) and re-runs ``fused_eligibility`` on the measured number, so
the budget decision is logged against bytes that exist rather than bytes
that were predicted.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional

from repro.utils import get_logger

log = get_logger("repro.obs.memory")


def live_array_bytes() -> int:
    """Total bytes of every live JAX array in this process (all devices).

    Returns 0 when JAX is unavailable or the registry walk fails — memory
    accounting is advisory and must never take a run down.
    """
    try:
        import jax

        return int(sum(int(a.nbytes) for a in jax.live_arrays()))
    except Exception as e:
        log.debug("live_array_bytes unavailable: %s", e)
        return 0


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """Allocator statistics per device; ``{}`` on the CPU backend.

    Accelerator backends report dicts like ``{"bytes_in_use": ...,
    "peak_bytes_in_use": ...}``; the CPU backend returns ``None`` from
    ``memory_stats()`` and contributes nothing here. An accelerator that
    reports nothing is an error, not a missing feature: every device
    memory number downstream would silently read as absent.
    """
    import jax

    out: Dict[str, Dict[str, int]] = {}
    for dev in jax.devices():
        stats = dev.memory_stats()
        if not stats:
            if dev.platform == "cpu":
                continue
            raise RuntimeError(f"{dev} ({dev.platform}) reports no memory_stats()")
        out[str(dev)] = {
            k: int(v) for k, v in stats.items() if isinstance(v, (int, float))
        }
    return out


def memory_snapshot() -> Dict:
    """One point-in-time reading: live-array total + allocator stats."""
    return {
        "live_array_bytes": live_array_bytes(),
        "device_stats": device_memory_stats(),
    }


class MemoryAccountant:
    """Phase-boundary high-water memory sampling.

    ``sample(phase)`` reads the live-array total, folds it into the
    per-phase peak, and (when a registry is wired) sets the
    ``memory.<phase>_bytes`` gauge — whose ``.max`` is then the phase's
    high-water mark across the run. ``scope(phase)`` samples on exit, the
    natural fit for ``span_scope``-bracketed regions.
    """

    def __init__(self, metrics=None):
        self._metrics = metrics
        self.peaks: Dict[str, int] = {}

    def sample(self, phase: str) -> int:
        n = live_array_bytes()
        if n > self.peaks.get(phase, -1):
            self.peaks[phase] = n
        if self._metrics is not None:
            self._metrics.gauge(f"memory.{phase}_bytes").set(n)
        return n

    @contextlib.contextmanager
    def scope(self, phase: str):
        """Sample at region exit — the footprint once the phase's arrays
        are resident (entry readings just repeat the previous phase)."""
        try:
            yield self
        finally:
            self.sample(phase)

    def summary(self) -> Dict:
        """The ``memory`` section: per-phase peaks + a final snapshot."""
        out: Dict = {"phase_peak_bytes": dict(self.peaks)}
        out.update(memory_snapshot())
        return out


def sample_scope(accountant: Optional[MemoryAccountant], phase: str):
    """Null-safe ``accountant.scope``: no accountant, no cost."""
    if accountant is None:
        return contextlib.nullcontext()
    return accountant.scope(phase)
