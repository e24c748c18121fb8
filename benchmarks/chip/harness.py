"""The benchmark harness: everything shared by the cells.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness finds them by name, with no list of its own:

- ``configs/<config>.json``: one model and its deployment;
- ``traffic/<mix>.json``: one traffic mix; its ``entry`` names the module
  ``entries/<entry>.py`` that drives the window;
- ``metrics/<metric>.py``: one per-layer metric, a ``read(ctx)`` that
  returns a number or None where the run has nothing to read;
- ``limits/<workload>.json``: the limits of the cell's correctness numbers.

A training configuration's encoder is found the same way, by the
program's own two axes of it (``HeteroGNNConfig.gnn_type`` and
``relation_agg``): ``encoders/<model.gnn_type>.py`` is the per-relation
layer and ``mixes/<model.relation_agg>.py`` the relation mix of Eq. 3.
Each gives ``leaves(d)``, its leaves named as the program names them with
their shapes and initial scales at width d; ``forward``, in plain float32
``jax.numpy`` with nothing from the program; and ``flops``, the needed
forward FLOPs: a layer's ``flops(d)`` per live (parent, relation) pair
and per live child edge, a mix's ``flops(d, n)`` per live parent over
its n live relations. What every encoder shares stays in
``reference.py`` and ``counts.py``, which counts on the towers a run
encoded. The training entry loads the two (``encoder``) first,
before the graph is loaded or generated, so a configuration the
reference cannot check ends the run in seconds.

An entry's ``run(ctx)`` returns the end-to-end numbers, the correctness
numbers and whatever its per-layer readers consume; this module turns
that into the result line.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, NamedTuple, Optional

HERE = Path(__file__).resolve().parent
CACHE = HERE / ".cache"


class RunError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints none."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise RunError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Encoder(NamedTuple):
    layer: ModuleType
    mix: ModuleType


def encoder(model: Dict, here: Path) -> Encoder:
    """The modules of a training configuration's encoder under ``here``.
    Without ``relation_agg`` the mix is the program's default, uniform."""
    if model.get("side_info"):
        raise RunError("the reference does not model side_info: true")
    gnn, agg = model["gnn_type"], model.get("relation_agg", "uniform")
    return Encoder(
        load_module(here / "encoders" / f"{gnn}.py",
                    "bench_encoder_" + gnn.replace("-", "_")),
        load_module(here / "mixes" / f"{agg}.py",
                    "bench_mix_" + agg.replace("-", "_")))


def resolve(bench: Dict, root: Path, workload: str) -> Dict:
    """The workload's entry of ``BENCHMARK.json`` with its configuration,
    traffic, limits and metric lists attached."""
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    here = root / "benchmarks" / "chip"

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "workload": wl,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (here / "traffic" / f"{wl['traffic']}.json").read_text()),
        "limits": json.loads(
            (here / "limits" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
        "dir": here,
    }


def require_chip(chips: int):
    """The devices a cell runs on; no accelerator, or fewer chips than the
    cell asks for, ends the run without a result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise RunError(
            f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs[:chips]


def configure_jax(config: Dict, cache: Path) -> None:
    """JAX as every run of a cell needs it: the persistent compilation
    cache inside the checkout, and the matmul precision at which the
    configuration states its float32 arithmetic is done."""
    jax_cache = cache / "jax"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(jax_cache)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(jax_cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    precision = config.get("model", {}).get("matmul_precision")
    if precision:
        jax.config.update("jax_default_matmul_precision", precision)


def settle_heap(log) -> None:
    """Collect once and freeze what set-up left behind, so that no full
    collection over set-up's objects lands inside the window."""
    t = time.perf_counter()
    gc.collect()
    gc.freeze()
    log(f"[info] gc: set-up's heap collected in {time.perf_counter() - t:.3f}s,"
        f" {gc.get_freeze_count()} objects frozen")


class GcWatch:
    """``with GcWatch() as g:`` counts the collector's passes inside the
    window (``g.passes``) and the longest of them in seconds (``g.worst``)."""

    def __enter__(self):
        self.passes, self.worst, self._t = 0, 0.0, None
        gc.callbacks.append(self._pass)
        return self

    def _pass(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.passes += 1
            self.worst = max(self.worst, time.perf_counter() - self._t)

    def __exit__(self, *exc):
        gc.callbacks.remove(self._pass)
        return False


class CompileClock:
    """Backend-compile seconds and count, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Traced:
    """``with Traced(ctx, on) as t:`` profiles the window when ``on``:
    JAX's profiler wraps it, annotated ``bench.window``, with a
    ``bench.anchor`` mark that places perf_counter spans on its clock."""

    def __init__(self, ctx: Dict, on: bool):
        self.ctx, self.on = ctx, on
        self.dir = str(ctx["cache"] / "trace" / ctx["name"])
        self.anchor_ns: Optional[int] = None

    def __enter__(self):
        if not self.on:
            return self
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.anchor_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("bench.anchor"):
            pass
        self._win = jax.profiler.TraceAnnotation("bench.window")
        self._win.__enter__()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            self._win.__exit__(*exc)
            jax.profiler.stop_trace()
        return False

    def reduce(self, modules=(), ops=(), extra_spans=()) -> Dict:
        tr = load_module(HERE / "trace_reduce.py", "bench_trace_reduce")
        return tr.reduce(tr.find_xplane(self.dir), modules=modules, ops=ops,
                         extra_spans=extra_spans, anchor_ns=self.anchor_ns)


def peak_for(kind: str, here: Path) -> Dict:
    peaks = json.loads((here / "peaks.json").read_text())
    if kind not in peaks:
        raise RunError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def check_lines(readings: Dict[str, float], limits: Dict) -> Dict:
    """Each compared number beside its limit; a number passes at or under
    its limit. A reading the cell's limits file does not name has no upper
    reading to set a limit from, and is not compared."""
    return {name: {"value": value, "limit": limits[name]["limit"]}
            for name, value in readings.items() if name in limits}


def run(argv: List[str], t_start: float, root: Path,
        cache: Path = CACHE, chip_check=require_chip) -> Dict:
    """One run of one cell; returns the result object (also printed)."""
    import argparse

    ap = argparse.ArgumentParser(description="on-chip benchmark run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = resolve(bench, root, args.workload)
    configure_jax(cell["config"], cache)
    import jax

    devices = chip_check(int(cell["workload"]["chips"]))
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro  # noqa: F401  the system under test; absent -> no result

    clock = CompileClock()
    dev = devices[0]
    log(f"[info] device {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, workload {args.workload}, seed {args.seed}")
    here = cell["dir"]
    ctx = {
        "name": args.workload, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "config": cell["config"],
        "traffic": cell["traffic"], "limits": cell["limits"],
        "devices": devices, "clock": clock, "t_start": t_start,
        "cache": cache, "here": here, "log": log,
        "peak": (peak_for(dev.device_kind, here)
                 if dev.platform == "tpu" else None),
    }
    entry = load_module(here / "entries" / f"{cell['traffic']['entry']}.py",
                        "bench_entry_" + cell["traffic"]["entry"])
    res = entry.run(ctx)

    metrics = {}
    if args.trace:
        for m in cell["per_layer"]:
            reader = load_module(here / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(res["layer"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            if m["name"] not in res["e2e"]:
                raise RunError(f"entry reported no {m['name']}")
            metrics[m["name"]] = {"value": float(res["e2e"][m["name"]]),
                                  "unit": m["unit"]}
    checks = check_lines(res["checks"], cell["limits"])
    for name in sorted(set(res["checks"]) - set(checks)):
        log(f"[info] not compared: {name} {res['checks'][name]!r}")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if args.trace:
        tr = res["layer"]["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["top_ops"],
                            "idle_gaps": tr["idle"]}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    print(json.dumps(out), flush=True)
    return out
