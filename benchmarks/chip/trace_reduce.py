"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData`` alone. Device planes are the
``/device:`` planes that hold an ``XLA Ops`` line: one event per executed
operation, named by its HLO text (``%fusion.3 = f32[...] fusion(...)``).
Their ``XLA Modules`` line holds one event per executed program
(``jit_<function name>(<fingerprint>)``). The harness starts the profiler
after set-up has drained the device and stops it after the window's final
barrier, so every device event in the trace is the window's work.

- ``window_s``: the length of the benchmark's ``bench.window`` annotation.
- ``busy_s``: the union of operation intervals, averaged over devices.
- ``modules``: per program-name prefix, device seconds and executions.
- ``ops``: per regular expression over operation text, the same.
- ``top_ops``: leaf operations (not a loop that holds others) by total
  device time, named by their HLO instruction (and custom-call target).
- ``idle``: idle time between busy intervals, named by what the host was
  doing at each gap's midpoint: the innermost host span there, from the
  trace's own ``bench.*`` annotations and from extra spans on the
  ``perf_counter_ns`` clock placed on the trace's clock by the
  ``bench.anchor`` annotation. The device clock in a trace can lead the
  host's by a millisecond or so, so a name is the host activity around the
  gap, not an exact attribution. Idle time before the first and after the
  last operation of the window is ``window edges``.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

ANCHOR = "bench.anchor"
WINDOW = "bench.window"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line) -> Iterable[Tuple[str, float, float]]:
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def leaves(events: Sequence[Tuple[str, float, float]]) -> List[bool]:
    """For each operation, whether it holds no other: a ``while`` or a
    ``conditional`` spans the operations of its body on the same line, and
    counting both would count that time twice."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1],
                                                     -events[i][2]))
    out = [True] * len(events)
    for a, b in zip(order, order[1:]):
        if events[b][1] < events[a][2]:
            out[a] = False
    return out


def op_label(text: str) -> str:
    """``%custom-call.9 TopK`` from an operation's HLO text."""
    name = text.split(" = ", 1)[0]
    m = _TARGET.search(text)
    return f"{name} {m.group(1)}" if m else name


def read_planes(path: str):
    """(device planes, host events): device planes as
    {plane name: {line name: [(name, start_ns, end_ns)]}}, host events as
    [(name, start_ns, end_ns)] from every host line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(_events(line)) for line in plane.lines}
            if "XLA Ops" in lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return devices, host


def reduce(
    path: str,
    modules: Sequence[str] = (),
    ops: Mapping[str, str] = (),
    extra_spans: Sequence[Tuple[str, int, int]] = (),
    anchor_ns: Optional[int] = None,
) -> Dict:
    """The reduction described in the module docstring.

    ``ops`` maps a name to a regular expression searched in each
    operation's HLO text. ``extra_spans`` are (name, start, duration) on
    ``perf_counter_ns``; ``anchor_ns`` is that clock's reading just before
    the ``bench.anchor`` annotation opened.
    """
    devices, host = read_planes(path)
    if not devices:
        raise ValueError(f"{path}: no device plane in the trace")
    ops = dict(ops)
    pats = {k: re.compile(v) for k, v in ops.items()}
    win = [(s, e) for n, s, e in host if n == WINDOW]
    spans = [(n, s, e) for n, s, e in host if n.startswith("bench.")
             and n not in (ANCHOR, WINDOW)]
    if extra_spans and anchor_ns is not None:
        anchors = [s for n, s, e in host if n == ANCHOR]
        if anchors:
            off = anchors[0] - anchor_ns
            spans += [(n, t + off, t + d + off) for n, t, d in extra_spans]
    spans.sort(key=lambda x: x[1])
    busy_total = 0.0
    window_s = 0.0
    mod = defaultdict(lambda: [0.0, 0])
    opt = defaultdict(lambda: [0.0, 0])
    top = defaultdict(float)
    idle = defaultdict(float)
    for lines in devices.values():
        op_ev = lines["XLA Ops"]
        busy = _merge([(s, e) for _, s, e in op_ev])
        busy_s = sum(e - s for s, e in busy) * 1e-9
        busy_total += busy_s
        if win:
            w = (win[0][1] - win[0][0]) * 1e-9
        else:
            w = (busy[-1][1] - busy[0][0]) * 1e-9 if busy else 0.0
        window_s = max(window_s, w)
        for name, s, e in lines.get("XLA Modules", []):
            for p in modules:
                if name.startswith(p):
                    mod[p][0] += (e - s) * 1e-9
                    mod[p][1] += 1
        leaf = leaves(op_ev)
        for (name, s, e), is_leaf in zip(op_ev, leaf):
            if is_leaf:
                top[op_label(name)] += (e - s) * 1e-9
            for k, pat in pats.items():
                if pat.search(name):
                    opt[k][0] += (e - s) * 1e-9
                    opt[k][1] += 1
        inner = 0.0
        j = 0
        for (_, ge_prev), (gs_next, _) in zip(busy, busy[1:]):
            gs, ge = ge_prev, gs_next
            mid = 0.5 * (gs + ge)
            while j < len(spans) and spans[j][2] < mid:
                j += 1
            best = None
            for n, s, e in spans[j:]:
                if s > mid:
                    break
                if e >= mid and (best is None or e - s < best[0]):
                    best = (e - s, n)
            idle[best[1] if best else "untraced"] += (ge - gs) * 1e-9
            inner += (ge - gs) * 1e-9
        idle["window edges"] += max(0.0, w - busy_s - inner)
    nd = len(devices)
    return {
        "devices": nd,
        "busy_s": busy_total / nd,
        "window_s": window_s,
        "modules": {k: {"seconds": v[0] / nd, "count": v[1] // nd}
                    for k, v in mod.items()},
        "ops": {k: {"seconds": v[0] / nd, "count": v[1] // nd}
                for k, v in opt.items()},
        "top_ops": sorted(([k, v / nd] for k, v in top.items()),
                          key=lambda kv: -kv[1])[:10],
        "idle": sorted(([k, v / nd] for k, v in idle.items() if v > 0),
                       key=lambda kv: -kv[1])[:10],
    }
