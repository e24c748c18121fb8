"""The trace reduction on a small trace recorded on a v5e chip
(``record_trace.py``): four executions of a jitted ``step`` calling the
window-pair kernel, each in a ``bench.retrieve_batch`` annotation with a
host sleep between them, inside ``bench.window``."""
from pathlib import Path

import pytest

import trace_reduce

SAMPLE = str(Path(__file__).resolve().parent / "data" / "sample.xplane.pb")
ANCHOR_NS = 34389791671  # record_trace.py's printed anchor reading
KERNEL = (r"= \(s32\[29,18\]\S*, s32\[29,18\]\S*\) "
          r"custom-call\(s32\[29,6\].*tpu_custom_call")


def test_device_planes_and_window():
    devices, host = trace_reduce.read_planes(SAMPLE)
    # the Megascale plane holds no operations and is not a device here
    assert list(devices) == ["/device:TPU:0"]
    names = [n for n, _, _ in host if n.startswith("bench.")]
    assert names.count("bench.retrieve_batch") == 4
    r = trace_reduce.reduce(SAMPLE, modules=("jit_step",),
                            ops={"window_pairs": KERNEL})
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(12837687e-9)
    assert r["modules"]["jit_step"]["count"] == 4
    assert r["modules"]["jit_step"]["seconds"] == pytest.approx(
        (5030 + 4835 + 4836 + 5197) * 1e-9)
    assert r["ops"]["window_pairs"] == {"count": 4, "seconds": pytest.approx(
        (316 + 316 + 317 + 317) * 1e-9)}
    assert 0 < r["busy_s"] < r["modules"]["jit_step"]["seconds"]


def test_top_ops_and_idle_names():
    r = trace_reduce.reduce(SAMPLE, modules=("jit_step",))
    labels = [k for k, _ in r["top_ops"]]
    assert labels[0] == "%fusion"
    assert "%step.1 tpu_custom_call" in labels
    idle = dict(r["idle"])
    # the three 3 ms gaps between steps fall in the host's batch
    # annotations; sub-microsecond gaps inside the first step come before
    # any (the device clock leads the host's by about 1.2 ms here)
    assert set(idle) <= {"bench.retrieve_batch", "window edges", "untraced"}
    assert idle["bench.retrieve_batch"] > 6e-3
    assert idle.get("untraced", 0) < 1e-5
    total = sum(idle.values())
    assert total == pytest.approx(r["window_s"] - r["busy_s"])


def test_extra_spans_placed_by_the_anchor():
    # a span 0.3-0.5 ms after the anchor covers the first gap's midpoint
    spans = [("phase.x", ANCHOR_NS + 300_000, 200_000)]
    r = trace_reduce.reduce(SAMPLE, extra_spans=spans, anchor_ns=ANCHOR_NS)
    idle = dict(r["idle"])
    assert "phase.x" in idle and "bench.retrieve_batch" in idle
    assert idle["phase.x"] < idle["bench.retrieve_batch"]


def test_loops_are_not_leaves():
    # a while over two body operations, a nested loop, then a plain op
    ev = [("%while", 0, 100), ("%a", 1, 10), ("%b", 11, 20),
          ("%while.2", 30, 90), ("%c", 31, 40), ("%d", 100, 110)]
    assert trace_reduce.leaves(ev) == [False, True, True, False, True, True]


def test_op_label():
    assert trace_reduce.op_label(
        '%custom-call.9 = (f32[2]) custom-call(f32[2] %x), '
        'custom_call_target="TopK"') == "%custom-call.9 TopK"
    assert trace_reduce.op_label("%fusion.3 = f32[] fusion()") == "%fusion.3"
