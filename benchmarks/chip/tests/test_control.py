"""The control, the reference in bfloat16 in the program's place, fails
each cell's committed limits, and the program's own readings pass them:
``control.py`` at the tiny deployment on the CPU. The limits themselves
were set from the same readings taken on the chip at the cells' sizes
(PERF.md, section 2)."""
import json

import pytest

import conftest
import control
import harness


def _readings(root, workload, seeds, controls):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = harness.resolve(bench, root, workload)
    ctx = {"name": workload, "config": cell["config"],
           "traffic": cell["traffic"],
           "cache": root / "benchmarks/chip/.cache", "log": lambda s: None,
           "devices": conftest.cpu_devices(1), "t_start": 0.0}
    rows = []
    run = control.training if cell["traffic"]["entry"] == "train" \
        else control.retrieval
    run(ctx, seeds, controls, lambda s, k, r: rows.append((k, r)))
    return rows, cell["limits"]


def _fails(readings, limits):
    return any(v > limits[k]["limit"] for k, v in readings.items()
               if k in limits)


@pytest.mark.parametrize("workload", ["lightgcn-ub.host", "gat-ub.fused",
                                      "lightgcn-ub.fused",
                                      "lightgcn-ub.retrieve"])
def test_control_fails_program_passes(tiny_root, workload):
    rows, limits = _readings(tiny_root, workload, [11, 12], {11})
    kinds = {k for k, _ in rows}
    assert "program" in kinds and "control_bf16" in kinds
    for kind, r in rows:
        if kind == "program":
            assert not _fails(r, limits), r
        elif kind in ("control_high", "control_default"):
            continue  # XLA:CPU runs every f32 dot in full: read on the chip
        else:  # the control, and a planted fault
            assert _fails(r, limits), (kind, r)
