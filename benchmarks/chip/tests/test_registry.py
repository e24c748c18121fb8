"""The harness finds a cell's files by name: a new configuration, mix,
entry, metric and limits file run with no edit to any existing file. And
a run that finds no chip, or no program, prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import conftest


def _add_dummy(root: Path) -> None:
    chip = root / "benchmarks" / "chip"
    (chip / "configs" / "dummy.json").write_text(json.dumps({"size": 3}))
    (chip / "traffic" / "dummy-mix.json").write_text(
        json.dumps({"entry": "dummy", "ops": 3}))
    (chip / "entries" / "dummy.py").write_text(
        "def run(ctx):\n"
        "    n = ctx['traffic']['ops'] * ctx['config']['size']\n"
        "    return {'e2e': {'ops_per_s': float(n), 'setup_s': 0.5},\n"
        "            'checks': {'exact': 0.0}, 'attempted': n,\n"
        "            'failed': 0, 'memory_peak_bytes': 0,\n"
        "            'layer': {'x': 2.5, 'trace': {'busy_s': 1.0,\n"
        "                      'window_s': 2.0, 'top_ops': [],\n"
        "                      'idle': []}}}\n")
    (chip / "metrics" / "dummy.x.py").write_text(
        "def read(layer):\n    return layer.get('x')\n")
    (chip / "limits" / "dummy.cell.json").write_text(
        json.dumps({"exact": {"limit": 0}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy", "source": "test",
                             "file": "benchmarks/chip/configs/dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "ops_per_s", "unit": "ops/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["dummy.cell"]})
    bench["per_layer"].append({"name": "dummy.x", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "ops_per_s",
                               "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_added_files_run_with_no_edit(tmp_path):
    root = conftest.make_root(tmp_path)
    chip = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    _add_dummy(root)
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
    out = conftest.run_cell(root, "dummy.cell")
    assert out["correct"] is True and out["attempted"] == 9
    assert out["metrics"] == {"ops_per_s": {"value": 9.0, "unit": "ops/s"},
                              "setup_s": {"value": 0.5, "unit": "s"}}
    assert list(out)[-1] == "checks"
    traced = conftest.run_cell(root, "dummy.cell", trace=1)
    # per-layer readers only; those listing other cells are not asked, and
    # one that finds nothing to read is left out
    assert traced["metrics"] == {"dummy.x": {"value": 2.5, "unit": "1"}}
    assert traced["device"]["busy_s"] == 1.0
    assert traced["device"]["window_s"] == 2.0


def _bare_run(root: Path, workload: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_and_bare_directory_print_no_result(tmp_path):
    root = conftest.make_root(tmp_path)
    p = _bare_run(root, "lightgcn-ub.host")  # a checkout on the CPU
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr
    (root / "src").unlink()  # only BENCHMARK.json and the benchmark's files
    shutil.rmtree(root / "benchmarks" / "chip" / "tests")
    p = _bare_run(root, "lightgcn-ub.host")
    assert p.returncode != 0 and p.stdout.strip() == ""
