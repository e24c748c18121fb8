"""The harness finds a cell's files by name: a new configuration, mix,
entry, metric and limits file run with no edit to any existing file, and
so does a new encoder of the program's zoo, whose reference and count are
files of their own, and a configuration over all eight UB relations,
whose count follows the relations its towers hold. A configuration the
reference does not model ends the run before its graph is made. And a
run that finds no chip, or no program, prints no result."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import conftest
import counts
import harness
import reference


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


SAGE_MEAN = """\
import jax.numpy as jnp
import numpy as np


def leaves(d):
    return {"w": ((2 * d, d), np.sqrt(2.0 / (3 * d)))}


def forward(p, h_self, h_nbr, mask):
    m = mask[..., None].astype(h_nbr.dtype)
    agg = (h_nbr * m).sum(-2) / jnp.maximum(m.sum(-2), 1)
    return jnp.maximum(jnp.concatenate([h_self, agg], -1) @ p["w"], 0)


def flops(d):
    return 2 * (2 * d) * d + 2 * d, d
"""


def _add_host_cell(root: Path, name: str, **model) -> str:
    """Configuration ``name``, lightgcn-ub's with ``model``'s keys changed,
    in a cell on the host mix with lightgcn-ub.host's limits; the cell's
    name."""
    chip = root / "benchmarks" / "chip"
    cell = f"{name}.host"
    cfg = json.loads((chip / "configs" / "lightgcn-ub.json").read_text())
    cfg["name"] = name
    cfg["model"].update(model)
    (chip / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (chip / "limits" / f"{cell}.json").write_text(
        (chip / "limits" / "lightgcn-ub.host.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"benchmarks/chip/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": "host", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("pairs_per_s", "train.mfu"):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


def _add_sage_mean(root: Path) -> str:
    """GraphSAGE-mean as core/gnn.py computes it, relu([h_self, masked
    mean] @ w) with w of shape (2d, d), in a cell on the host mix."""
    chip = root / "benchmarks" / "chip"
    (chip / "encoders" / "sage-mean.py").write_text(SAGE_MEAN)
    return _add_host_cell(root, "sage-ub", gnn_type="sage-mean")


class _NamedChip:
    """A CPU device that reports itself as a v5e, so that a traced run on
    the CPU takes that chip's peaks."""

    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {}


def _traced_on_named_chip(root: Path, workload: str, monkeypatch):
    """A traced run on the CPU taken for a v5e: the CPU's trace has no
    device plane, so the run reads one made up."""
    monkeypatch.setattr(harness.Traced, "reduce", lambda self, **kw: {
        "busy_s": 1.0, "window_s": 2.0, "modules": {}, "ops": {},
        "top_ops": [], "idle": []})
    return harness.run(
        ["--workload", workload, "--seed", "7", "--seconds", "0.5",
         "--trace", "1"], 0.0, root, cache=root / "benchmarks/chip/.cache",
        chip_check=lambda n: [_NamedChip()])


def _unedited(root: Path, add):
    """Runs ``add(root)`` and checks that it edited no file of the
    benchmark; what ``add`` returns."""
    chip = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    out = add(root)
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
    return out


def test_zoo_encoder_enters_as_files(tmp_path, monkeypatch):
    root = conftest.make_root(tmp_path)
    chip = root / "benchmarks" / "chip"
    cell = _unedited(root, _add_sage_mean)
    out = conftest.run_cell(root, cell)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"pairs_per_s", "setup_s"}
    # d = 64, fanouts (4, 3), one live relation of two: 6 parents, 20
    # edges; per parent 4d^2 + 2d and the residual's 3d, per edge d
    cfg = json.loads((chip / "configs" / "sage-ub.json").read_text())
    enc = harness.encoder(cfg["model"], chip)
    assert counts.tower_flops(cfg["model"], enc,
                              conftest.full_tower([4, 3], 2)) == (
        6 * (4 * 64 * 64 + 2 * 64 + 3 * 64) + 20 * 64)
    traced = _traced_on_named_chip(root, cell, monkeypatch)
    assert traced["correct"] is True
    assert traced["metrics"]["train.mfu"]["value"] > 0


UB8 = [f"{a}2{b}2{c}" for b in ("click", "cart", "fav", "buy")
       for a, c in (("u", "i"), ("i", "u"))]


def _lightgcn_uniform_flops(levels, fanouts, R, d):
    """LightGCN's needed forward FLOPs under the uniform mix, slot by slot:
    per live parent the residual (3d) and the n - 1 adds of its n live
    relations; per live relation a divide per dim; per live child an add
    per dim."""
    total = 0
    for layer in range(len(fanouts)):
        for k in range(len(fanouts) - layer):
            par = levels[k]
            kids = levels[k + 1].reshape(*par.shape, R, fanouts[k])
            for b, w in zip(*np.nonzero(par >= 0)):
                live = [int((kids[b, w, r] >= 0).sum()) for r in range(R)]
                n = sum(1 for m in live if m)
                total += 3 * d + max(n - 1, 0) * d + n * d + sum(live) * d
    return total


def test_eight_relations_enter_as_files(tmp_path, monkeypatch):
    root = conftest.make_root(tmp_path)
    cell = _unedited(root, lambda r: _add_host_cell(
        r, "lightgcn-ub8", relations=UB8))
    out = conftest.run_cell(root, cell)
    assert out["correct"] is True, out["checks"]
    # what the reference checked, and what the mfu reader was given
    checked, layers = [], []
    train_steps = reference.train_steps

    def spy_steps(params0, model, enc, opt, batches, *a):
        checked.append([b["towers"] for b in batches])
        return train_steps(params0, model, enc, opt, batches, *a)

    monkeypatch.setattr(reference, "train_steps", spy_steps)
    load = harness.load_module

    def spy_load(path, name):
        mod = load(path, name)
        if name == "bench_metric_train_mfu":
            read = mod.read
            mod.read = lambda layer: layers.append(layer) or read(layer)
        return mod

    monkeypatch.setattr(harness, "load_module", spy_load)
    traced = _traced_on_named_chip(root, cell, monkeypatch)
    assert traced["correct"] is True
    fan, d, P = [4, 3], 64, layers[0]["pairs"]
    # towers of 801 slots (level 2: 32 x 8 x 3), with parents that mix
    # more than one live relation
    assert checked[0][0][2].shape[1] == 4 * 8 * 8 * 3
    assert max(counts.tower_work(checked[0][0], fan, 8)[2]) >= 2
    fwd = np.mean([_lightgcn_uniform_flops(t, fan, 8, d)
                   for t in checked[0]])
    flops = 3 * (fwd + 2 * P * P * d + 4 * P * P)
    want = 100 * flops * layers[0]["pairs_per_s"] / P / 197e12
    assert traced["metrics"]["train.mfu"]["value"] == pytest.approx(
        want, rel=1e-12)


@pytest.mark.parametrize("change,named", [
    ({"relation_agg": "no-such-mix"}, "mixes/no-such-mix.py"),
    ({"side_info": True}, "side_info"),
])
def test_unmodelled_encoder_ends_before_the_graph(tmp_path, monkeypatch,
                                                  change, named):
    root = conftest.make_root(tmp_path)
    f = root / "benchmarks" / "chip" / "configs" / "lightgcn-ub.json"
    cfg = json.loads(f.read_text())
    cfg["model"].update(change)
    f.write_text(json.dumps(cfg))
    loaded = []
    load = harness.load_module

    def spy(path, name):
        loaded.append(Path(path).name)
        return load(path, name)

    monkeypatch.setattr(harness, "load_module", spy)
    with pytest.raises(SystemExit, match=re.escape(named)):
        conftest.run_cell(root, "lightgcn-ub.host")
    assert "ub.py" not in loaded


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
