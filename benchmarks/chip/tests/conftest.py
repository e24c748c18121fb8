"""Fixtures for the benchmark's own tests (outside the repo's tier-1
``testpaths``; run them with ``JAX_PLATFORMS=cpu python -m pytest -q
benchmarks/chip/tests``).

``tiny_root`` is a checkout in a temporary directory holding this
benchmark at a small UB deployment (300 users, 500 items, 6,900
behaviours), with its caches there too, so nothing is written into the
repository and everything runs on the CPU in seconds.
"""
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(REPO / "src"))

TINY = {"num_users": 300, "num_items": 500, "num_clusters": 10,
        "behaviors": {"click": 6000, "buy": 300, "cart": 400, "fav": 200}}


def make_root(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(CHIP, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        f = root / c["file"]
        d = json.loads(f.read_text())
        d["deployment"].update(TINY)
        d["trainer"]["unique_bucket"] = 4096
        d["trainer"]["sparse_min_rows"] = 0
        f.write_text(json.dumps(d))
    ret = root / "benchmarks/chip/traffic/retrieve.json"
    t = json.loads(ret.read_text())
    t["pool_batches"] = 1
    ret.write_text(json.dumps(t))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def cpu_devices(n):
    import jax

    return jax.devices()[:n]


def run_cell(root: Path, workload: str, seed: int = 7, seconds: float = 0.5,
             trace: int = 0):
    """One harness run on the CPU (the chip check skipped); its result."""
    import harness

    return harness.run(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        time.perf_counter(), root, cache=root / "benchmarks/chip/.cache",
        chip_check=cpu_devices)


def full_tower(fanouts, relations=1, live=0):
    """One ego tower in the reference's layout: every live slot has all
    its children under relation ``live`` and none under the others."""
    levels = [np.zeros((1, 1), np.int64)]
    for f in fanouts:
        block = np.full((levels[-1].shape[1], relations, f), -1, np.int64)
        block[levels[-1][0] >= 0, live] = 1
        levels.append(block.reshape(1, -1))
    return levels
