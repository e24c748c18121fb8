"""The per-layer readers of the trainer's attribution sections, on
synthetic ``layer`` dicts: each case beside its expected value, and the
run with nothing to read beside its None."""
import pytest

import conftest
import harness

PHASES = {"phases": {"sample": {"total_s": 0.6}, "walk": {"total_s": 0.1},
                     "pairs": {"total_s": 0.05}, "ego": {"total_s": 0.4}},
          "counters": {"rows.unique": 3000, "rows.bucket": 12000},
          "setup": {"engine": 3.5, "fused_tables": 61.25,
                    "train_pairs": 0.75}}
FULL = {"phases": PHASES, "steps": 100}
NO_ATTRIBUTION = {"steps": 100}  # an untraced run's layer has no phases
FUSED = {"phases": {"phases": {"dispatch": {"total_s": 0.2}},
                    "setup": {"engine": 3.5, "fused_tables": 61.25}},
         "steps": 100}


def read(name, layer):
    mod = harness.load_module(conftest.CHIP / "metrics" / f"{name}.py",
                              "reader_" + name.replace(".", "_"))
    return mod.read(layer)


@pytest.mark.parametrize("name,layer,want", [
    ("train.walk_ms", FULL, 1.0),
    ("train.walk_ms", FUSED, None),
    ("train.walk_ms", NO_ATTRIBUTION, None),
    ("train.walk_ms", {"phases": PHASES, "steps": 0}, None),
    ("train.pairs_ms", FULL, 0.5),
    ("train.pairs_ms", FUSED, None),
    ("train.ego_ms", FULL, 4.0),
    ("train.ego_ms", NO_ATTRIBUTION, None),
    ("train.unique_row_share", FULL, 25.0),
    ("train.unique_row_share", FUSED, None),
    ("train.unique_row_share", NO_ATTRIBUTION, None),
    ("train.unique_row_share",
     {"phases": {"counters": {"rows.bucket": 4096}}}, 0.0),
    ("setup.engine_s", FULL, 3.5),
    ("setup.engine_s", FUSED, 3.5),
    ("setup.engine_s", NO_ATTRIBUTION, None),
    ("setup.engine_s", {"phases": {"setup": {"train_pairs": 0.7}}}, None),
    ("setup.fused_tables_s", FUSED, 61.25),
    ("setup.fused_tables_s", {"phases": {"setup": {"engine": 3.5}}}, None),
    ("setup.fused_tables_s", NO_ATTRIBUTION, None),
])
def test_reader(name, layer, want):
    got = read(name, layer)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
