"""Whole-step model FLOP utilisation: the encoder's forward, backward and
loss FLOPs per step (``counts.train_step_flops``, needed work only, by
the encoder modules the entry resolved, counted on the towers of the
run's checked steps) times steps per second, over the chip's bf16
peak."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import counts  # noqa: E402


def read(layer):
    if not layer.get("peak") or not layer.get("pairs_per_s"):
        return None
    flops = counts.train_step_flops(layer["model"], layer["encoder"],
                                    layer["pairs"], layer["checked_towers"])
    steps_per_s = layer["pairs_per_s"] / layer["pairs"]
    return 100.0 * flops * steps_per_s / layer["peak"]["bf16_flops_per_s"]
