"""Roofline share of the fused sampler's window-pair kernel: the least
time its bytes and operations need (``counts.window_pairs_cost``) over its
device time in the trace."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import counts  # noqa: E402


def read(layer):
    k = layer.get("trace", {}).get("ops", {}).get("window_pairs")
    if not layer.get("fused") or not k or not k["count"] or not k["seconds"]:
        return None
    flops, nbytes = counts.window_pairs_cost(layer["walks"], layer["walk_len"],
                                             layer["npos"])
    least = counts.roofline_seconds(flops, nbytes, layer["peak"]) * k["count"]
    return 100.0 * least / k["seconds"]
