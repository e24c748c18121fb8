"""Roofline share of the exact top-k scan program (``_chunked_topk_scan``):
the least time its inner products and one read of the item table need
(``counts.topk_scan_cost``) over the program's device time in the trace."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import counts  # noqa: E402


def read(layer):
    m = layer.get("trace", {}).get("modules", {}).get("jit__chunked_topk_scan")
    if not m or not m["count"] or not m["seconds"]:
        return None
    flops, nbytes = counts.topk_scan_cost(layer["queries"], layer["items"],
                                          layer["dim"], layer["exclude"],
                                          layer["k"])
    least = counts.roofline_seconds(flops, nbytes, layer["peak"]) * m["count"]
    return 100.0 * least / m["seconds"]
