"""Whole-call FLOP utilisation of retrieval: 2 * items * dim FLOPs per
query times queries per second, over the chip's bf16 peak."""


def read(layer):
    if not layer.get("peak") or not layer.get("queries_per_s"):
        return None
    flops = 2.0 * layer["items"] * layer["dim"] * layer["queries_per_s"]
    return 100.0 * flops / layer["peak"]["bf16_flops_per_s"]
