"""Set-up seconds the graph engine took to partition the graph: the
``engine`` stage of the trainer's attribution ``setup`` section."""


def read(layer):
    return ((layer.get("phases") or {}).get("setup") or {}).get("engine")
