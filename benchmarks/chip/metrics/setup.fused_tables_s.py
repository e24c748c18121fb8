"""Set-up seconds of the fused sampler's device tables (sampler, padded
adjacency, transfer, re-gate): the ``fused_tables`` stage of the trainer's
attribution ``setup`` section."""


def read(layer):
    return ((layer.get("phases") or {}).get("setup") or {}).get(
        "fused_tables")
