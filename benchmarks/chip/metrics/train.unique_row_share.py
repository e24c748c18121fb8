"""Share of the sparse step's gathered and scattered rows that are real:
PhaseTimer counters ``rows.unique`` (non-PAD ids of each batch's node
bucket) over ``rows.bucket`` (the bucket's width), summed over the window."""


def read(layer):
    c = (layer.get("phases") or {}).get("counters") or {}
    if not c.get("rows.bucket"):
        return None
    return 100.0 * c.get("rows.unique", 0) / c["rows.bucket"]
