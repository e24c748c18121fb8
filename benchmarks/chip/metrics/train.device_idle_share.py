"""Share of the traced training window in which no operation ran on the
device."""


def read(layer):
    tr = layer.get("trace")
    if not tr or not tr["window_s"] or "pairs_per_s" not in layer:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
