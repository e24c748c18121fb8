"""Host pair time per step: PhaseTimer ``pairs`` total (window pairs and
their shuffle, nested in ``sample``) over the window's steps."""


def read(layer):
    ph = (layer.get("phases") or {}).get("phases", {})
    if "pairs" not in ph or not layer.get("steps"):
        return None
    return 1e3 * ph["pairs"]["total_s"] / layer["steps"]
