"""Host walk time per step: PhaseTimer ``walk`` total (``walker.generate``,
nested in ``sample``) over the window's steps."""


def read(layer):
    ph = (layer.get("phases") or {}).get("phases", {})
    if "walk" not in ph or not layer.get("steps"):
        return None
    return 1e3 * ph["walk"]["total_s"] / layer["steps"]
