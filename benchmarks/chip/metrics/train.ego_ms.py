"""Host ego time per step: PhaseTimer ``ego`` total (ego sampling through
the engine, nested in ``sample``) over the window's steps."""


def read(layer):
    ph = (layer.get("phases") or {}).get("phases", {})
    if "ego" not in ph or not layer.get("steps"):
        return None
    return 1e3 * ph["ego"]["total_s"] / layer["steps"]
