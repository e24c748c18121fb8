"""Host sampling time per step: PhaseTimer ``sample`` total (walks and
ego gathers in the producer thread) over the window's steps."""


def read(layer):
    ph = (layer.get("phases") or {}).get("phases", {})
    if "sample" not in ph or not layer.get("steps"):
        return None
    return 1e3 * ph["sample"]["total_s"] / layer["steps"]
