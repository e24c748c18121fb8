"""Share of the training window the step loop spent blocked on the host
pipeline's queue: PhaseTimer ``batch_wait`` total over the window."""


def read(layer):
    ph = (layer.get("phases") or {}).get("phases", {})
    if "batch_wait" not in ph or not layer.get("wall_s"):
        return None
    return 100.0 * ph["batch_wait"]["total_s"] / layer["wall_s"]
