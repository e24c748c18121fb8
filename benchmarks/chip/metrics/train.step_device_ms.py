"""Device time of the jitted training step per step: the ``jit_step``
program's executions in the trace over the window's steps."""


def read(layer):
    m = layer.get("trace", {}).get("modules", {}).get("jit_step")
    if not m or not m["count"]:
        return None
    return 1e3 * m["seconds"] / m["count"]
