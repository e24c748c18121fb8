"""Set-up seconds before the window other than compilation: graph load or
generation, engine and sampler tables, weights, warm-up."""


def read(layer):
    return layer.get("setup_graph_s")
