"""Backend-compile seconds during set-up, from JAX's monitoring events."""


def read(layer):
    return layer.get("setup_compile_s")
