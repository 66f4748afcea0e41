"""Programs compiled (or fetched from the compile cache) inside the window:
``jax.monitoring`` backend-compile events the harness counted there."""


def read(art):
    return art.get("compiles_in_window")
