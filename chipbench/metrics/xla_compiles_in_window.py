"""XLA backend compiles made by the window's planning children during their
calls, from JAX's monitoring events (``/jax/core/compile/backend_compile_duration``)
in each child. A program the persistent cache holds is loaded, not compiled."""


def read(run):
    return run["compiles_in_window"]
