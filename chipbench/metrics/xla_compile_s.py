"""Seconds a traced decision in XLA backend compiles
(``backend_compile_and_load``, ``backend_compile``), every program's: the
seconds beside ``xla_compiles_in_window``'s count, and what the persistent
compile cache can save."""
from chipbench import spans


def read(run):
    return spans.jax_seconds(run, spans.COMPILE)
