"""Seconds a traced decision in JAX's tracing to a jaxpr and lowering to
MLIR (``trace_to_jaxpr_dynamic``, ``lower_sharding_computation``), every
program's. The persistent compile cache does not save this: a program is
traced and lowered to compute its cache key."""
from chipbench import spans


def read(run):
    return spans.jax_seconds(run, spans.TRACE_LOWER)
