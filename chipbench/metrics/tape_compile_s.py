"""Seconds a traced decision in the host tape compile (``repro.tapes``:
``repro.scenarios.trajectory.compile_batch``, once per decision), less
JAX's tracing, lowering and compiles inside it."""
from chipbench import spans


def read(run):
    return spans.self_seconds(run, "repro.tapes")
