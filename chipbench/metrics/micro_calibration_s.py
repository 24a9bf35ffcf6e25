"""Seconds a traced decision in the micro calibration (``repro.micro``:
``repro.core.sim.measure_micro``'s measurement, once per planning child),
less JAX's tracing, lowering and compiles inside it."""
from chipbench import spans


def read(run):
    return spans.self_seconds(run, "repro.micro")
