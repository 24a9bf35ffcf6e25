"""Seconds a traced decision in the per-seed verdict tapes
(``repro.verdicts``: the detector's loop in
``repro.scenarios.trajectory._resolve_program``, once per candidate), less
JAX's tracing, lowering and compiles inside it."""
from chipbench import spans


def read(run):
    return spans.self_seconds(run, "repro.verdicts")
