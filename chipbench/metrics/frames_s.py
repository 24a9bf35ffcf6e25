"""Seconds a traced decision in the metric frames and summary statistics
(``repro.frames``: ``frames_from_replay`` through the reductions in
``repro.scenarios.montecarlo.mc_trajectories``, once per candidate), less
JAX's tracing, lowering and compiles inside it."""
from chipbench import spans


def read(run):
    return spans.self_seconds(run, "repro.frames")
