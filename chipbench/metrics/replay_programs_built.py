"""Replay programs a traced decision builds: the program's
``repro.replay_program`` span, one per miss of the replay kernel's program
cache (``repro.scenarios.trajectory._compiled_replayer``), counted in the
traced window and divided by the decisions traced. None where the program
has no such span."""
from chipbench import spans

NAME = "repro.replay_program"


def read(run):
    if not spans.traced(run):
        return None
    built = sum(1 for name, _, _ in run["trace"]["host"] if name == NAME)
    return built / len(run["call_s"]) if built else None
