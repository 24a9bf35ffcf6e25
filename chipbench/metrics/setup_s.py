"""Seconds from the start of the run's process to its first timed request:
imports and the set-up request, answered in its own planning child (the
child's start, the device, compiling or loading every program, the call)."""


def read(run):
    return run["setup_s"]
