"""Device milliseconds per run of the replay program
(``repro.scenarios.trajectory._compiled_replayer``): the mean over its runs
in the traced window of the run's duration on the slowest chip."""
from chipbench import tracefile

#: what the replay program's module events are named by in a TPU trace
PATTERN = "jit_one_seed"


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    runs = [r for r in tracefile.module_runs(trace, PATTERN).values() if r]
    if not runs:
        return None
    n = min(len(r) for r in runs)
    slowest = [max(r[i][1] - r[i][0] for r in runs) for i in range(n)]
    return sum(slowest) / n / 1e6
