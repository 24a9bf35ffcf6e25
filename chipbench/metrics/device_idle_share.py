"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over the chips."""
from chipbench import tracefile


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    window = tracefile.window_ns(trace)
    busy = [tracefile.busy_ns(trace, d) for d in trace["devices"]]
    return 1.0 - sum(busy) / len(busy) / window
