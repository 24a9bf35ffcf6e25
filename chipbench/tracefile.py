"""The profiler trace of a measured window, reduced to what the metrics read.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
only what the readers use, in a plain form that a recorded trace can be
committed in (``tests/recorded_trace.json.gz``)::

    {"window": [start_ns, end_ns],          # the harness's window span
     "devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's XLA op events, ``modules`` its whole-program
events (one per program run), ``host`` every host event that overlaps the
window. All times are on the trace's own clock. Each planning child of a
run writes its own trace; :func:`concat` lays them end to end.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

#: the span, in a planning child, around the traced window: the oracle's call
WINDOW_SPAN = "chipbench_window"
#: the span around the request's call inside that window
REQUEST_SPAN = "chipbench_request"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def load(log_dir: str) -> Dict:
    """The newest trace under ``log_dir`` in the plain form above."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List]] = {}
    host: List[List] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [[e.name, e.start_ns, e.duration_ns] for e in lines[name].events]
                if name in lines else []
                for key, name in (("ops", OPS_LINE), ("modules", MODULES_LINE))
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns] for e in ln.events]
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    w0, w1 = spans[0][1], spans[0][1] + spans[0][2]
    host = [h for h in host if h[1] < w1 and h[1] + h[2] > w0 and h[0] != WINDOW_SPAN]
    return {"window": [w0, w1], "devices": devices, "host": host}


def clip(events: Sequence, window: Sequence[float]) -> List[Interval]:
    """``(start, end)`` of each event, cut to the window; empty ones dropped."""
    w0, w1 = window
    out = []
    for _, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering exactly what ``intervals`` cover."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def concat(traces: Sequence[Dict]) -> Dict:
    """Traces of consecutive windows (one per planning child) as one: each
    event cut to its own trace's window, and the windows laid end to end
    from 0 in the order given."""
    out: Dict = {"devices": {}, "host": []}
    t = 0
    for tr in traces:
        w0, w1 = tr["window"]

        def cut(events):
            kept = []
            for name, start, dur in events:
                a, b = max(start, w0), min(start + dur, w1)
                if b > a:
                    kept.append([name, a - w0 + t, b - a])
            return kept

        for dev, d in tr["devices"].items():
            into = out["devices"].setdefault(dev, {"ops": [], "modules": []})
            for key in ("ops", "modules"):
                into[key] += cut(d[key])
        out["host"] += cut(tr["host"])
        t += w1 - w0
    out["window"] = [0, t]
    return out


def busy_ns(trace: Dict, device: str) -> float:
    """Nanoseconds of the window in which some op ran on ``device``."""
    return sum(b - a for a, b in union(clip(trace["devices"][device]["ops"], trace["window"])))


def window_ns(trace: Dict) -> float:
    w0, w1 = trace["window"]
    return w1 - w0


def idle_gaps(trace: Dict, device: str) -> List[Interval]:
    """The window's stretches in which no op ran on ``device``."""
    w0, w1 = trace["window"]
    gaps, t = [], w0
    for a, b in union(clip(trace["devices"][device]["ops"], trace["window"])):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def host_activity(trace: Dict, gap: Interval) -> str:
    """What the host was doing in ``gap``: the host event that covers most of
    it, where that covers half of it or more; else the harness's request
    span (host work inside a request with no finer event), else
    ``"untraced host time"``."""
    a, b = gap
    best, request = (0.0, ""), False
    for name, start, dur in trace["host"]:
        cover = min(b, start + dur) - max(a, start)
        if cover <= 0:
            continue
        if name == REQUEST_SPAN:
            request = True
        elif (cover, -dur) > (best[0], 0):
            best = (cover, name)
    if best[0] >= 0.5 * (b - a):
        return best[1]
    return REQUEST_SPAN if request else "untraced host time"


def op_name(event_name: str) -> str:
    """An XLA op's short name: the instruction's name without its HLO text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_op_seconds(trace: Dict) -> List[Tuple[str, float]]:
    """Ops by their self seconds inside the window (an op's time less that
    of the ops nested in it, as a loop's body in the loop), summed over
    the devices and divided by their number, most first. An op is named
    ``<program>/<instruction>`` by the program run it falls in."""
    total: Dict[str, float] = defaultdict(float)
    for dev in trace["devices"].values():
        runs = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in runs]
        ops = sorted(((a, b, name) for name, start, dur in dev["ops"]
                      for a, b in clip([(name, start, dur)], trace["window"])),
                     key=lambda o: (o[0], -o[1]))
        open_ops: List[List] = []  # [end, key] of the ops the current one may nest in
        for a, b, name in ops:
            while open_ops and open_ops[-1][0] <= a:
                open_ops.pop()
            i = bisect.bisect_right(starts, a) - 1
            inside = i >= 0 and a < runs[i][1] + runs[i][2]
            key = (runs[i][0] + "/" if inside else "") + op_name(name)
            total[key] += (b - a) / 1e9
            if open_ops:
                total[open_ops[-1][1]] -= (b - a) / 1e9
            open_ops.append([b, key])
    n = max(len(trace["devices"]), 1)
    return sorted(((k, v / n) for k, v in total.items()), key=lambda kv: -kv[1])


def breakdown(trace: Dict, top: int = 10) -> Dict:
    """The ``breakdown`` of a traced run: the device ops that took most
    time and the longest idle gaps of the busiest device, each named by
    what the host was doing in it."""
    devices = sorted(trace["devices"])
    if not devices:
        return {"device_ops": [], "idle_gaps": []}
    busiest = max(devices, key=lambda d: busy_ns(trace, d))
    gaps = sorted(idle_gaps(trace, busiest), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in device_op_seconds(trace)[:top]],
        "idle_gaps": [[host_activity(trace, g), (g[1] - g[0]) / 1e9] for g in gaps],
    }


def module_runs(trace: Dict, pattern: str) -> Dict[str, List[Interval]]:
    """Per device, the window-clipped runs of every program whose name
    contains ``pattern``, in time order."""
    return {
        dev: sorted(clip([m for m in d["modules"] if pattern in m[0]], trace["window"]))
        for dev, d in trace["devices"].items()
    }
