"""What runs inside the planning child that answers one request.

Every request of a run goes through the program's own isolation,
``repro.orchestrator.plan.plan_in_child``: a freshly spawned process
imports JAX, turns on the compile cache, answers, and exits before the
answer comes back, as the daemon's planning calls do. The function the
child runs is :func:`decide`: it calls the traffic's entry point (the
oracle, ``choose_strategy``) exactly as the daemon calls it, and only after
the answer reads what the comparison and the metrics need from the same
process: the two wall-clock reinstate times the oracle billed with, the
XLA compiles the call made, and with ``trace_dir`` a profiler trace of the
call. :func:`setup` is the run's set-up request: it first checks the
device, and answers only on the TPU the cell asks for.

This module runs in the child, where the parent's ``sys.path`` is restored;
it imports JAX only inside its functions.
"""
from __future__ import annotations

import importlib
import json
import time
from pathlib import Path
from typing import Dict, Optional

from chipbench import tracefile

DEVICES_FILE = Path(__file__).resolve().parent / "devices.json"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def device_refusal(device: Dict, chips: int, kinds: Dict) -> Optional[str]:
    """Why a run may not measure on ``device`` (``platform``, ``kind``,
    ``count``), or None: a TPU of a kind in ``devices.json``, exactly as
    many chips as the cell asks for (the oracle shards its seeds over every
    chip it sees, so more chips would be another path)."""
    if device["platform"] != "tpu":
        return (f"no TPU: JAX's backend is {device['platform']!r}; "
                "this benchmark runs only on a TPU")
    if device["kind"] not in kinds:
        return f"device kind {device['kind']!r} is not in chipbench/devices.json"
    if device["count"] != chips:
        return f"the cell runs on {chips} chip(s), JAX found {device['count']}"
    return None


def _device() -> Dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _peak_memory_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def decide(entry: str, spec, request: Dict, trace_dir: Optional[str] = None) -> Dict:
    """One request: ``entry(spec, **request)``, its answer, and what the
    harness reads beside it."""
    import jax
    import jax.monitoring

    module, name = entry.split(":")
    fn = getattr(importlib.import_module(module), name)
    compiles = []

    def on_duration(event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    # the child's JAX backend starts here, not inside the timed call (the
    # program's child asks for the devices after the call; the work is the same)
    jax.devices()
    if trace_dir is not None:
        options = jax.profiler.ProfileOptions()
        # Python function tracing off: the host runs at its untraced speed
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        # the traced window is the call; the request span names the host
        # time in it that no finer event covers
        with jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN), \
                jax.profiler.TraceAnnotation(tracefile.REQUEST_SPAN):
            t0 = time.perf_counter()
            answer = fn(spec, **request)
            call_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
    else:
        t0 = time.perf_counter()
        answer = fn(spec, **request)
        call_s = time.perf_counter() - t0

    from repro.orchestrator.plan import PROFILE
    from repro.workloads import resolve

    # measure_micro is memoised per process: this is the record the call
    # billed with, not a new measurement
    micro = resolve(spec.workload, spec).micro(PROFILE, n_nodes=spec.n_nodes)
    return {
        "answer": answer,
        "reinstate_s": {"agent": micro.agent_reinstate_s, "core": micro.core_reinstate_s},
        "call_s": call_s,
        "compiles": len(compiles),
        "compile_s": sum(compiles),
    }


def setup(entry: str, spec, request: Dict, chips: Optional[int]) -> Dict:
    """The set-up request: the device first (unless ``chips`` is None, as
    in the tests that drive a run on the CPU), then one request that
    compiles (or loads) every program the window's requests run."""
    device = _device()
    if chips is not None:
        refusal = device_refusal(device, chips, json.loads(DEVICES_FILE.read_text())["kinds"])
        if refusal is not None:
            return {"refused": refusal, "device": device}
    out = decide(entry, spec, request)
    memory = _peak_memory_bytes()
    if memory is not None:
        device["memory_peak_bytes"] = memory
    out["device"] = device
    return out
