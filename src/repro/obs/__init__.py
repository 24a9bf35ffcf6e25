"""Campaign observability: structured traces, metric frames, exporters,
and profiling hooks.

The subsystem is strictly opt-in and zero-overhead when unused: the
engine's recorder is ``None`` unless ``trace=True``, the replay kernel
only returns per-slot arrays under ``record_slots=True`` (a separate
cached jit program), the profiling hooks are plain functions that
cost nothing until called, and a ``span`` records nothing without a
``jax.profiler`` session.

Layout — submodules import lazily so ``repro.obs.profile`` (pure
stdlib) never drags jax in:

``obs.trace``
    typed event timelines from the engine, and the exact reconstruction
    of the same timeline from the replay kernel's tapes
``obs.metrics``
    per-campaign time-in-state frames (sum to the billed total by
    construction), cross-seed p5/p50/p95 aggregation, availability
    timelines, verdict ledgers
``obs.export``
    Chrome-trace / Perfetto JSON serialisation
``obs.profile``
    the repo's one wall-clock timing idiom (``timed``/``stopwatch``),
    ``span``, the program's host spans on the ``jax.profiler`` clock,
    compile-vs-execute splits + seeds/sec for the vmapped replay kernel,
    measured Pallas step surfaces per shard count
"""
from __future__ import annotations

from repro.obs.profile import (  # noqa: F401  (dependency-free, eager)
    Timed,
    kernel_step_surface,
    now_s,
    profile_replay,
    span,
    stopwatch,
    time_pallas_kernel,
    timed,
)

_LAZY = {
    "TraceEvent": "repro.obs.trace",
    "CampaignTrace": "repro.obs.trace",
    "TraceRecorder": "repro.obs.trace",
    "reconstruct_traces": "repro.obs.trace",
    "MODE_OUTCOME": "repro.obs.trace",
    "MetricFrame": "repro.obs.metrics",
    "frame_from_result": "repro.obs.metrics",
    "frames_from_replay": "repro.obs.metrics",
    "aggregate_frames": "repro.obs.metrics",
    "availability_timeline": "repro.obs.metrics",
    "verdict_ledger": "repro.obs.metrics",
    "to_chrome_trace": "repro.obs.export",
    "write_chrome_trace": "repro.obs.export",
}

__all__ = [
    "Timed",
    "timed",
    "stopwatch",
    "now_s",
    "span",
    "profile_replay",
    "time_pallas_kernel",
    "kernel_step_surface",
    *_LAZY,
]


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(name)
    import importlib

    return getattr(importlib.import_module(mod), name)
