"""Host spans of the traced calls, reduced to seconds a decision.

The program marks its host layers with leaf spans named ``repro.<layer>``
(``repro.obs.profile.span``); JAX marks its own work inside a jitted call
(``PjitFunction(<fn>)``): tracing to a jaxpr, lowering to MLIR, the XLA
backend compile, and the fetch of a result to numpy. The readers in
``metrics/`` that split the oracle's call share what is here. Every figure
is the union of the named events' intervals, cut to the traced window (a
name nested in itself counts once), divided by the decisions traced; a
span's self time leaves out JAX's tracing, lowering and compiles inside it.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

from chipbench import tracefile
from chipbench.tracefile import Interval

#: the prefix of the program's own spans
PROGRAM = "repro."
#: JAX's span around each call of a jitted function, ``PjitFunction(<fn>)``
JIT_CALL = "PjitFunction("
#: JAX's span around the copy of a device array into numpy
FETCH = "np.asarray(jax.Array)"
#: JAX's spans for tracing a function to a jaxpr and lowering it to MLIR
TRACE_LOWER = ("trace_to_jaxpr_dynamic", "lower_sharding_computation")
#: JAX's spans around the XLA backend compile
COMPILE = ("backend_compile_and_load", "backend_compile")


def intervals(trace: Dict, match: Callable[[str], bool]) -> List[Interval]:
    """The union of the host events whose name ``match`` accepts, cut to the window."""
    named = [h for h in trace["host"] if match(h[0])]
    return tracefile.union(tracefile.clip(named, trace["window"]))


def length(ivs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Where two sorted, disjoint interval lists overlap."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def per_decision_s(run: Dict, ns: float) -> float:
    return ns / 1e9 / len(run["call_s"])


def traced(run: Dict) -> bool:
    """Whether the run has a trace and at least one decision in it."""
    return bool(run["trace"]) and bool(run["call_s"])


def jax_seconds(run: Dict, names: Sequence[str]) -> Optional[float]:
    """Seconds a decision under JAX's events ``names``, every program's."""
    if not traced(run):
        return None
    return per_decision_s(run, length(intervals(run["trace"], lambda n: n in names)))


def self_seconds(run: Dict, name: str) -> Optional[float]:
    """Seconds a decision in the program's span ``name``, less JAX's
    tracing, lowering and compiles inside it; None where the program has
    no such span."""
    if not traced(run):
        return None
    own = intervals(run["trace"], lambda n: n == name)
    if not own:
        return None
    jax_work = intervals(run["trace"], lambda n: n in TRACE_LOWER + COMPILE)
    return per_decision_s(run, length(own) - length(intersect(own, jax_work)))


def untraced_share(run: Dict) -> Optional[float]:
    """Share of the traced requests' time under no program span, jitted
    call or fetch."""
    if not traced(run):
        return None
    request = intervals(run["trace"], lambda n: n == tracefile.REQUEST_SPAN)
    if not length(request):
        return None
    covered = intervals(run["trace"], lambda n: n.startswith((PROGRAM, JIT_CALL)) or n == FETCH)
    return 1.0 - length(intersect(request, covered)) / length(request)
