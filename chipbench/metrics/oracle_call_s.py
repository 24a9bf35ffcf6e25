"""Seconds of the oracle's call inside each planning child of the window,
from its issue to its answer there, the child's JAX backend already up (its
start, its imports, the backend and its exit left out), averaged over the
window's requests."""


def read(run):
    calls = run["call_s"]
    if not calls:
        return None
    return sum(calls) / len(calls)
