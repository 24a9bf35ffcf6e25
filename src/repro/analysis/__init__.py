"""Static invariant linter: AST-level proofs of the repo's correctness
contracts, run before any test executes.

The repo's headline guarantee — the vmapped replay kernel is trial-for-
trial identical to the reference :class:`~repro.scenarios.engine.
CampaignEngine` under every strategy × detector × workload — is enforced
at runtime by differential tests. Those tests can only catch drift they
happen to execute: a contributor who adds a DSL process kind, a
``TraceEvent`` kind, or a registry builtin and forgets one of its
consumer sites gets a silent semantic gap until a slow-tier sweep covers
it. The FT survey (Treaster, cs/0501002) stresses that protocol-level
correctness of the recovery path is the hard part of fault tolerance,
and the multi-agent tuning framework (Roy et al., 1005.2027) argues for
analysis agents that *inspect* the system rather than only run it. This
package is that inspection layer: a stdlib-``ast`` pass (no third-party
deps, nothing is imported or executed) over the source tree that proves
five invariant families:

``traced-purity``
    no impure call (wall clock, global RNG, stdout, file I/O, module-
    global mutation) is reachable from a ``jax.jit`` / ``jax.vmap`` /
    ``pl.pallas_call`` root — impurity inside a trace bakes stale values
    into the compiled program and silently breaks replay determinism.
``parity-coverage``
    every DSL process kind (``scenarios/spec.py``) and every
    ``TraceEvent`` kind (``obs/trace.py``) is threaded through *all* of
    its consumer sites — dispatch, scenario families, engine-side
    emitters, kernel-side reconstruction, tests.
``registry-completeness``
    every ``@register``-ed strategy / detector / workload and every
    scenario family reaches the bench matrix and at least one test.
``units-s``
    time-valued names carry the ``_s`` suffix and seconds never mix with
    other unit suffixes under ``+``/``-``.
``dtype-x64``
    replay-kernel modules (built under ``repro.utils.backend.x64``) and
    Pallas kernel modules construct arrays with explicit dtypes, and the
    x64 modules carry no 32-bit float literals.

Run it as ``python -m repro.analysis src/`` (text report, nonzero exit
on findings) or ``--json`` for the machine-readable record CI uploads.
Suppress a deliberate violation with ``# repro: ignore[rule]`` on the
flagged line, or a whole file with ``# repro: ignore-file[rule]``.

Rules live in a registration-ordered registry (the idiom of
``strategies``/``telemetry``/``workloads``): ``@register("my-rule")`` on
a :class:`~repro.analysis.base.Rule` subclass adds it to every run, the
CLI, and the JSON schema at once.
"""
from repro.analysis.base import Rule
from repro.analysis.findings import Finding, SEVERITIES
from repro.analysis.project import ModuleSource, Project
from repro.analysis.registry import all_rules, get, names, register, unregister
from repro.analysis.runner import run_analysis

__all__ = [
    "Finding",
    "SEVERITIES",
    "Rule",
    "ModuleSource",
    "Project",
    "register",
    "unregister",
    "get",
    "names",
    "all_rules",
    "run_analysis",
]
