"""Observability layer: structured traces, metric frames, exporters.

The load-bearing invariants:

* **trace parity** — ``CampaignEngine(trace=True)`` and the kernel-side
  :func:`~repro.obs.trace.reconstruct_traces` produce the *same* event
  timeline per seed (the repo's trial-for-trial parity idiom, extended
  from aggregate counters to typed events) on every scenario family
  under >= 3 strategies;
* **exact-sum breakdown** — a :class:`~repro.obs.metrics.MetricFrame`'s
  components re-sum bitwise to the billed total, for every builtin
  strategy x workload, from both execution layers;
* **exporter round-trip** — the Chrome-trace JSON is loadable and its
  timestamps are monotonic;
* **zero overhead when disabled** — no trace object, no slot arrays, no
  serialisation change unless explicitly requested.
"""
import json
import os

import pytest

from repro.core.sim import measure_micro
from repro.obs.export import to_chrome_trace, write_chrome_trace
from repro.obs.metrics import (
    COMPONENTS,
    aggregate_frames,
    availability_timeline,
    frame_from_result,
    frames_from_replay,
    verdict_ledger,
)
from repro.obs.profile import Timed, span, stopwatch, timed
from repro.obs.trace import TraceEvent, reconstruct_traces, schedule_events
from repro.scenarios import mc_trajectories, registry
from repro.scenarios.engine import CampaignEngine
from repro.scenarios.trajectory import compile_batch, replay_batch
from repro.strategies import names as strategy_names
from repro.workloads import registry as workload_registry

_MICRO = {}


def micro_for(n_nodes: int):
    if n_nodes not in _MICRO:
        _MICRO[n_nodes] = measure_micro("placentia", n_nodes=n_nodes)
    return _MICRO[n_nodes]


@pytest.fixture(scope="module")
def micro():
    return micro_for(4)


# the acceptance sweep: every registered family under >= 3 strategies —
# window billing (central_single), proactive multi-agent (core), and the
# Rules 1-3 hybrid switcher
TRACE_STRATEGIES = ("central_single", "core", "hybrid")


def engine_trace(spec, strat, seed, **kw):
    res = CampaignEngine(spec, strat, seed=seed, trace=True, **kw).run()
    return res, res.trace


# ======================================================================
# Trace parity: engine timeline == kernel-reconstructed timeline
# ======================================================================
@pytest.mark.parametrize("family", registry.names())
def test_trace_parity_every_family(family):
    """Event-for-event engine == kernel on every family x 3 strategies."""
    spec = registry.get(family)
    micro = micro_for(spec.n_nodes) if spec.workload == "analytic" else None
    kw = {"micro": micro} if micro is not None else {}
    n_seeds = 2
    for strat in TRACE_STRATEGIES:
        ktraces = reconstruct_traces(spec, strat, n_seeds=n_seeds, micro=micro)
        for s in range(n_seeds):
            _, etr = engine_trace(spec, strat, s, **kw)
            assert etr.source == "engine" and ktraces[s].source == "kernel"
            assert etr.comparable() == ktraces[s].comparable(), (
                f"{family}/{strat} seed={s}: engine and kernel traces differ"
            )


@pytest.mark.slow
@pytest.mark.parametrize("family", registry.names())
def test_trace_parity_sweep_slow(family):
    """Wider sweep: 5 strategies x 6 seeds per family."""
    spec = registry.get(family)
    micro = micro_for(spec.n_nodes) if spec.workload == "analytic" else None
    kw = {"micro": micro} if micro is not None else {}
    # fleet-size families pay seconds per engine trial — keep the kernel
    # side wide via the tier-1 parity test, thin the engine sweep here
    n_seeds = 6 if spec.n_nodes <= 64 else 2
    for strat in ("central_single", "core", "hybrid", "agent", "cold_restart"):
        ktraces = reconstruct_traces(spec, strat, n_seeds=n_seeds, micro=micro)
        for s in range(n_seeds):
            _, etr = engine_trace(spec, strat, s, **kw)
            assert etr.comparable() == ktraces[s].comparable()


def test_trace_parity_under_ml_detector(micro):
    """Parity holds under a noisy detector too: the pre-sampled verdict
    tapes are the shared source of truth for both producers."""
    spec = registry.get("mc_stress")
    ktraces = reconstruct_traces(spec, "core", n_seeds=2, micro=micro, detector="ml")
    for s in range(2):
        _, etr = engine_trace(spec, "core", s, micro=micro, detector="ml")
        assert etr.comparable() == ktraces[s].comparable()


def test_trace_event_vocabulary(micro):
    """The mc_stress composition exercises the failure-handling kinds and
    the static schedule kinds land from the spec timelines."""
    spec = registry.get("mc_stress")
    _, tr = engine_trace(spec, "central_single", 0, micro=micro)
    counts = tr.counts()
    # every handled failure gets exactly one verdict + one migrate; the
    # rest landed on already-down hosts (coalesced) or stranded the run
    assert counts["failure"] >= counts["verdict"] + counts.get("stranded", 0)
    assert counts.get("migrate", 0) == counts["verdict"]
    assert counts.get("ckpt_write", 0) > 0  # window-mode cadence markers
    for ev in tr.events:
        assert 0.0 <= ev.t <= tr.end_s or ev.kind == "degrade"
    # deterministic order
    keys = [ev.sort_key() for ev in tr.events]
    assert keys == sorted(keys)


def test_schedule_events_clip():
    """Static schedule rows stop at the billed end (lost campaigns)."""
    spec = registry.get("table1_periodic")
    full = schedule_events(spec, spec.period_s * 4, mode_window=True, flags_stragglers=False)
    cut = schedule_events(spec, spec.period_s * 1.5, mode_window=True, flags_stragglers=False)
    assert len(full) == 3 and len(cut) == 1  # markers strictly inside the span
    assert all(ev.kind == "ckpt_write" for ev in full)


def test_trace_event_unknown_kind_rejected():
    with pytest.raises(ValueError, match="unknown trace event kind"):
        TraceEvent.make(0.0, "not_a_kind")


# ======================================================================
# Zero overhead when disabled
# ======================================================================
def test_trace_off_by_default(micro):
    spec = registry.get("flaky_node")
    res = CampaignEngine(spec, "core", micro=micro).run()
    assert res.trace is None
    assert "trace" not in res.to_dict()  # records stay byte-identical


def test_traced_result_serialisation_unchanged(micro):
    """trace=True must not perturb the result record itself."""
    spec = registry.get("flaky_node")
    plain = CampaignEngine(spec, "core", micro=micro).run().to_dict()
    traced = CampaignEngine(spec, "core", micro=micro, trace=True).run().to_dict()
    assert plain == traced


def test_replay_slots_off_by_default(micro):
    spec = registry.get("flaky_node")
    batch = compile_batch(spec, 2)
    out = replay_batch(spec, batch, "core", micro=micro)
    assert not any(k.startswith("slot_") for k in out)
    out = replay_batch(spec, batch, "core", micro=micro, record_slots=True)
    assert {"slot_processed", "slot_handled", "slot_victim", "slot_verdict"} <= set(out)


# ======================================================================
# Metric frames: the exact-sum invariant
# ======================================================================
def test_frame_sums_every_strategy_and_workload():
    """compute+lost+migrate+ckpt+probe+slowdown == billed total, bitwise,
    for every builtin strategy x workload on the stress composition."""
    spec = registry.get("mc_stress")
    for wl_name in workload_registry.names():
        for strat in strategy_names():
            res = CampaignEngine(spec, strat, workload=wl_name, seed=0).run()
            fr = frame_from_result(spec, res, seed=0)
            if res.survived:
                assert fr.total_s() == res.total_s, (strat, wl_name)
                assert fr.billed_total_s == res.total_s
                assert fr.overhead_frac >= 0.0
            else:
                assert fr.total_s() is None
                assert fr.failed_at_s == res.failed_at_s
            assert set(fr.breakdown()) == set(COMPONENTS)


def test_frame_sums_from_replay_kernel(micro):
    """Kernel-side frames re-sum bitwise to the kernel's own totals."""
    spec = registry.get("mc_stress")
    batch = compile_batch(spec, 8)
    for strat in ("central_single", "hybrid"):
        out = replay_batch(spec, batch, strat, micro=micro)
        frames = frames_from_replay(spec, out, strat)
        assert len(frames) == 8
        for s, fr in enumerate(frames):
            if fr.survived:
                assert fr.total_s() == float(out["total_s"][s])


def test_frame_engine_kernel_equal(micro):
    """Same seed -> identical frame components from either layer."""
    spec = registry.get("rack_outage")
    batch = compile_batch(spec, 3)
    out = replay_batch(spec, batch, "core", micro=micro)
    kframes = frames_from_replay(spec, out, "core")
    for s in range(3):
        res = CampaignEngine(spec, "core", micro=micro, seed=s).run()
        ef = frame_from_result(spec, res, seed=s)
        assert ef.breakdown() == kframes[s].breakdown()


def test_aggregate_frames_and_mc_attachment(micro):
    spec = registry.get("flaky_node")
    mc = mc_trajectories(spec, "core", micro=micro, n_seeds=16)
    agg = mc["frames"]
    assert agg["n_seeds"] == 16
    assert agg["approach"] == "core" and agg["scenario"] == "flaky_node"
    assert 0.0 <= agg["survival_rate"] <= 1.0
    comp = agg["components"]
    for k in COMPONENTS + ("stall_s", "total_s", "overhead_frac"):
        assert {"mean", "p5", "p50", "p95"} <= set(comp[k])
        assert comp[k]["p5"] <= comp[k]["p50"] <= comp[k]["p95"]
    # the aggregate's total mean reproduces the MC's mean over survivors
    assert comp["total_s"]["mean"] == pytest.approx(mc["mean_s"], rel=1e-6)


def test_availability_and_ledger(micro):
    spec = registry.get("mc_stress")
    res, tr = engine_trace(spec, "core", 0, micro=micro)
    pts = availability_timeline(tr)
    assert pts[0] == (0.0, 1.0)
    ts = [t for t, _ in pts]
    assert ts == sorted(ts)
    assert all(0.0 <= f <= 1.0 for _, f in pts)
    led = verdict_ledger(tr)
    assert led["n_verdicts"] == len(tr.select("verdict"))
    assert led["claims"] == led["true_saves"] + led["false_claims"]
    assert led["n_verdicts"] == led["claims"] + led["blind"]
    assert led["detector"] == "oracle"


# ======================================================================
# Exporter round-trip
# ======================================================================
def test_chrome_trace_roundtrip(micro, tmp_path):
    spec = registry.get("mc_stress")
    _, tr = engine_trace(spec, "core", 0, micro=micro)
    path = write_chrome_trace(tr, os.path.join(tmp_path, "trace.json"))
    with open(path) as f:
        doc = json.load(f)  # valid JSON round-trip
    evs = doc["traceEvents"]
    assert len(evs) >= len(tr.events)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)  # monotonic timestamps
    assert all(e["ts"] >= 0 for e in evs)
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "i", "C"} <= phases
    names = {e["name"] for e in evs if e["ph"] == "i"}
    assert "failure" in names and "migrate" in names
    # per-host thread tracks are declared for every node
    threads = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
    assert len(threads) == tr.n_hosts + 1  # + the campaign track
    assert doc["otherData"]["scenario"] == "mc_stress"


def test_chrome_trace_lost_campaign(micro):
    """A lost campaign exports a cut billed span, not the horizon."""
    spec = registry.get("spare_exhaustion")
    res, tr = engine_trace(spec, "core", 0, micro=micro)
    assert not res.survived
    doc = to_chrome_trace(tr)
    span = next(e for e in doc["traceEvents"] if e["ph"] == "X" and "campaign" in e["name"])
    assert span["name"] == "campaign (lost)"
    assert span["dur"] == pytest.approx(res.failed_at_s * 1e6)


# ======================================================================
# Profiling helpers + the consolidated timing idiom
# ======================================================================
def test_timed_and_stopwatch():
    calls = []
    out = timed(lambda: calls.append(1) or 41 + 1, n=3, warmup=2, name="probe")
    assert isinstance(out, Timed)
    assert out.result == 42
    assert len(calls) == 5  # warmup iterations run but are not recorded
    assert len(out.times_s) == 3
    assert out.min_s <= out.mean_s <= out.total_s
    assert out.to_dict()["name"] == "probe"
    with stopwatch() as sw:
        pass
    assert sw.s >= 0.0


def test_span_is_inert_without_a_profiler_session():
    """A span outside a profiler session records nothing and passes
    exceptions through."""
    with span("repro.probe", strategy="agent"):
        pass
    with pytest.raises(KeyError):
        with span("repro.probe"):
            raise KeyError("through")


# one request of the chip benchmark's traffic at a tiny size, traced in a
# fresh process as the benchmark's planning child traces it: cold caches,
# so every program is traced, lowered and compiled inside the call
_TRACED_CALL = """
import json, sys
from chipbench import child
from repro.scenarios import registry
request = {"candidates": ("central_single", "agent"), "n_seeds": 8, "seed": 0,
           "detector": "ewma_straggler", "workload": "analytic"}
out = child.decide("repro.orchestrator.plan:choose_strategy", registry.get("table2_random"),
                   request, sys.argv[1])
print(json.dumps({"call_s": out["call_s"]}))
"""
_SPAN_READERS = ("micro_calibration_s", "tape_compile_s", "verdict_tapes_s", "frames_s",
                 "jit_trace_lower_s", "xla_compile_s", "untraced_call_share",
                 "replay_programs_built")


def test_traced_oracle_call_has_leaf_spans(tmp_path, monkeypatch):
    """The oracle's host layers land as leaf spans in the profiler's trace:
    each once per call or per candidate (none in a per-seed loop), none
    enclosing another or a jitted call, and every span reader finds them."""
    import math
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    proc = subprocess.run([sys.executable, "-c", _TRACED_CALL, str(tmp_path)], cwd=root,
                          env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-4000:]
    call_s = json.loads(proc.stdout.strip().splitlines()[-1])["call_s"]

    monkeypatch.syspath_prepend(str(root))
    from chipbench import tracefile
    from chipbench.run import load_reader

    trace = tracefile.load(str(tmp_path))
    host = trace["host"]
    counts = {}
    for name, _, _ in host:
        if name.startswith("repro."):
            counts[name] = counts.get(name, 0) + 1
    assert counts == {"repro.micro": 1, "repro.tapes": 1, "repro.verdicts": 2, "repro.frames": 2,
                      "repro.replay_program": 1}
    leaves = [h for h in host if h[0].startswith("repro.")]
    inner = leaves + [h for h in host if h[0].startswith("PjitFunction(")]
    assert any(h[0] == "PjitFunction(one_seed)" for h in inner)
    for name, start, dur in leaves:
        for other, a, d in inner:
            if (other, a, d) != (name, start, dur):
                assert not (start <= a and a + d <= start + dur), (name, other)
    run = {"trace": trace, "call_s": [call_s]}
    for reader in _SPAN_READERS:
        value = load_reader(reader)(run)
        assert value is not None and math.isfinite(value), reader
    assert load_reader("xla_compile_s")(run) > 0
    assert 0 <= load_reader("untraced_call_share")(run) < 0.25
    assert load_reader("replay_programs_built")(run) == 1.0


# two decisions over the benchmark's four candidates in one process, each
# traced on its own: the first builds the one replay program, the second
# finds it in the cache
_TWO_DECISIONS = """
import sys
from chipbench import child
from repro.scenarios import registry
for seed, trace_dir in ((0, sys.argv[1]), (1000, sys.argv[2])):
    request = {"candidates": ("central_single", "agent", "core", "hybrid"), "n_seeds": 8,
               "seed": seed, "detector": "ewma_straggler", "workload": "analytic"}
    child.decide("repro.orchestrator.plan:choose_strategy", registry.get("table2_random"),
                 request, trace_dir)
"""


def test_decision_builds_one_replay_program(tmp_path, monkeypatch):
    """The four candidates of a decision share one replay program: the
    first decision of a process records one ``repro.replay_program``
    span, the second, on a warm cache, none."""
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    dirs = [str(tmp_path / "first"), str(tmp_path / "second")]
    proc = subprocess.run([sys.executable, "-c", _TWO_DECISIONS, *dirs], cwd=root,
                          env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-4000:]

    monkeypatch.syspath_prepend(str(root))
    from chipbench import spans, tracefile
    from chipbench.run import load_reader

    built = []
    for d in dirs:
        trace = tracefile.load(d)
        # JAX nests two events of the name in each call: four calls
        assert len(spans.intervals(trace, lambda n: n == "PjitFunction(one_seed)")) == 4
        built.append(sum(h[0] == "repro.replay_program" for h in trace["host"]))
        run = {"trace": trace, "call_s": [1.0]}
        assert load_reader("replay_programs_built")(run) == (1.0 if len(built) == 1 else None)
    assert built == [1, 0]


def _span_run(host, decisions=1):
    trace = {"window": [0, 1000], "devices": {}, "host": [list(h) for h in host]}
    return {"trace": trace, "call_s": [1.0] * decisions}


@pytest.mark.parametrize(
    "run, want",
    [
        # one program built in one traced decision
        (_span_run([("chipbench_request", 0, 1000), ("repro.replay_program", 100, 5)]), 1.0),
        # the mean over the traced decisions
        (_span_run([("repro.replay_program", 100, 5), ("repro.replay_program", 600, 5),
                    ("repro.replay_program", 700, 5)], decisions=2), 1.5),
        # a program without the span (an older tree) reads nothing
        (_span_run([("chipbench_request", 0, 1000), ("PjitFunction(one_seed)", 100, 50)]), None),
        # no trace, or no decision in it
        ({"trace": None, "call_s": [1.0]}, None),
        (_span_run([("repro.replay_program", 100, 5)], decisions=0), None),
    ],
)
def test_replay_programs_built_reader(run, want, monkeypatch):
    from pathlib import Path

    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    from chipbench.run import load_reader

    assert load_reader("replay_programs_built")(run) == want


def test_replay_programs_built_reads_nothing_before_the_span(monkeypatch):
    """The decision recorded on a TPU v5e before the span existed (four
    replay programs, no ``repro.replay_program``) reads null."""
    import gzip
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    monkeypatch.syspath_prepend(str(root))
    from chipbench import spans
    from chipbench.run import load_reader

    with gzip.open(root / "chipbench" / "tests" / "recorded_spans_trace.json.gz", "rt") as f:
        trace = json.load(f)
    run = {"trace": trace, "call_s": [trace["window"][1] / 1e9]}
    assert len(spans.intervals(trace, lambda n: n == "PjitFunction(one_seed)")) == 4
    assert load_reader("replay_programs_built")(run) is None


def test_measured_step_surface_mapping():
    """Workloads with no kernel hot path return None (no timing runs)."""
    assert workload_registry.get("analytic").measured_step_surface() is None
    assert workload_registry.get("genome_search").measured_step_surface() is None


def test_live_verdict_ledger():
    from repro.telemetry import Verdict
    from repro.telemetry import verdict_ledger as live_ledger

    vs = [
        Verdict(node=0, kind="failure_predicted", detector="ml"),
        Verdict(node=1, kind="straggler", detector="ewma"),
        Verdict(node=2, kind="failure_predicted", detector="ml"),
    ]
    led = live_ledger(vs)
    assert led["ml"]["failure_predicted"] == 2
    assert led["ewma"]["straggler"] == 1


# ======================================================================
# The repo-root perf record
# ======================================================================
def test_bench_record_schema():
    """BENCH_scenarios.json (written by benchmarks/bench_scenarios.py)
    must stay parseable under the pinned schema."""
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_scenarios.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_scenarios.json at repo root (bench not yet run)")
    with open(path) as f:
        rec = json.load(f)
    assert rec["schema_version"] == 4
    assert isinstance(rec["seeds_per_s"], (int, float)) and rec["seeds_per_s"] > 0
    assert {"montecarlo", "trajectory", "fleet", "min_required"} <= set(rec["speedup"])
    assert rec["trace_parity"] is True
    assert rec["n_devices"] >= 1
    fleet = rec["speedup"]["fleet"]
    assert fleet["family"] == "fleet_stress" and fleet["n_nodes"] >= 1024
    assert fleet["engine_match"] is True
    assert rec["per_family_seeds_per_s"]["fleet_stress"] > 0
    assert rec["program_cache"]["programs"] >= 1
    for wl, fams in rec["workload_overhead_pct"].items():
        for fam, cells in fams.items():
            assert all(v is None or isinstance(v, (int, float)) for v in cells.values())
    # v3: the serving-traffic block — per-strategy x per-autoscaler SLOs
    traffic = rec["traffic"]
    assert traffic["family"] == "decode_fleet_churn" and traffic["n_nodes"] >= 256
    assert {"by_makespan", "by_p99_static", "differs"} <= set(traffic["ordering"])
    for strat, per in traffic["slo"].items():
        for asc, cell in per.items():
            assert {"p50_s", "p99_s", "dropped_mean", "availability_mean"} <= set(cell)
    # v4: the live-orchestrator block — live vs predicted makespan per
    # strategy (kill injector) and per registered injector
    orch = rec["orchestrator"]
    assert orch["scenario"] == "live_genome_single"
    assert {"none", "kill", "stall", "slow"} <= set(orch["injectors"])
    for strat, cell in orch["strategies"].items():
        assert cell["survived"] is True
        assert {"live_total_s", "predicted_total_s", "rel_err"} <= set(cell)
