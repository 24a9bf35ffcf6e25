"""Live orchestrator: spool protocol, injector axis, planning oracle, and
the supervision daemon driven subprocess-free under a fake clock.

The stub harness (:mod:`repro.orchestrator.testing`) runs the *entire*
daemon — heartbeat ingest, injection firing, stall detection, strategy
resolution, modelled-stall resumes, drift re-planning — in-process and
deterministically; two subprocess tests (one fast analytic smoke in
tier 1, the full genome live-cert marked ``slow``) prove the same loop
supervises real ``python -m repro.orchestrator.worker`` processes.
"""
import json
import os

import pytest

from repro.orchestrator import contract
from repro.orchestrator import registry as injector_registry
from repro.orchestrator.daemon import LiveReport, OrchestratorDaemon, SubprocessLauncher
from repro.orchestrator.injector import Injection, Injector
from repro.orchestrator.plan import (
    DriftMonitor,
    LivePlan,
    choose_strategy,
    make_live_plan,
    predicted_makespan_s,
    scale_failure_rate,
)
from repro.orchestrator.spool import Spool
from repro.orchestrator.testing import (
    FakeClock,
    StubLauncher,
    StubWorker,
    scripted_sleeper,
)
from repro.core.heartbeat import HeartbeatService
from repro.scenarios import registry as scenario_registry
from repro.scenarios.trajectory import compile_tape

LIVE_SCENARIO = "live_genome_single"
#: fast stub-run scaling: 1 wall second = 900 simulated seconds
TIME_SCALE = 900.0


def live_spec(workload="genome_search"):
    spec = scenario_registry.get(LIVE_SCENARIO)
    spec.workload = workload
    return spec


def stub_plan(spec, strategy="central_single", seed=0):
    """A LivePlan priced by the engine but laid out for stub workers."""
    return make_live_plan(
        spec,
        time_scale=TIME_SCALE,
        seed=seed,
        strategy=strategy,
        calibrate=False,  # stubs don't run real steps
    )


def run_stub_daemon(
    plan,
    tmp_path,
    *,
    injector="kill",
    script=None,
    launcher_hook=None,
    **daemon_kw,
):
    clock = FakeClock()
    spool = Spool(str(tmp_path / "spool"))
    launcher = StubLauncher(spool, clock)
    if launcher_hook is not None:
        launcher_hook(launcher)
    daemon = OrchestratorDaemon(
        plan,
        spool,
        launcher,
        injector=injector,
        clock=clock,
        async_sleep=scripted_sleeper(clock, launcher, script=script),
        poll_wall_s=0.05,
        deadline_wall_s=600.0,  # fake-clock seconds: a backstop, not a wait
        **daemon_kw,
    )
    rep = daemon.run_sync()
    return rep, daemon, launcher


def kinds(trace):
    return [e.kind for e in trace.events]


# ------------------------------------------------------------- contract ---
def test_exit_contract_classification():
    assert contract.classify_exit(contract.EXIT_OK) == "ok"
    assert contract.classify_exit(contract.EXIT_FAULT_INJECTED) == "fault-injected"
    assert contract.classify_exit(contract.EXIT_STALLED) == "stalled"
    assert contract.classify_exit(contract.EXIT_PREEMPTED) == "preempted"
    assert contract.classify_exit(-9) == "fault-injected"  # SIGKILL
    assert contract.classify_exit(-19) == "stalled"  # SIGSTOP reap
    assert contract.classify_exit(7) == "crashed"


# ---------------------------------------------------------------- spool ---
def test_spool_roundtrip_and_sequencing(tmp_path):
    sp = Spool(str(tmp_path / "sp"))
    assert sp.read_heartbeat(0) is None
    sp.write_heartbeat(0, {"t_wall_s": 1.5, "state": "idle"})
    assert sp.read_heartbeat(0)["state"] == "idle"

    sp.send_command(0, {"op": "warm"}, seq=1)
    sp.send_command(0, {"op": "assign", "shard": 2}, seq=2)
    cmd = sp.read_command(0)
    assert cmd["op"] == "assign" and cmd["seq"] == 2  # later write wins

    sp.write_checkpoint(3, {"shard": 3, "step": 4, "state": {}})
    assert sp.read_checkpoint(3)["step"] == 4
    sp.write_result(3, {"shard": 3, "steps_done": 8})
    assert sp.results(4) == {3: {"shard": 3, "steps_done": 8}}

    sp.write_status({"state": "running"})
    assert sp.read_status()["state"] == "running"


def test_spool_corrupt_file_reads_as_none(tmp_path):
    sp = Spool(str(tmp_path / "sp"))
    sp.write_heartbeat(1, {"t_wall_s": 0.0})
    with open(os.path.join(sp.worker_dir(1), "hb.json"), "w") as f:
        f.write("{not json")
    assert sp.read_heartbeat(1) is None


# ------------------------------------------- heartbeat stalls (satellite) ---
def test_heartbeat_beat_and_stalled_with_explicit_timestamps():
    hb = HeartbeatService(3)
    hb.beat(0, at_s=10.0)
    hb.beat(1, at_s=14.0)
    # node 2 never beat: silence from a never-started node is not a stall
    assert hb.stalled(5.0, now_s=16.0) == [0]
    assert hb.stalled(1.0, now_s=16.0) == [0, 1]
    assert hb.stalled(10.0, now_s=16.0) == []


def test_heartbeat_stalled_ignores_known_dead_nodes():
    hb = HeartbeatService(2)
    hb.beat(0, at_s=0.0)
    hb.beat(1, at_s=0.0)
    hb.mark_failed(1)
    assert hb.stalled(5.0, now_s=100.0) == [0]
    hb.revive(1)
    assert hb.stalled(5.0, now_s=100.0) == [0, 1]


def test_heartbeat_injected_clock_is_the_default_now():
    clk = FakeClock(50.0)
    hb = HeartbeatService(1, clock=clk)
    hb.beat(0)  # stamps at the injected clock's now
    clk.advance(3.0)
    assert hb.stalled(5.0) == []
    clk.advance(3.0)
    assert hb.stalled(5.0) == [0]


# ------------------------------------------------------- injector axis ---
def test_injector_registry_names_and_aliases():
    assert injector_registry.names() == ["none", "kill", "stall", "slow"]
    for name in injector_registry.names():
        inj = injector_registry.get(name)
        assert isinstance(inj, Injector) and inj.name == name
    assert injector_registry.get_class("sigkill") is injector_registry.get_class("kill")
    assert injector_registry.get_class("off") is injector_registry.get_class("none")
    with pytest.raises(KeyError):
        injector_registry.get("no_such_injector")


def test_injector_registry_rejects_duplicates_and_non_injectors():
    with pytest.raises(KeyError):
        @injector_registry.register("kill")
        class Clash(Injector):  # pragma: no cover - never registered
            def schedule(self, tape):
                return []
    with pytest.raises(TypeError):
        injector_registry.register("not_an_injector")(object)

    @injector_registry.register("throwaway_chaos")
    class Throwaway(Injector):
        def schedule(self, tape):
            return []

    try:
        assert "throwaway_chaos" in injector_registry.names()
    finally:
        injector_registry.unregister("throwaway_chaos")
    assert "throwaway_chaos" not in injector_registry.names()


def test_injector_schedules_follow_the_compiled_tape():
    tape = compile_tape(live_spec(), 0)
    n_real = sum(1 for j in range(tape.n_slots) if tape.times[j] < float("inf"))
    assert n_real == 1  # the spec's single burst event at t=2250

    assert injector_registry.get("none").schedule(tape) == []
    kills = injector_registry.get("kill").schedule(tape)
    assert [i.action for i in kills] == ["kill"] and kills[0].t_s == 2250.0
    stalls = injector_registry.get("stall").schedule(tape)
    assert [i.action for i in stalls] == ["stall"]
    slows = injector_registry.get("slow", factor=3.0).schedule(tape)
    assert [(i.action, i.factor) for i in slows] == [("slow", 3.0)]

    with pytest.raises(ValueError):
        Injection(0, 1.0, "meteor")


# ------------------------------------------------------- planning oracle ---
def test_live_scenario_is_registered():
    spec = scenario_registry.get(LIVE_SCENARIO)
    assert spec.n_nodes == 4 and spec.n_spares == 2
    assert spec.workload == "genome_search"
    assert spec.horizon_s == 3600.0 and spec.period_s == 900.0


def test_make_live_plan_grid_matches_the_horizon():
    plan = stub_plan(live_spec())
    assert plan.n_steps == 8  # 4 periods x 2 steps
    assert plan.step_sim_s == pytest.approx(450.0)
    assert plan.n_steps * plan.step_sim_s == pytest.approx(3600.0)
    assert plan.ckpt_every_steps == 2  # a checkpoint on every period boundary
    # probe cost folded in: the paced step is never shorter than the raw grid
    assert plan.step_wall_s >= plan.step_sim_s / plan.time_scale
    assert plan.predicted_total_s > 3600.0  # horizon + failure bill
    d = plan.to_dict()
    assert d["strategy"] == "central_single" and "surface" not in d["calibration"]


def test_choose_strategy_survival_dominates_then_cost():
    winner, scores = choose_strategy(live_spec(), n_seeds=8, seed=0)
    assert winner in scores and set(scores) == set(
        ("central_single", "agent", "core", "hybrid")
    )
    best = max(s["survival_rate"] for s in scores.values())
    assert scores[winner]["survival_rate"] >= best
    finalists = [n for n, s in scores.items() if s["survival_rate"] >= best]
    assert scores[winner]["mean_s"] == min(scores[n]["mean_s"] for n in finalists)


def test_scale_failure_rate_scales_count_knobs():
    spec = live_spec()
    doubled = scale_failure_rate(spec, 2.0)
    assert doubled.processes[0].params["k"] == 2
    assert spec.processes[0].params["k"] == 1  # original untouched


def test_drift_monitor_bands():
    dm = DriftMonitor(expected_failures=1, horizon_s=3600.0, step_wall_s=0.5)
    dm.observe_failure()
    assert dm.drifted(100.0) is None  # below min_failures
    dm.observe_failure()
    d = dm.drifted(100.0)
    assert d is not None and d["cause"] == "failure_rate" and d["ratio"] > 1.8

    dm2 = DriftMonitor(expected_failures=1, horizon_s=3600.0, step_wall_s=0.5)
    for _ in range(20):
        dm2.observe_step(0.55)
    assert dm2.drifted(1800.0) is None  # 1.1x: inside the band
    for _ in range(20):
        dm2.observe_step(1.5)
    d = dm2.drifted(1800.0)
    assert d is not None and d["cause"] == "step_time"


# ------------------------------------------------- stub daemon campaigns ---
def test_stub_kill_campaign_migrates_and_matches_prediction(tmp_path):
    plan = stub_plan(live_spec())
    rep, daemon, launcher = run_stub_daemon(plan, tmp_path, injector="kill")

    assert rep.survived and rep.failed_at_s is None
    assert rep.n_events == 1 and rep.n_handled == 1
    assert sorted(rep.results) == [0, 1, 2, 3]  # every shard's result landed
    assert rep.n_replans == 0

    # the live trace is a real CampaignTrace with the engine's event grammar
    ks = kinds(rep.trace)
    assert rep.trace.source == "live"
    assert ks.index("failure") < ks.index("verdict") < ks.index("migrate")
    mig = next(e for e in rep.trace.events if e.kind == "migrate")
    assert mig.target >= 4  # landed on a warm spare
    assert "ckpt_write" in ks  # schedule markers merge in at finalize

    # live and predicted are the same campaign priced two ways
    assert rep.predicted_total_s == pytest.approx(
        predicted_makespan_s(plan.spec, plan.strategy, seed=plan.seed,
                             detector=plan.detector, workload=plan.workload)
    )
    assert rep.live_total_s is not None
    assert rep.rel_err < 0.25, (rep.live_total_s, rep.predicted_total_s)


def test_stub_stall_campaign_is_reaped_by_the_stall_detector(tmp_path):
    plan = stub_plan(live_spec())
    rep, daemon, _ = run_stub_daemon(
        plan, tmp_path, injector="stall",
        stall_timeout_wall_s=3.0 * plan.step_wall_s,
    )
    assert rep.survived
    assert rep.n_stalls == 1 and rep.n_handled == 1
    assert sorted(rep.results) == [0, 1, 2, 3]
    fail = next(e for e in rep.trace.events if e.kind == "failure")
    assert dict(fail.meta)["cause"] == "stalled"


def test_stub_slow_injection_is_not_a_death(tmp_path):
    plan = stub_plan(live_spec())
    rep, daemon, _ = run_stub_daemon(
        plan, tmp_path, injector="slow", max_replans=0,
    )
    assert rep.survived
    assert rep.n_events == 0 and rep.n_handled == 0 and rep.n_stalls == 0
    assert sorted(rep.results) == [0, 1, 2, 3]
    # the slowed shard is the long pole: it really paced 2x after t=2250
    assert rep.live_total_s > 3600.0


def test_stub_drift_doubling_triggers_exactly_one_replan(tmp_path):
    """The satellite contract: the observed failure rate doubling past the
    spec's declared rate triggers exactly one re-plan + strategy switch."""
    plan = stub_plan(live_spec())
    clock_kills = []

    def hook(launcher):
        # script two organic kills the spec never declared (its burst says
        # ONE failure per horizon; these double+ the observed rate)
        def kill(host):
            def fire():
                launcher.stubs[host].deliver("kill")
            return fire
        clock_kills.extend([(1.0, kill(1)), (1.3, kill(2))])

    def planner(observed_spec, old_plan, drift_info):
        # the oracle sees the scaled spec, not the stale one
        assert drift_info["cause"] == "failure_rate"
        assert observed_spec.processes[0].params["k"] >= 2
        return "hybrid"

    rep, daemon, _ = run_stub_daemon(
        plan, tmp_path, injector="none",
        script=clock_kills, launcher_hook=hook,
        planner=planner, max_replans=1,
    )
    assert rep.survived
    assert rep.n_replans == 1  # exactly one, though drift persists all run
    assert rep.final_strategy == "hybrid" and rep.strategy == "central_single"
    assert rep.replans[0]["cause"] == "failure_rate"
    assert rep.replans[0]["from"] == "central_single"
    assert rep.replans[0]["to"] == "hybrid"
    replan_events = [e for e in rep.trace.events if e.kind == "rebalance"]
    assert len(replan_events) == 1
    assert dict(replan_events[0].meta)["reason"] == "replan"
    assert rep.n_handled == 2  # both scripted victims moved to spares


def test_stub_respawn_backoff_retries_failed_spawns(tmp_path):
    plan = stub_plan(live_spec())
    launcher_ref = {}

    def hook(launcher):
        launcher_ref["l"] = launcher

    def arm(n):
        def fire():
            launcher_ref["l"].fail_next_spawns = n
        return fire

    rep, daemon, launcher = run_stub_daemon(
        plan, tmp_path, injector="kill",
        script=[(0.5, arm(2))],  # armed after the 6 fleet spawns succeed
        launcher_hook=hook,
        respawn_backoff_s=0.1,
    )
    assert rep.survived
    # repair completes at t=2250+1200=3450 < makespan: the respawn path ran
    assert rep.n_reprovisioned == 1
    # 6 fleet spawns + 2 injected failures + 1 success
    assert launcher.n_spawn_attempts == 9
    assert any(e.kind == "provision" for e in rep.trace.events)


def test_stub_blacklist_ttl_restores_eligibility(tmp_path):
    spec = live_spec()
    spec.max_strikes = 1  # first strike is permanent
    plan = stub_plan(spec)
    rep, daemon, _ = run_stub_daemon(
        plan, tmp_path, injector="kill", blacklist_ttl_s=600.0,
    )
    assert rep.survived and rep.n_blacklisted == 1
    assert any(e.kind == "blacklist" for e in rep.trace.events)
    # TTL expired mid-run: the victim left the blacklist again
    assert daemon.rt.blacklist == set()


def test_stub_daemon_writes_machine_readable_status(tmp_path):
    plan = stub_plan(live_spec())
    rep, daemon, _ = run_stub_daemon(plan, tmp_path, injector="kill")
    status = daemon.spool.read_status()
    assert status["state"] == "done"
    assert status["n_events"] == 1
    assert status["final_strategy"] == "central_single"
    # LiveReport round-trips through JSON (the CLI's --json line)
    assert json.loads(json.dumps(rep.to_dict()))["survived"] is True


def test_live_trace_exports_like_a_simulated_one(tmp_path):
    from repro.obs.export import write_chrome_trace

    plan = stub_plan(live_spec())
    rep, _, _ = run_stub_daemon(plan, tmp_path, injector="kill")
    path = write_chrome_trace(rep.trace, str(tmp_path / "live_trace.json"))
    doc = json.load(open(path))
    names = {ev.get("name") for ev in doc["traceEvents"]}
    assert "failure" in names and "migrate" in names


# ------------------------------------------------ one process per chip ---
def test_jax_workload_refused_when_workers_outnumber_chips(tmp_path):
    """4 shard holders + 2 warm spares of a JAX workload on a one-chip
    host: the daemon refuses before launching anything, naming the cause,
    instead of letting five workers hang on the chip's lock."""
    spawned = []
    plan = stub_plan(live_spec("genome_search"))
    plan.device = {"platform": "tpu", "count": 1}
    with pytest.raises(RuntimeError, match="need 6 tpu chips, the host has 1"):
        run_stub_daemon(plan, tmp_path / "jax", launcher_hook=spawned.append)
    assert spawned[0].n_spawn_attempts == 0

    # the analytic workload runs no JAX in its workers: unchanged
    plan = stub_plan(live_spec("analytic"))
    plan.device = {"platform": "tpu", "count": 1}
    rep, _, _ = run_stub_daemon(plan, tmp_path / "analytic")
    assert rep.survived and sorted(rep.results) == [0, 1, 2, 3]


def test_replan_plans_on_the_cpu_while_workers_hold_every_chip(tmp_path, monkeypatch):
    """6 JAX workers on a 6-chip host hold every chip mid-run: the re-plan
    child is told to plan on the CPU instead of asking for a held chip,
    and the replan entry records the device the child reports."""
    import repro.orchestrator.plan as plan_mod

    asked = []

    def fake_plan_in_child(fn, /, *args, jax_platforms=None, **kwargs):
        asked.append(jax_platforms)
        return ("hybrid", {}), {"platform": jax_platforms or "tpu", "count": 1}

    monkeypatch.setattr(plan_mod, "plan_in_child", fake_plan_in_child)
    plan = stub_plan(live_spec("genome_search"))
    plan.device = {"platform": "tpu", "count": 6}
    kills = []

    def hook(launcher):
        kills.extend(
            (t, lambda h=h: launcher.stubs[h].deliver("kill")) for t, h in ((1.0, 1), (1.3, 2))
        )

    rep, _, _ = run_stub_daemon(
        plan, tmp_path, injector="none", script=kills, launcher_hook=hook,
        max_replans=1,
    )
    assert asked == ["cpu"]
    assert rep.n_replans == 1 and rep.replans[0]["to"] == "hybrid"
    assert rep.replans[0]["device"] == {"platform": "cpu", "count": 1}

    # the analytic workload's workers hold no chip: the child may take one
    asked.clear()
    plan = stub_plan(live_spec("analytic"))
    plan.device = {"platform": "tpu", "count": 1}
    kills.clear()
    rep, _, _ = run_stub_daemon(
        plan, tmp_path / "analytic", injector="none", script=kills,
        launcher_hook=hook, max_replans=1,
    )
    assert asked == [None] and rep.replans[0]["device"]["platform"] == "tpu"


_PLAN_AND_REPLAN = """
import json, sys
from jax._src import xla_bridge
from repro.orchestrator.daemon import OrchestratorDaemon
from repro.orchestrator.plan import make_live_plan, plan_in_child
from repro.orchestrator.spool import Spool
from repro.orchestrator.testing import FakeClock, StubLauncher, scripted_sleeper
from repro.scenarios import registry

spec = registry.get("live_genome_single")
plan, device = plan_in_child(
    make_live_plan, spec, time_scale=900.0, seed=0, strategy=None,
    candidates=("central_single", "core"), n_seeds=8, calibrate=False,
)
plan.device = device
clock = FakeClock()
launcher = StubLauncher(Spool(sys.argv[1]), clock)
# two organic kills the spec never declared: the drift monitor re-plans
kills = [(t, lambda h=h: launcher.stubs[h].deliver("kill")) for t, h in ((1.0, 1), (1.3, 2))]
daemon = OrchestratorDaemon(
    plan, launcher.spool, launcher, injector="none", clock=clock,
    async_sleep=scripted_sleeper(clock, launcher, script=kills),
    poll_wall_s=0.05, deadline_wall_s=600.0, max_replans=1, replan_seeds=8,
)
rep = daemon.run_sync()
print(json.dumps({"device": device, "n_replans": rep.n_replans,
                  "replan_device": rep.replans[0]["device"],
                  "survived": rep.survived,
                  "backend_in_daemon": xla_bridge.backends_are_initialized()}))
"""


def test_planning_and_replan_never_start_jax_in_the_daemon(tmp_path):
    """The oracle's plan and a forced re-plan both run in a child process
    that exits before the answer returns: the daemon's own process never
    initialises a JAX backend, so it never holds a chip its workers need."""
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [src, env.get("PYTHONPATH")]))
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    proc = subprocess.run(
        [sys.executable, "-c", _PLAN_AND_REPLAN, str(tmp_path / "spool")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n_replans"] == 1 and out["survived"]
    assert out["device"]["count"] >= 1 and out["device"]["platform"]
    assert out["replan_device"]["platform"] == out["device"]["platform"]
    assert out["backend_in_daemon"] is False


# --------------------------------------------------- real subprocesses ---
def test_subprocess_analytic_campaign_end_to_end(tmp_path):
    """4 real worker processes, injector kills one, daemon completes the
    campaign — the CI smoke lane's contract, at a faster time scale."""
    spec = live_spec(workload="analytic")
    plan = make_live_plan(
        spec, time_scale=1800.0, seed=0, strategy="central_single",
    )
    spool = Spool(str(tmp_path / "spool"))
    launcher = SubprocessLauncher(spool, "analytic", plan.seed, abort_after_s=120.0)
    daemon = OrchestratorDaemon(
        plan, spool, launcher, injector="kill", deadline_wall_s=90.0,
    )
    rep = daemon.run_sync()
    assert rep.survived, rep.to_dict()
    assert rep.n_handled == 1
    assert sorted(rep.results) == [0, 1, 2, 3]
    assert all(r["steps_done"] == plan.n_steps for r in rep.results.values())
    ks = kinds(rep.trace)
    assert ks.index("failure") < ks.index("verdict") < ks.index("migrate")
    assert rep.live_total_s is not None and rep.rel_err < 0.35


@pytest.mark.slow
def test_subprocess_genome_live_cert(tmp_path):
    """The end-to-end live cert: real jax genome-search shards supervised
    with the oracle-chosen strategy, zero manual intervention, live
    makespan within tolerance of the engine's prediction."""
    spec = live_spec(workload="genome_search")
    plan = make_live_plan(
        spec, time_scale=240.0, seed=0, strategy=None,
        candidates=("central_single", "core"), n_seeds=24,
    )
    assert plan.scores  # the oracle actually ranked the candidates
    spool = Spool(str(tmp_path / "spool"))
    launcher = SubprocessLauncher(
        spool, "genome_search", plan.seed, abort_after_s=300.0
    )
    daemon = OrchestratorDaemon(
        plan, spool, launcher, injector="kill", deadline_wall_s=240.0,
    )
    rep = daemon.run_sync()
    assert rep.survived, rep.to_dict()
    assert rep.n_handled == 1 and sorted(rep.results) == [0, 1, 2, 3]
    # real work crossed the migration: the genome hits survived the move
    assert all("hits" in r["payload"] for r in rep.results.values())
    ks = kinds(rep.trace)
    assert ks.index("failure") < ks.index("verdict") < ks.index("migrate")
    assert rep.rel_err < 0.25, (rep.live_total_s, rep.predicted_total_s)


# ------------------------------------------------------------------ CLI ---
def test_cli_status_reads_the_spool(tmp_path, capsys):
    from repro.orchestrator.cli import main

    sp = Spool(str(tmp_path / "spool"))
    assert main(["status", "--spool", sp.root, "--json"]) == 1  # no daemon yet
    capsys.readouterr()
    sp.write_status({"state": "running", "shards_done": 2})
    assert main(["status", "--spool", sp.root, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc == {"state": "running", "shards_done": 2}
