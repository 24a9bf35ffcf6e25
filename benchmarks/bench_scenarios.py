"""Scenario-engine benchmark: every registered campaign under every FT
strategy, plus the vectorised Monte-Carlo speedup certifications.

Emits a JSON report (BENCH_OUT/scenarios.json) with these sections:

  paper_exactness   the two Table 1/2 scenarios re-expressed as registered
                    specs must match the seed simulator's closed-form
                    totals to the second (bit-for-bit: same MicroCosts);
  campaigns         per scenario x approach: engine totals, migrations,
                    blacklistings, re-provisionings, survival;
  montecarlo        >= N seeds of the closed-form model via jax.vmap vs the
                    one-trial-per-Python-call baseline; asserts >= 10x;
  trajectories      >= N seeds of FULL engine trajectories per registered
                    family (cascade, rack, flaky, burst, partition, ...)
                    through the batched replay kernel: per-family p5/p50/
                    p95 tails + survival, a trial-for-trial differential
                    check against the Python engine, the >= 10x speedup
                    certification over the per-seed engine loop (on the
                    mc_stress family), per-family steady-state seeds/sec,
                    and the fleet-scale certification: >= 100x over the
                    engine loop on the 1024-node fleet_stress family
                    through the tiled/sharded kernel, engine-exact on
                    every differentially-checked seed;
  detectors         per-detector x per-family detection quality over the
                    compiled verdict tapes: coverage (bounded by the 29 %
                    of failures that emit a signature at all), precision
                    (the paper's ~64 % operating band), recall over the
                    signature-emitting events, and median claimed lead
                    time. Asserted for the ml detector on the
                    rack-correlated families.
  workloads         per-workload x per-family x per-strategy overhead
                    matrix over the batched trajectory path, plus each
                    workload's calibrated sizing (state bytes, Z, step
                    time). Asserts the paper's headline ordering —
                    checkpointing >> multi-agent overhead — on the
                    genome_search (and analytic) workloads, and reports
                    every (workload, family) cell where it inverts.
  traffic           the serving fleet (decode_fleet_churn) billed for
                    request-level SLOs: per-strategy x per-autoscaler
                    p50/p99 latency, dropped requests, and availability
                    over the batched trajectory path. Certifies that the
                    p99-billed strategy ordering differs from the
                    makespan ordering — checkpoint-write stalls freeze
                    serving, so the ranking a fleet operator sees is not
                    the one the makespan bill suggests;
  orchestrator      the live daemon closing the loop on the simulator:
                    deterministic stub campaigns on live_genome_single
                    (fake clock, no subprocesses) supervised end to end
                    for >= 2 strategies and under EVERY registered fault
                    injector, comparing the live (scaled) makespan against
                    the engine's predicted bill for the same (spec, seed).
                    Asserts the live/predicted relative error stays inside
                    the tolerance band for the death-path injectors;
  profiling         the vmapped replay kernel's compile-vs-execute split
                    (jit AOT lower/compile vs steady-state execution) and
                    the headline seeds/sec throughput, plus measured
                    Pallas step-time surfaces per shard count next to the
                    analytic ones from workloads/builtin.py;
  observability     one engine campaign recorded as a structured trace
                    and exported as Chrome-trace JSON (open in Perfetto),
                    the engine-trace == kernel-trace differential check,
                    a per-campaign metric frame whose components sum
                    exactly to the billed total, and the aggregated
                    p5/p50/p95 metric frames from the batched MC path.

A schema-versioned summary of the headline numbers (seeds/sec, speedup
certs, per-workload overhead matrix) is additionally written to
BENCH_scenarios.json at the repo root — the perf-trajectory record.

Usage:
  python benchmarks/bench_scenarios.py [--seeds 2000] [--dry-run]

--dry-run swaps in tiny trial counts and skips the speedup assertions —
the CI smoke path (it still exercises the profiling and observability
sections end to end).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import OUT_DIR
from repro.core.sim import fmt_hms, measure_micro, scenario_totals, strategy_rows
from repro.scenarios import (
    compile_batch,
    mc_totals,
    mc_trajectories,
    python_loop_baseline,
    registry,
)
from repro.core.failure import PREDICTABLE_FRACTION
from repro.obs.profile import profile_replay, stopwatch
from repro.scenarios.engine import CampaignEngine
from repro.scenarios.montecarlo import params_from_scenario
from repro.strategies import names as strategy_names
from repro.telemetry import registry as detector_registry
from repro.utils.backend import use_compile_cache
from repro.workloads import registry as workload_registry

PAPER_SCENARIOS = ("table1_periodic", "table1_random", "table2_random")
MIN_SPEEDUP = 10.0
SPEEDUP_FAMILY = "mc_stress"  # big enough that the ratio is unambiguous
# the fleet-scale certification: the tiled/sharded kernel vs the per-seed
# engine loop on the 1024-node family (the engine pays seconds per trial
# there, so its loop time is extrapolated from a few real runs)
FLEET_FAMILY = "fleet_stress"
MIN_FLEET_SPEEDUP = 100.0
# per-family seed caps for the trajectory tails loop: fleet-size tapes pay
# ~10 ms/seed through the batched path — plenty of tail resolution at 256
FAMILY_SEED_CAP = {FLEET_FAMILY: 256}
TRAJECTORY_STRATEGIES = ("central_single", "core")
# rack-correlated families: the ml detector's asserted operating band
DETECTOR_ASSERT_FAMILIES = ("rack_outage", "mc_stress", "multi_window_storm")
ML_PRECISION_BAND = (0.50, 0.80)  # around the paper's ~64 % operating point
# the per-workload overhead matrix: every registered workload x these
# families x these strategies, through the batched trajectory path
WORKLOAD_FAMILIES = ("flaky_node", "multi_window_storm")
WORKLOAD_STRATEGIES = ("central_single", "agent", "core", "hybrid")
MULTI_AGENT = ("agent", "core", "hybrid")
# the paper's headline ordering (checkpointing >> multi-agent overhead) is
# asserted on its own application and on the analytic anchor; the other
# workloads only *report* where it inverts
ORDERING_ASSERT_WORKLOADS = ("analytic", "genome_search")
# observability section: small family so the exported trace stays readable
OBS_FAMILY = "flaky_node"
# the serving-traffic section: the one family bound to a TrafficSpec,
# billed under every registered autoscaler x these strategies
TRAFFIC_FAMILY = "decode_fleet_churn"
TRAFFIC_STRATEGIES = ("central_single", "agent", "core", "cold_restart")
# the live-orchestrator section: stub campaigns on the live scenario,
# live (scaled) makespan vs the engine's predicted bill per strategy and
# per registered injector; parity asserted on the death-path injectors
ORCH_SCENARIO = "live_genome_single"
ORCH_STRATEGIES = ("central_single", "core")
ORCH_TIME_SCALE = 900.0  # 1 wall second = 15 simulated minutes
ORCH_TOLERANCE = 0.25  # |live - predicted| / predicted band
# parity is only meaningful where the live run replays the predicted
# failures as deaths: "none" skips the billed failure entirely, "stall"
# pays the detection timeout, "slow" really degrades the pace
ORCH_PARITY_INJECTORS = ("kill",)
BENCH_SCHEMA_VERSION = 4  # v4: orchestrator section (live vs predicted makespan)
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def check_paper_exactness(micro) -> dict:
    """Registered paper specs vs the seed simulator's strategy_rows."""
    out = {}
    ok_all = True
    for name in PAPER_SCENARIOS:
        spec = registry.get(name)
        proc = spec.processes[0]
        offset_min = proc.params.get("offset_s", 900.0) / 60.0 if proc.kind == "periodic" else None
        rows = strategy_rows(
            spec.horizon_s / 3600.0,
            [spec.period_s / 3600.0],
            n_nodes=spec.n_nodes,
            micro=micro,
            periodic_offset_min=offset_min,
        )
        via_scenario = scenario_totals(spec, micro=micro)
        rec = {}
        for r in rows:
            if r.strategy not in via_scenario:
                continue
            seed_total = (
                r.exec_1periodic_s if spec.closed_form == "periodic" else r.exec_1random_s
            )
            got = via_scenario[r.strategy]["total_s"]
            exact = bool(got == seed_total)
            ok_all &= exact
            rec[r.strategy] = {
                "seed_simulator": fmt_hms(seed_total),
                "scenario_engine": fmt_hms(got),
                "exact": exact,
            }
        out[name] = rec
    out["all_exact"] = ok_all
    return out


def run_campaigns(micro, scenarios=None) -> dict:
    out = {}
    for name in scenarios or registry.names():
        spec = registry.get(name)
        if spec.closed_form:
            continue  # priced above, exactly
        # workload-bound families bill from their own calibrated micro
        # (resolved by the engine); analytic families share the seed record
        kw = {"micro": micro} if spec.workload == "analytic" else {}
        per = {}
        for approach in strategy_names():  # every registered strategy
            res = CampaignEngine(spec, approach, **kw).run()
            d = res.to_dict()
            d["total"] = fmt_hms(res.total_s) if res.total_s is not None else None
            per[approach] = d
        out[name] = per
    return out


def run_montecarlo(micro, n_seeds: int, assert_speedup: bool) -> dict:
    spec = registry.get("table2_random")
    out = {"n_seeds": n_seeds, "strategies": {}}
    for strat in ("central_single", "core"):
        params = params_from_scenario(spec, strat, micro)
        # proactive params are deterministic (no lost progress): mc_totals
        # short-circuits, so only the stochastic strategies certify the
        # vectorisation speedup
        stochastic = params.lost_progress and params.fixed_lost_s is None

        # warm-up compiles the jitted program; the paid path is steady-state
        mc_totals(params, n_seeds=n_seeds, seed=0)
        with stopwatch() as sw_vec:
            mc = mc_totals(params, n_seeds=n_seeds, seed=1)
        t_vec = sw_vec.s

        with stopwatch() as sw_loop:
            base = python_loop_baseline(params, n_seeds=n_seeds, seed=1)
        t_loop = sw_loop.s

        speedup = t_loop / max(t_vec, 1e-9)
        # same model, same seed count -> means agree to MC error
        mean_gap = abs(mc["mean_s"] - float(base.mean())) / float(base.mean())
        out["strategies"][strat] = {
            "mean": fmt_hms(mc["mean_s"]),
            "std_s": round(mc["std_s"], 1),
            "p5": fmt_hms(mc["p5_s"]),
            "p95": fmt_hms(mc["p95_s"]),
            "vectorised_s": round(t_vec, 5),
            "python_loop_s": round(t_loop, 5),
            "speedup": round(speedup, 1),
            "stochastic": stochastic,
            "mean_gap_pct": round(100 * mean_gap, 3),
        }
        if assert_speedup:
            if stochastic:
                assert speedup >= MIN_SPEEDUP, (
                    f"vectorised MC only {speedup:.1f}x faster than the Python loop "
                    f"for {strat} (need >= {MIN_SPEEDUP}x)"
                )
            assert mean_gap < 0.02, f"MC mean diverged from baseline: {mean_gap:.3%}"
    out["min_speedup_required"] = MIN_SPEEDUP
    out["asserted"] = assert_speedup
    return out


def run_trajectories(micro, n_seeds: int, assert_speedup: bool) -> dict:
    """Batched trajectory Monte-Carlo over EVERY registered family:
    per-family recovery-cost tails, a trial-for-trial differential check
    against the Python engine, and the speedup certification."""
    out = {"n_seeds": n_seeds, "families": {}}
    stress_mc = None
    for name in registry.names():
        spec = registry.get(name)
        n_fam = min(n_seeds, FAMILY_SEED_CAP.get(name, n_seeds))
        batch = compile_batch(spec, n_fam)  # shared across strategies
        per = {}
        wl_micro = micro if spec.workload == "analytic" else None
        for strat in TRAJECTORY_STRATEGIES:
            mc = mc_trajectories(spec, strat, micro=wl_micro, batch=batch)
            if name == SPEEDUP_FAMILY and strat == "central_single":
                stress_mc = mc  # reused for the differential check below
            per[strat] = {
                "survival_rate": round(mc["survival_rate"], 4),
                "mean": fmt_hms(mc["mean_s"]) if mc["survival_rate"] else None,
                "p5": fmt_hms(mc["p5_s"]) if mc["survival_rate"] else None,
                "p50": fmt_hms(mc["p50_s"]) if mc["survival_rate"] else None,
                "p95": fmt_hms(mc["p95_s"]) if mc["survival_rate"] else None,
                "mean_migrations": round(mc["counters"]["n_migrations"], 2),
                "mean_blacklisted": round(mc["counters"]["n_blacklisted"], 2),
            }
        # steady-state per-family throughput (the program is compiled by
        # the strategy loop above; this re-runs the full batched path —
        # replay + metric-frame aggregation — once more and normalises)
        with stopwatch() as sw_fam:
            mc_trajectories(spec, "central_single", micro=wl_micro, batch=batch)
        per["n_seeds"] = n_fam
        per["seeds_per_s"] = round(n_fam / max(sw_fam.s, 1e-9), 1)
        per["workload"] = spec.workload  # which cost model billed the trials
        out["families"][name] = per

    # trial-for-trial differential: the kernel must reproduce the engine
    # exactly on identical seeds (a slice of the family loop's batch; the
    # full sweep lives in tests/test_trajectory.py)
    spec = registry.get(SPEEDUP_FAMILY)
    mc = stress_mc
    n_diff = min(20, n_seeds)
    exact = True
    for s in range(n_diff):
        r = CampaignEngine(spec, "central_single", micro=micro, seed=s).run()
        got = float(mc["trials"]["total_s"][s])
        want = r.total_s if r.survived else float("nan")
        exact &= (got != got and want != want) or abs(got - want) < 1e-6 * abs(want)
    out["engine_match"] = {"n_trials": n_diff, "exact": bool(exact)}

    # speedup: steady-state batched path (the differential call above has
    # compiled the jitted program for these shapes) vs the per-seed Python
    # engine loop, extrapolated from n_base real engine runs. The timed
    # batched call includes tape compilation — the full cost of the path.
    with stopwatch() as sw_traj:
        mc_trajectories(spec, "central_single", n_seeds=n_seeds, micro=micro)
    t_traj = sw_traj.s
    n_base = min(40, n_seeds)
    with stopwatch() as sw_loop:
        for s in range(n_base):
            CampaignEngine(spec, "central_single", micro=micro, seed=s).run()
    t_loop = sw_loop.s / n_base * n_seeds
    speedup = t_loop / max(t_traj, 1e-9)
    out["speedup"] = {
        "family": SPEEDUP_FAMILY,
        "batched_s": round(t_traj, 4),
        "engine_loop_s": round(t_loop, 4),
        "engine_loop_seeds_measured": n_base,
        "speedup": round(speedup, 1),
    }
    if assert_speedup:
        assert exact, "trajectory kernel diverged from the Python engine"
        assert speedup >= MIN_SPEEDUP, (
            f"batched trajectory MC only {speedup:.1f}x faster than the "
            f"per-seed engine loop (need >= {MIN_SPEEDUP}x)"
        )
    out["min_speedup_required"] = MIN_SPEEDUP

    # fleet-scale certification: the tiled/sharded kernel vs the per-seed
    # engine loop on the 1024-node family. The engine pays seconds per
    # trial here, so its loop time is extrapolated from a few real runs —
    # each of which doubles as a trial-for-trial differential check. The
    # timed batched call again includes tape compilation.
    fspec = registry.get(FLEET_FAMILY)
    n_fleet = min(512, n_seeds)
    mc_trajectories(fspec, "central_single", n_seeds=n_fleet, micro=micro)  # warm
    with stopwatch() as sw_fleet:
        fmc = mc_trajectories(fspec, "central_single", n_seeds=n_fleet, micro=micro)
    t_fleet = sw_fleet.s
    n_fleet_base = 3
    fleet_exact = True
    with stopwatch() as sw_floop:
        engine_res = [
            CampaignEngine(fspec, "central_single", micro=micro, seed=s).run()
            for s in range(n_fleet_base)
        ]
    for s, r in enumerate(engine_res):
        got = float(fmc["trials"]["total_s"][s])
        want = r.total_s if r.survived else float("nan")
        fleet_exact &= (got != got and want != want) or abs(got - want) < 1e-6 * abs(want)
    t_floop = sw_floop.s / n_fleet_base * n_fleet
    fleet_speedup = t_floop / max(t_fleet, 1e-9)
    out["fleet"] = {
        "family": FLEET_FAMILY,
        "n_nodes": fspec.n_nodes,
        "n_seeds": n_fleet,
        "batched_s": round(t_fleet, 4),
        "batched_ms_per_seed": round(1000.0 * t_fleet / n_fleet, 3),
        "engine_loop_s": round(t_floop, 4),
        "engine_s_per_seed": round(sw_floop.s / n_fleet_base, 4),
        "engine_loop_seeds_measured": n_fleet_base,
        "speedup": round(fleet_speedup, 1),
        "engine_match": bool(fleet_exact),
        "min_required": MIN_FLEET_SPEEDUP,
    }
    if assert_speedup:
        assert fleet_exact, (
            f"trajectory kernel diverged from the Python engine on {FLEET_FAMILY}"
        )
        assert fleet_speedup >= MIN_FLEET_SPEEDUP, (
            f"fleet-scale batched MC only {fleet_speedup:.1f}x faster than the "
            f"per-seed engine loop on {FLEET_FAMILY} (need >= {MIN_FLEET_SPEEDUP}x)"
        )
    out["asserted"] = assert_speedup
    return out


def run_detectors(n_seeds: int, assert_bounds: bool) -> dict:
    """Per-detector x per-family detection quality over compiled verdict
    tapes — the exact per-event draws the engine and replay kernel route
    to the strategies. Ground truth is the tape's ``predictable`` bit:
    coverage = TP / all failures (bounded by the 29 % that emit a
    degrading signature), precision = TP / claimed, recall = TP /
    signature-emitting, lead = the detector's claimed lead time."""
    import numpy as np

    out = {"n_seeds": n_seeds, "detectors": {}}
    fams = [n for n in registry.names() if not registry.get(n).closed_form]
    batches = {f: compile_batch(registry.get(f), n_seeds) for f in fams}
    for det_name in detector_registry.names():
        det = detector_registry.get(det_name)
        per = {}
        for fam in fams:
            spec = registry.get(fam)
            batch = batches[fam]
            tp = fp = fn = tn = 0
            leads = []
            for s in range(batch.n_seeds):
                v, lead = det.verdict_tape(
                    spec,
                    times=batch.times[s],
                    predictable=batch.predictable[s],
                    rack_corr=batch.rack_corr[s],
                    seed=int(batch.seeds[s]),
                )
                m = batch.valid[s]
                gt, pd = batch.predictable[s][m], v[m]
                tp += int((gt & pd).sum())
                fp += int((~gt & pd).sum())
                fn += int((gt & ~pd).sum())
                tn += int((~gt & ~pd).sum())
                leads.extend(lead[m][pd].tolist())
            total = max(tp + fp + fn + tn, 1)
            per[fam] = {
                "events": total,
                "coverage": round(tp / total, 4),
                "precision": round(tp / max(tp + fp, 1), 4),
                "recall": round(tp / max(tp + fn, 1), 4),
                "median_lead_s": round(float(np.median(leads)), 2) if leads else 0.0,
            }
        out["detectors"][det_name] = per
        if assert_bounds and det_name == "ml":
            for fam in DETECTOR_ASSERT_FAMILIES:
                r = per[fam]
                assert r["coverage"] <= PREDICTABLE_FRACTION + 0.04, (
                    f"ml coverage {r['coverage']} on {fam} exceeds the "
                    f"{PREDICTABLE_FRACTION} predictable bound"
                )
                lo, hi = ML_PRECISION_BAND
                assert lo <= r["precision"] <= hi, (
                    f"ml precision {r['precision']} on {fam} outside the "
                    f"paper's operating band {ML_PRECISION_BAND}"
                )
    out["asserted"] = assert_bounds
    return out


def run_workloads(n_seeds: int, assert_ordering: bool) -> dict:
    """Per-workload x per-family x per-strategy overhead matrix.

    Each cell Monte-Carlos the family's compiled tape batch through the
    batched trajectory kernel under one workload's calibrated micro-costs
    (tapes are workload-independent — one compile_batch per family serves
    every workload) and reports the mean overhead fraction
    ``(mean_total - horizon) / horizon`` over surviving trials.

    The paper's headline claim — checkpointing adds ~90 % overhead where
    the multi-agent approaches add ~10 % — becomes workload-parameterized
    here: the ordering (checkpoint overhead strictly above every
    multi-agent strategy's) is asserted on the paper's own application
    (``genome_search``) and the ``analytic`` anchor, and every cell where
    another workload *inverts* it is reported under ``"inversions"``."""
    out = {"n_seeds": n_seeds, "workloads": {}, "inversions": []}
    batches = {f: compile_batch(registry.get(f), n_seeds) for f in WORKLOAD_FAMILIES}
    for wl_name in workload_registry.names():
        wl = workload_registry.get(wl_name)
        table = wl.cost_table("placentia", n_nodes=4)
        rec = {
            "sizing": {
                "z": table.z,
                "state_bytes_per_shard": table.state_bytes_per_shard,
                "payload_bytes": table.payload_bytes,
                "step_time_s_at_4": round(float(table.step_time(4)), 4),
                "ckpt_write_s_at_4": round(float(table.at(4)["ckpt_write_s"]), 2),
            },
            "families": {},
        }
        for fam in WORKLOAD_FAMILIES:
            spec = registry.get(fam)
            per = {}
            for strat in WORKLOAD_STRATEGIES:
                mc = mc_trajectories(spec, strat, batch=batches[fam], workload=wl)
                ovh = (
                    (mc["mean_s"] - spec.horizon_s) / spec.horizon_s
                    if mc["survival_rate"]
                    else None
                )
                per[strat] = {
                    "overhead_pct": round(100 * ovh, 3) if ovh is not None else None,
                    "survival_rate": round(mc["survival_rate"], 4),
                }
            rec["families"][fam] = per
            ck = per["central_single"]["overhead_pct"]
            agents = [
                per[s]["overhead_pct"]
                for s in MULTI_AGENT
                if per[s]["overhead_pct"] is not None
            ]
            if ck is not None and agents and ck <= max(agents):
                out["inversions"].append(
                    {
                        "workload": wl_name,
                        "family": fam,
                        "checkpoint_pct": ck,
                        "max_multi_agent_pct": max(agents),
                    }
                )
        out["workloads"][wl_name] = rec

    if assert_ordering:
        for wl_name in ORDERING_ASSERT_WORKLOADS:
            for fam in WORKLOAD_FAMILIES:
                per = out["workloads"][wl_name]["families"][fam]
                ck = per["central_single"]["overhead_pct"]
                assert ck is not None, (
                    f"cannot assert the paper ordering on workload {wl_name!r}, "
                    f"family {fam!r}: no central_single trial survived"
                )
                for s in MULTI_AGENT:
                    ma = per[s]["overhead_pct"]
                    assert ma is not None and ma < ck, (
                        f"paper ordering violated on workload {wl_name!r}, "
                        f"family {fam!r}: {s} overhead "
                        f"{ma}% >= checkpointing {ck}%"
                    )
    out["asserted"] = assert_ordering
    return out


def run_traffic(n_seeds: int, assert_ordering: bool) -> dict:
    """Request-level SLO matrix on the serving fleet: every registered
    autoscaler x the serving strategies, over one shared tape batch.

    Beyond the numbers, the section certifies the subsystem's reason to
    exist: under the ``static`` capacity policy, ranking strategies by
    mean p99 latency gives a *different order* than ranking them by mean
    makespan. Checkpoint writes freeze the whole serving fleet (the
    window strategies' p99 collapses) while cold restarts recompute
    everything without ever stalling serving — so the cheapest strategy
    by the classic bill is not the one a fleet operator should run."""
    from repro.traffic import names as autoscaler_names

    spec = registry.get(TRAFFIC_FAMILY)
    batch = compile_batch(spec, n_seeds)  # shared across the whole matrix
    out = {
        "family": TRAFFIC_FAMILY,
        "n_nodes": spec.n_nodes,
        "n_seeds": n_seeds,
        "traffic": spec.traffic.to_dict(),
        "expected_requests": round(spec.traffic.expected_requests(spec.horizon_s), 1),
        "matrix": {},
    }
    makespan_mean = {}
    p99_mean = {}
    for strat in TRAFFIC_STRATEGIES:
        per = {}
        for asc in autoscaler_names():
            mc = mc_trajectories(spec, strat, batch=batch, autoscaler=asc)
            slo = mc["slo"]
            per[asc] = {
                "p50_s": slo["p50_s"]["mean"] if slo["p50_s"] else None,
                "p99_s": slo["p99_s"]["mean"] if slo["p99_s"] else None,
                "dropped_mean": slo["dropped_mean"],
                "availability_mean": slo["availability_mean"],
                "survival_rate": round(mc["survival_rate"], 4),
            }
            if asc == "static":
                makespan_mean[strat] = mc["mean_s"]
                p99_mean[strat] = per[asc]["p99_s"]
        out["matrix"][strat] = per
    by_makespan = sorted(makespan_mean, key=makespan_mean.get)
    by_p99 = sorted(p99_mean, key=lambda s: (p99_mean[s] is None, p99_mean[s]))
    out["ordering"] = {
        "by_makespan": by_makespan,
        "by_p99_static": by_p99,
        "differs": by_makespan != by_p99,
    }
    if assert_ordering:
        assert out["ordering"]["differs"], (
            f"p99-billed strategy ordering {by_p99} equals the makespan "
            f"ordering on {TRAFFIC_FAMILY} — the serving bill adds no "
            f"information; recalibrate the family's TrafficSpec"
        )
    out["asserted"] = assert_ordering
    return out


def run_orchestrator(assert_tolerance: bool) -> dict:
    """The live daemon closing the loop: supervise deterministic stub
    campaigns on the live scenario and compare the live (scaled) makespan
    against the engine's predicted bill for the same (spec, seed).

    Two sweeps over one scenario (``live_genome_single``):

      strategies   >= 2 FT strategies under the ``kill`` injector — the
                   live campaign must land within ORCH_TOLERANCE of the
                   engine's prediction for each;
      injectors    central_single under EVERY registered injector — the
                   full fault-injection axis drives the daemon end to
                   end; parity is asserted only for ``kill`` (``none``
                   never pays the predicted failure bill, ``stall`` pays
                   the detection timeout, ``slow`` really degrades the
                   pace — their live totals legitimately leave the band).

    Stub campaigns replay the daemon's real control loop (heartbeat
    ingest, detector verdicts, strategy resolution, modelled-stall
    resumes) under a fake clock — deterministic and subprocess-free, so
    the recorded numbers are stable across hosts."""
    import tempfile

    from repro.orchestrator import registry as injector_registry
    from repro.orchestrator.daemon import OrchestratorDaemon
    from repro.orchestrator.plan import make_live_plan
    from repro.orchestrator.spool import Spool
    from repro.orchestrator.testing import FakeClock, StubLauncher, scripted_sleeper

    def live_run(strategy: str, injector: str) -> dict:
        spec = registry.get(ORCH_SCENARIO)
        plan = make_live_plan(
            spec, time_scale=ORCH_TIME_SCALE, seed=0,
            strategy=strategy, calibrate=False,
        )
        clock = FakeClock()
        spool = Spool(tempfile.mkdtemp(prefix="bench_orch_"))
        launcher = StubLauncher(spool, clock)
        daemon = OrchestratorDaemon(
            plan, spool, launcher, injector=injector, clock=clock,
            async_sleep=scripted_sleeper(clock, launcher),
            poll_wall_s=0.05, deadline_wall_s=600.0,
            stall_timeout_wall_s=3.0 * plan.step_wall_s,
        )
        rep = daemon.run_sync()
        return {
            "survived": rep.survived,
            "live_total_s": round(rep.live_total_s, 1) if rep.live_total_s else None,
            "predicted_total_s": round(rep.predicted_total_s, 1),
            "rel_err": round(rep.rel_err, 4) if rep.live_total_s else None,
            "n_events": rep.n_events,
            "n_handled": rep.n_handled,
            "n_stalls": rep.n_stalls,
            "n_shards_done": len(rep.results),
        }

    out = {
        "scenario": ORCH_SCENARIO,
        "time_scale": ORCH_TIME_SCALE,
        "tolerance": ORCH_TOLERANCE,
        "strategies": {},
        "injectors": {},
    }
    for strat in ORCH_STRATEGIES:
        out["strategies"][strat] = live_run(strat, "kill")
    for inj in injector_registry.names():  # the full injection axis
        out["injectors"][inj] = live_run("central_single", inj)

    if assert_tolerance:
        for strat, r in out["strategies"].items():
            assert r["survived"], f"live campaign lost under {strat}"
            assert r["rel_err"] is not None and r["rel_err"] < ORCH_TOLERANCE, (
                f"live makespan {r['live_total_s']}s vs predicted "
                f"{r['predicted_total_s']}s under {strat}: rel_err "
                f"{r['rel_err']} outside the {ORCH_TOLERANCE} band"
            )
        for inj in ORCH_PARITY_INJECTORS:
            r = out["injectors"][inj]
            assert r["survived"] and r["rel_err"] < ORCH_TOLERANCE, (
                f"injector {inj}: live {r['live_total_s']}s vs predicted "
                f"{r['predicted_total_s']}s (rel_err {r['rel_err']})"
            )
    out["asserted"] = assert_tolerance
    return out


def run_profiling(micro, n_seeds: int, dry_run: bool) -> dict:
    """Compile-vs-execute split for the vmapped replay kernel (jit AOT
    lower/compile vs steady-state execution, seeds/sec throughput) plus
    measured Pallas step-time surfaces per shard count — the wall-clock
    siblings of the analytic surfaces in workloads/builtin.py. The
    backend travels with every number: on CPU the Pallas path runs in
    interpret mode and is never comparable to a compiled TPU figure."""
    from repro.scenarios.trajectory import default_seed_devices, replay_cache_stats

    spec = registry.get(SPEEDUP_FAMILY)
    out = {"replay": {}, "kernels": {}}
    for strat in TRAJECTORY_STRATEGIES:
        out["replay"][strat] = profile_replay(spec, strat, n_seeds=n_seeds, micro=micro)

    # fleet-scale profile: the tiled/sharded execution shape on the
    # 1024-node family, sharding the seed axis over every local device,
    # plus the donation A/B — record-mode outputs are [seeds, slots] so
    # donated tape buffers alias into them and peak memory drops
    fspec = registry.get(FLEET_FAMILY)
    n_fleet = 32 if dry_run else 256
    out["replay"][FLEET_FAMILY] = profile_replay(
        fspec,
        "central_single",
        n_seeds=n_fleet,
        micro=micro,
        n_devices=default_seed_devices(n_fleet),
    )
    mem_ab = {}
    for label, donate in (("donate", True), ("no_donate", False)):
        p = profile_replay(
            fspec,
            "central_single",
            n_seeds=n_fleet,
            micro=micro,
            donate=donate,
            record_slots=True,
            n_exec=1,
            n_devices=1,  # isolate donation from shard_map's buffer layout
        )
        mem_ab[label] = p["memory"]
    if mem_ab["donate"] and mem_ab["no_donate"]:
        mem_ab["peak_drop_bytes"] = (
            mem_ab["no_donate"]["peak_bytes"] - mem_ab["donate"]["peak_bytes"]
        )
    out["fleet_memory"] = mem_ab
    # how many distinct XLA programs the whole bench compiled so far vs
    # how many replays were served from cache (the cost table travels as
    # traced values, so every strategy on one scenario shape shares one
    # compile)
    out["program_cache"] = replay_cache_stats()

    # interpret-mode Pallas is slow: tiny shapes in dry-run, modest in full
    shards = (1, 2) if dry_run else (1, 2, 4)
    shape = (
        dict(batch=2, seq_len=32, heads=2, head_dim=16)
        if dry_run
        else dict(batch=4, seq_len=128, heads=2, head_dim=32)
    )
    for wl_name in workload_registry.names():
        wl = workload_registry.get(wl_name)
        surf = wl.measured_step_surface(n_shards=shards, **shape)
        if surf is None:
            continue  # no kernel hot path (analytic, genome_search)
        table = wl.cost_table("placentia", n_nodes=4)
        surf["analytic_step_time_s"] = [
            round(float(table.step_time(n)), 6) for n in shards
        ]
        out["kernels"][wl_name] = surf
    return out


def run_observability(micro, n_seeds: int) -> dict:
    """One campaign end to end through the obs layer: record an engine
    trace, export it as Chrome-trace JSON (open in Perfetto), check the
    kernel-side reconstruction reproduces it event for event, and check
    the metric frame's components sum exactly to the billed total. Also
    aggregates p5/p50/p95 metric frames over the batched MC path."""
    from repro.obs.export import write_chrome_trace
    from repro.obs.metrics import availability_timeline, frame_from_result, verdict_ledger
    from repro.obs.trace import reconstruct_traces

    spec = registry.get(OBS_FAMILY)
    res = CampaignEngine(spec, "core", micro=micro, seed=0, trace=True).run()
    os.makedirs(OUT_DIR, exist_ok=True)
    trace_path = os.path.join(OUT_DIR, f"trace_{OBS_FAMILY}_core.json")
    write_chrome_trace(res.trace, trace_path)

    # engine trace == kernel-reconstructed trace, event for event (the
    # full family x strategy sweep lives in tests/test_obs.py)
    parity = True
    for strat in ("central_single", "core"):
        ktr = reconstruct_traces(spec, strat, n_seeds=2, micro=micro)
        for s in range(2):
            etr = CampaignEngine(spec, strat, micro=micro, seed=s, trace=True).run().trace
            parity &= etr.comparable() == ktr[s].comparable()

    fr = frame_from_result(spec, res, seed=0)
    mc = mc_trajectories(spec, "core", micro=micro, n_seeds=n_seeds)
    return {
        "family": OBS_FAMILY,
        "trace": {
            "path": trace_path,
            "n_events": len(res.trace.events),
            "counts": res.trace.counts(),
            "survived": res.trace.survived,
        },
        "trace_parity": bool(parity),
        "metric_frame": {
            "breakdown": fr.breakdown(),
            "sums_to_billed_total": bool(fr.total_s() == res.total_s),
            "overhead_frac": round(fr.overhead_frac, 6),
        },
        "aggregated_frames": mc["frames"],
        "verdict_ledger": verdict_ledger(res.trace),
        "availability_points": len(availability_timeline(res.trace)),
    }


def write_bench_record(report: dict, dry_run: bool) -> str:
    """The schema-versioned perf-trajectory record at the repo root:
    just the headline numbers future sessions diff against."""
    prof = report["profiling"]["replay"]
    sp = report["trajectories"]["speedup"]
    overhead = {
        wl: {
            fam: {s: per[s]["overhead_pct"] for s in WORKLOAD_STRATEGIES}
            for fam, per in rec["families"].items()
        }
        for wl, rec in report["workloads"]["workloads"].items()
    }
    import jax

    fleet = report["trajectories"]["fleet"]
    record = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "generated_by": "benchmarks/bench_scenarios.py",
        "dry_run": bool(dry_run),
        "backend": prof["central_single"]["backend"],
        "n_devices": int(jax.local_device_count()),
        "replay_profile": {
            strat: {
                k: p[k]
                for k in (
                    "n_seeds",
                    "n_devices",
                    "tile_slots",
                    "tape_compile_s",
                    "lower_s",
                    "compile_s",
                    "execute_s",
                    "seeds_per_s",
                    "compile_over_execute",
                )
            }
            for strat, p in prof.items()
        },
        "seeds_per_s": prof["central_single"]["seeds_per_s"],
        "per_family_seeds_per_s": {
            fam: per["seeds_per_s"]
            for fam, per in report["trajectories"]["families"].items()
        },
        "speedup": {
            "montecarlo": {
                s: mc["speedup"] for s, mc in report["montecarlo"]["strategies"].items()
            },
            "trajectory": sp["speedup"],
            "min_required": MIN_SPEEDUP,
            "fleet": {
                k: fleet[k]
                for k in (
                    "family",
                    "n_nodes",
                    "n_seeds",
                    "batched_ms_per_seed",
                    "engine_s_per_seed",
                    "speedup",
                    "engine_match",
                    "min_required",
                )
            },
            "asserted": report["trajectories"]["asserted"],
        },
        "program_cache": report["profiling"]["program_cache"],
        "fleet_memory": report["profiling"]["fleet_memory"],
        "trace_parity": report["observability"]["trace_parity"],
        "workload_overhead_pct": overhead,
        "traffic": {
            "family": report["traffic"]["family"],
            "n_nodes": report["traffic"]["n_nodes"],
            "n_seeds": report["traffic"]["n_seeds"],
            "slo": report["traffic"]["matrix"],
            "ordering": report["traffic"]["ordering"],
        },
        "orchestrator": report["orchestrator"],
    }
    path = os.path.join(REPO_ROOT, "BENCH_scenarios.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=lambda o: o.item())
        f.write("\n")
    return path


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seeds", type=int, default=2000, help="Monte-Carlo trials")
    ap.add_argument("--dry-run", action="store_true", help="tiny counts, no asserts")
    args = ap.parse_args(argv)
    use_compile_cache()

    n_seeds = 64 if args.dry_run else max(args.seeds, 1000)
    micro = measure_micro("placentia", n_nodes=4)

    # detector tapes draw per-slot rngs in Python: enough seeds for stable
    # precision/recall estimates, far fewer than the jitted trajectory MC
    n_det = 16 if args.dry_run else max(min(args.seeds, 200), 100)

    # the matrix replays one tape batch per family under every workload's
    # cost table: modest seed counts give stable means at a fraction of
    # the trajectory section's program count
    n_wl = 16 if args.dry_run else max(min(args.seeds, 256), 64)

    # profiling re-lowers the replay program from scratch: modest seed
    # counts keep the AOT split readable without re-paying the MC budget
    n_prof = 64 if args.dry_run else max(min(args.seeds, 1024), 256)

    # the SLO matrix folds each trial's request tape in Python after the
    # batched replay: fleet-size tapes keep the per-seed fold cheap, so
    # modest counts give stable p99 means across the full matrix
    n_traffic = 16 if args.dry_run else max(min(args.seeds, 64), 32)

    report = {
        "paper_exactness": check_paper_exactness(micro),
        "campaigns": run_campaigns(micro),
        "montecarlo": run_montecarlo(micro, n_seeds, assert_speedup=not args.dry_run),
        "trajectories": run_trajectories(micro, n_seeds, assert_speedup=not args.dry_run),
        "detectors": run_detectors(n_det, assert_bounds=not args.dry_run),
        "workloads": run_workloads(n_wl, assert_ordering=not args.dry_run),
        "traffic": run_traffic(n_traffic, assert_ordering=not args.dry_run),
        "orchestrator": run_orchestrator(assert_tolerance=not args.dry_run),
        "profiling": run_profiling(micro, n_prof, dry_run=args.dry_run),
        "observability": run_observability(micro, n_seeds=n_wl),
    }

    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "scenarios.json")
    with open(path, "w") as f:
        # .item() unboxes stray numpy scalars (np.float64 totals, np.bool_)
        json.dump(report, f, indent=2, default=lambda o: o.item())
    record_path = write_bench_record(report, dry_run=args.dry_run)

    print(path)
    print(record_path)
    print(f"paper_exactness: {'PASS' if report['paper_exactness']['all_exact'] else 'FAIL'}")
    for name, per in report["campaigns"].items():
        core = per["core"]
        ck = per["central_single"]
        fmt = lambda d: d["total"] if d["survived"] else f"LOST@{fmt_hms(d['failed_at_s'])}"
        print(
            f"  {name:20s} core={fmt(core):14s} central_single={fmt(ck):14s} "
            f"events={core['n_events']} migrations={core['n_migrations']}"
        )
    for strat, mc in report["montecarlo"]["strategies"].items():
        print(
            f"  MC[{strat}] mean={mc['mean']} p95={mc['p95']} "
            f"speedup={mc['speedup']}x (loop {mc['python_loop_s']}s vs vec {mc['vectorised_s']}s)"
        )
    traj = report["trajectories"]
    for name, per in traj["families"].items():
        ck = per["central_single"]
        tails = (
            f"p5={ck['p5']} p50={ck['p50']} p95={ck['p95']}"
            if ck["survival_rate"]
            else f"survival={ck['survival_rate']}"
        )
        print(f"  TRAJ[{name:20s}] central_single {tails}")
    sp = traj["speedup"]
    print(
        f"  TRAJ speedup on {sp['family']}: {sp['speedup']}x "
        f"(engine loop {sp['engine_loop_s']}s vs batched {sp['batched_s']}s), "
        f"engine_match={traj['engine_match']['exact']}"
    )
    fl = traj["fleet"]
    print(
        f"  FLEET speedup on {fl['family']} ({fl['n_nodes']} nodes): "
        f"{fl['speedup']}x (engine {fl['engine_s_per_seed']}s/seed vs batched "
        f"{fl['batched_ms_per_seed']}ms/seed, need >= {fl['min_required']}x), "
        f"engine_match={fl['engine_match']}"
    )
    for det_name, per in report["detectors"]["detectors"].items():
        if det_name == "ewma_straggler":
            continue  # flags stragglers, claims no failures
        for fam in ("rack_outage", "mc_stress"):
            r = per[fam]
            print(
                f"  DET[{det_name:8s}] {fam:20s} coverage={r['coverage']:.3f} "
                f"precision={r['precision']:.3f} recall={r['recall']:.3f} "
                f"lead={r['median_lead_s']}s"
            )
    wl_rep = report["workloads"]
    for wl_name, rec in wl_rep["workloads"].items():
        for fam, per in rec["families"].items():
            cells = " ".join(
                f"{s}={per[s]['overhead_pct']}%" for s in WORKLOAD_STRATEGIES
            )
            print(f"  WL[{wl_name:13s}] {fam:18s} {cells}")
    if wl_rep["inversions"]:
        for inv in wl_rep["inversions"]:
            print(
                f"  WL ordering inverts on {inv['workload']}/{inv['family']}: "
                f"checkpoint {inv['checkpoint_pct']}% <= "
                f"multi-agent {inv['max_multi_agent_pct']}%"
            )
    else:
        print("  WL ordering (checkpointing >> multi-agent) holds on every workload")
    tr = report["traffic"]
    for strat, per in tr["matrix"].items():
        cells = " ".join(
            f"{asc}:p99={per[asc]['p99_s']}s/drop={per[asc]['dropped_mean']:.0f}"
            for asc in per
        )
        print(f"  SLO[{strat:14s}] {cells}")
    print(
        f"  SLO ordering on {tr['family']} ({tr['n_nodes']} shards): "
        f"makespan={tr['ordering']['by_makespan']} vs "
        f"p99={tr['ordering']['by_p99_static']} "
        f"(differs={tr['ordering']['differs']})"
    )
    orc = report["orchestrator"]
    for strat, r in orc["strategies"].items():
        print(
            f"  ORCH[{strat:14s}] live={r['live_total_s']}s "
            f"predicted={r['predicted_total_s']}s rel_err={r['rel_err']} "
            f"(band {orc['tolerance']})"
        )
    inj_cells = " ".join(
        f"{inj}:rel_err={r['rel_err']}" for inj, r in orc["injectors"].items()
    )
    print(f"  ORCH[injector axis ] {inj_cells}")
    for strat, p in report["profiling"]["replay"].items():
        print(
            f"  PROF[{strat:14s}] backend={p['backend']} devices={p['n_devices']} "
            f"compile={p['lower_s'] + p['compile_s']:.3f}s "
            f"execute={p['execute_s']:.5f}s seeds/s={p['seeds_per_s']:.0f} "
            f"(compile/execute={p['compile_over_execute']}x)"
        )
    mem = report["profiling"]["fleet_memory"]
    if mem.get("peak_drop_bytes") is not None:
        print(
            f"  PROF[fleet memory] donate peak={mem['donate']['peak_bytes']}B "
            f"vs no-donate {mem['no_donate']['peak_bytes']}B "
            f"(drop={mem['peak_drop_bytes']}B, aliased={mem['donate']['alias_bytes']}B)"
        )
    cache = report["profiling"]["program_cache"]
    print(
        f"  PROF[program cache] programs={cache['programs']} "
        f"hits={cache['hits']} misses={cache['misses']}"
    )
    for wl_name, surf in report["profiling"]["kernels"].items():
        pairs = " ".join(
            f"n={n}:{m}s" for n, m in zip(surf["n_shards"], surf["step_time_s"])
        )
        print(f"  PROF[{wl_name:13s}] {surf['kernel']} ({surf['backend']}) {pairs}")
    obs = report["observability"]
    print(
        f"  OBS[{obs['family']}] trace={obs['trace']['n_events']} events -> "
        f"{obs['trace']['path']}, parity={obs['trace_parity']}, "
        f"frame_sums_to_total={obs['metric_frame']['sums_to_billed_total']}"
    )
    if not (obs["trace_parity"] and obs["metric_frame"]["sums_to_billed_total"]):
        return 1
    if not report["paper_exactness"]["all_exact"]:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
