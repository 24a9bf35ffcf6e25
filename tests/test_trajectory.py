"""Batched trajectory engine: the vmapped replay kernel must reproduce
``CampaignEngine`` trial-for-trial on identical seeds (same tapes, same
arithmetic under x64) across every scenario family — cascade chains,
rack outages, flaky repeat offenders, spare exhaustion, checkpoint storms,
network partitions, heavy-tailed repairs, Rules 1-3 hybrid billing — and
``mc_trajectories`` must agree statistically with the closed-form
``mc_totals`` where both models apply."""
import numpy as np
import pytest

from repro.core.sim import measure_micro
from repro.scenarios import mc_totals, mc_trajectories, registry
from repro.scenarios.engine import CampaignEngine
from repro.scenarios.montecarlo import params_from_scenario
from repro.scenarios.spec import FailureProcessSpec, ScenarioSpec
from repro.scenarios.trajectory import (
    _compiled_replayer,
    _quiet_donation,
    compile_batch,
    compile_tape,
    replay_batch,
    replay_cache_stats,
    replay_program,
)
from repro.utils.backend import x64


_MICRO = {}


def micro_for(n_nodes: int):
    """Module-wide micro cache: identical MicroCosts give identical cost
    tables, so the jitted replay programs are shared across tests."""
    if n_nodes not in _MICRO:
        _MICRO[n_nodes] = measure_micro("placentia", n_nodes=n_nodes)
    return _MICRO[n_nodes]


@pytest.fixture(scope="module")
def micro():
    return micro_for(4)


# one strategy per family, used by BOTH the differential sweep and the
# mc_trajectories coverage test so they replay through the same compiled
# programs; together the ten pairs exercise every billing mode (window,
# ckpt-invalidation, proactive, rules, cold) and every process kind
FAMILY_STRATEGY = [
    ("table1_periodic", "central_single"),
    ("table1_random", "core"),
    ("table2_random", "central_single"),
    ("rack_outage", "core"),
    ("cascade_spare", "core"),  # dynamically re-targeted cascade chain
    ("flaky_node", "central_single"),  # repairs + blacklist after strikes
    ("spare_exhaustion", "core"),  # burst; every trial stranded
    ("checkpoint_storm", "central_single"),  # in-flight ckpt invalidation
    ("partition_split", "core"),  # cut + quorum placement + heal
    ("multi_window_storm", "cold_restart"),  # attempt-clock accounting
    ("mc_stress", "central_single"),  # 24 nodes, 12 h composition
]
N_DIFF_SEEDS = 10


def assert_trials_match(spec, strategy, n_seeds, micro, placement=None):
    """Every kernel trial equals the engine run for the same seed; returns
    the kernel's outputs."""
    batch = compile_batch(spec, n_seeds)
    out = replay_batch(spec, batch, strategy, micro=micro, placement=placement)
    for k in range(n_seeds):
        r = CampaignEngine(
            spec, strategy, micro=micro, seed=k, placement=placement
        ).run()
        ctx = (spec.name, strategy, k)
        assert bool(out["survived"][k]) == r.survived, ctx
        for f in (
            "n_events",
            "n_handled",
            "n_migrations",
            "n_blacklisted",
            "n_reprovisioned",
        ):
            assert int(out[f][k]) == getattr(r, f), (*ctx, f)
        for f in ("lost_s", "reinstate_s", "overhead_s", "probe_s"):
            want = getattr(r, f)
            assert out[f][k] == pytest.approx(want, rel=1e-9, abs=1e-6), (*ctx, f)
        if r.survived:
            assert out["total_s"][k] == pytest.approx(r.total_s, rel=1e-9)
            assert np.isnan(out["failed_at_s"][k])
        else:
            assert np.isnan(out["total_s"][k])
            assert out["failed_at_s"][k] == pytest.approx(r.failed_at_s, rel=1e-12)
    return out


# ------------------------------------------------- differential: families ---
@pytest.mark.parametrize("family,strategy", FAMILY_STRATEGY)
def test_kernel_matches_engine_per_family(family, strategy):
    spec = registry.get(family)
    assert_trials_match(spec, strategy, N_DIFF_SEEDS, micro_for(spec.n_nodes))


@pytest.mark.slow
def test_kernel_matches_engine_exhaustive():
    """Full sweep: every registered family under every mode of billing."""
    for family in registry.names():
        spec = registry.get(family)
        m = micro_for(spec.n_nodes)
        # engine trials dominate at fleet scale (~seconds per seed on
        # 1k+ nodes) — thin the seed sweep there, keep it wide elsewhere
        n_seeds = 25 if spec.n_nodes <= 64 else 4
        for strategy in ("central_single", "decentral", "agent", "core", "hybrid", "cold_restart"):
            assert_trials_match(spec, strategy, n_seeds, m)


# ------------------------------------------- differential: special physics ---
def test_kernel_bills_hybrid_rules_mechanism(micro):
    """Z > 10 on the star hub makes Rules 1-3 pick AGENT migration; the
    kernel must track dependency degrees through remaps and bill agent
    costs for exactly those events."""
    spec = ScenarioSpec(
        name="hub_failure_traj",
        n_nodes=12,
        n_spares=2,
        horizon_s=3600.0,
        processes=[
            FailureProcessSpec(
                "cascade", {"node": 11, "t": 600.0, "depth": 1, "delay_s": 300.0, "predictable": True}
            )
        ],
        repair_s=900.0,
    )
    m = micro_for(12)
    assert_trials_match(spec, "hybrid", 4, m)
    # and the billed reinstate really is the agent pair (predict + agent)
    out = replay_batch(spec, compile_batch(spec, 1), "hybrid", micro=m)
    r = CampaignEngine(spec, "hybrid", micro=m, seed=0).run()
    assert any(e.get("outcome") == "migrated" for e in r.events)
    assert out["reinstate_s"][0] == pytest.approx(r.reinstate_s, rel=1e-9)
    assert r.reinstate_s > 2 * m.predict_s  # two events, both agent-routed


def test_kernel_matches_engine_lognormal_repairs(micro):
    """Heavy-tailed repair delays: the compiler pre-samples the engine's
    exact rng sequence, consumed in schedule order."""
    spec = ScenarioSpec(
        name="lognormal_traj",
        n_nodes=4,
        n_spares=2,
        horizon_s=3 * 3600.0,
        processes=[
            FailureProcessSpec("flaky", {"node": 1, "every_s": 1500.0}),
            FailureProcessSpec("random", {}),
        ],
        repair_s=("lognormal", 6.5, 0.8),
        max_strikes=3,
    )
    assert_trials_match(spec, "core", 12, micro)


def test_kernel_matches_engine_minority_partition(micro):
    """A failure on the minority side of a cut finds no quorum: the
    campaign strands — identically in engine and kernel."""
    spec = ScenarioSpec(
        name="minority_cut",
        n_nodes=6,
        n_spares=2,
        horizon_s=2 * 3600.0,
        processes=[
            FailureProcessSpec(
                "partition",
                {"t": 1000.0, "heal_t": 5000.0,
                 "components": {0: 0, 1: 0, 2: 0, 3: 1, 4: 1, 5: 1, 6: 0, 7: 0}},
            ),
            FailureProcessSpec("cascade", {"node": 4, "t": 2000.0, "depth": 0}),
        ],
        repair_s=900.0,
        placement="partition-aware",
    )
    m = micro_for(6)
    res = CampaignEngine(spec, "core", micro=m, seed=0).run()
    assert not res.survived and res.failed_at_s == pytest.approx(2000.0)
    assert_trials_match(spec, "core", 4, m)


def test_replay_rejects_unknown_placement(micro):
    spec = registry.get("rack_outage")
    with pytest.raises(ValueError, match="placement"):
        replay_batch(spec, compile_batch(spec, 2), "core", micro=micro, placement="voodoo")


# ----------------------------------------------------- compiler invariants ---
def test_tape_cascade_slots_are_parent_linked():
    tape = compile_tape(registry.get("cascade_spare"), 0)
    roots = tape.parent < 0
    assert roots.sum() == 1 and (~roots).sum() == 2  # depth 2 -> 2 children
    kids = np.where(~roots)[0]
    assert (tape.victim[kids] == -1).all()  # victims resolved at replay
    assert tape.times[kids[0]] == pytest.approx(1200.0 + 120.0)
    assert tape.parent[kids[1]] == kids[0]  # chain, not fan-out


def test_tape_partition_resolution():
    tape = compile_tape(registry.get("partition_split"), 0)
    assert len(tape.partition_changes) == 2
    # first failure (t=2400) is inside the cut, second (t=5400) after heal
    assert tape.part_active.tolist() == [True, False]
    assert tape.part_comp[0, 3] == 1 and tape.part_comp[0, 6] == 0
    assert (tape.part_comp[1] == -1).all()


def test_batch_padding_masks_variable_event_counts():
    spec = registry.get("table2_random")
    batch = compile_batch(spec, 32)
    assert batch.n_slots % 8 == 0
    counts = batch.valid.sum(axis=1)
    assert counts.max() <= batch.n_slots
    assert np.isinf(batch.times[~batch.valid]).all()


def test_spec_roundtrip_keeps_placement_and_partition():
    spec = registry.get("partition_split")
    again = ScenarioSpec.from_dict(spec.to_dict())
    assert again.placement == "partition-aware"
    assert again.partition_timeline() == spec.partition_timeline()


# ----------------------------------------------------------- monte-carlo ----
def test_mc_trajectories_covers_every_family():
    """Every registered family — including cascade, rack, flaky, burst and
    partition — Monte-Carlos through ONE jitted vmapped program (reusing
    the differential sweep's programs: same strategy, same seed count)."""
    strat_for = dict(FAMILY_STRATEGY)
    for name in registry.names():
        spec = registry.get(name)
        mc = mc_trajectories(
            spec,
            strat_for.get(name, "central_single"),
            n_seeds=N_DIFF_SEEDS,
            micro=micro_for(spec.n_nodes),
        )
        assert mc["n_seeds"] == N_DIFF_SEEDS
        assert 0.0 <= mc["survival_rate"] <= 1.0
        if mc["survival_rate"] > 0.0:
            assert mc["p5_s"] <= mc["p50_s"] <= mc["p95_s"]
            assert mc["mean_s"] > spec.horizon_s
        else:
            assert name == "spare_exhaustion"
            assert mc["mean_failed_at_s"] == pytest.approx(2700.0, abs=1.0)


def test_mc_trajectories_agrees_with_closed_form(micro):
    """Statistical: on the closed-form-able paper scenario the trajectory
    MC and the window-model MC sample the same uniform loss distribution
    — means agree to Monte-Carlo error."""
    spec = registry.get("table1_random")
    mc_t = mc_trajectories(spec, "central_single", n_seeds=2000, micro=micro)
    params = params_from_scenario(spec, "central_single", micro)
    mc_c = mc_totals(params, n_seeds=2000, seed=7)
    assert mc_t["survival_rate"] == 1.0
    assert mc_t["mean_s"] == pytest.approx(mc_c["mean_s"], rel=0.02)
    assert mc_t["std_s"] == pytest.approx(mc_c["std_s"], rel=0.10)


def test_mc_trajectories_tails_separate_proactive_from_reactive(micro):
    """The Treaster point: distributions, not just means. Proactive p95 is
    far below reactive p95 on the same correlated-failure campaign."""
    spec = registry.get("multi_window_storm")
    m = micro_for(6)
    batch = compile_batch(spec, 256)
    ck = mc_trajectories(spec, "central_single", micro=m, batch=batch)
    core = mc_trajectories(spec, "core", micro=m, batch=batch)
    assert core["p95_s"] < ck["p50_s"]
    assert core["counters"]["n_migrations"] > 0


# ------------------------------------------------ engine satellite fixes ----
def test_lost_campaign_stops_probing_at_failure(micro):
    """Bug fix: probes accrue only until failed_at_s, not the full horizon."""
    spec = registry.get("spare_exhaustion")
    res = CampaignEngine(spec, "core", micro=micro).run()
    assert not res.survived
    strat_rate = 5.0  # core probing s/hour
    assert res.probe_s == pytest.approx(strat_rate * res.failed_at_s / 3600.0)
    assert res.probe_s < strat_rate * spec.horizon_s / 3600.0


def test_stranded_event_record_uses_float_time(micro):
    spec = registry.get("spare_exhaustion")
    res = CampaignEngine(spec, "core", micro=micro).run()
    assert res.events and res.events[-1]["outcome"] == "stranded"
    assert isinstance(res.events[-1]["t"], float)


# ------------------------------------------------------- cost-table layer ----
def test_cost_tables_mirror_scalar_costs(micro):
    from repro.strategies import CostContext, get as get_strategy

    ctx = CostContext(micro=micro, period_h=2.0)
    ck = get_strategy("central_single")
    t = ck.cost_table(ctx)
    c = ck.costs(ctx)
    assert t.mode == "window" and t.ckpt_invalidation
    assert t.reinstate_s == c.reinstate_s and t.overhead_s == c.overhead_s

    hy = get_strategy("hybrid")
    th = hy.cost_table(ctx)
    assert th.mode == "proactive" and th.mechanism == "rules"
    assert th.agent_reinstate_s == micro.agent_reinstate_s
    assert th.core_reinstate_s == micro.core_reinstate_s
    assert th.agent_overhead_s > th.core_overhead_s  # log-mining asymmetry
    assert th.probe_s_per_hour == 5.0  # probes on the core's cheap path

    cold = get_strategy("cold_restart")
    assert cold.cost_table(ctx).mode == "cold"


def test_default_cost_table_for_custom_strategy(micro):
    """A strategy that only implements costs() still gets a replayable
    window-mode table (the documented default reduction)."""
    from repro.strategies import CostContext, FaultToleranceStrategy, StrategyCosts

    class Custom(FaultToleranceStrategy):
        name = "custom_traj_test"

        def costs(self, ctx):
            return StrategyCosts(predict_s=0.0, reinstate_s=11.0, overhead_s=7.0)

        def on_failure(self, event, target):  # pragma: no cover - unused
            raise NotImplementedError

    t = Custom().cost_table(CostContext(micro=micro, period_h=1.0))
    assert t.mode == "window" and not t.ckpt_invalidation
    assert t.reinstate_s == 11.0 and t.overhead_s == 7.0


def test_replay_program_module_is_named_jit_one_seed(micro):
    """The chip benchmark finds the replay program's device runs by its
    module's name (``chipbench/metrics/replay_device_ms.py``), so a rename
    fails here instead of leaving that metric silently empty."""
    spec = registry.get("table2_random")
    fn, args = replay_program(spec, compile_batch(spec, 8), "central_single", micro=micro)
    with x64(), _quiet_donation():
        text = fn.lower(*args).as_text()
    assert text.split(None, 2)[:2] == ["module", "@jit_one_seed"]


# ------------------------------------------- one program per scenario shape ----
#: every billing branch of the replay kernel: window with checkpoint
#: invalidation, window without it (a custom reactive strategy's default
#: table), proactive agent / core / rules, cold
_ONE_PROGRAM_STRATEGIES = (
    "central_single", "custom_traj_window", "agent", "core", "hybrid", "cold_restart",
)


def test_one_program_serves_every_cost_table():
    """The cost table's branch flags travel as runtime values: six
    strategies on one scenario shape build one program (one miss, five
    hits), each still matches the engine trial for trial, and replaying
    them again in reverse order gives bitwise the first pass's outputs —
    no branch of an earlier table is left over in a later one."""
    from repro.strategies import FaultToleranceStrategy, StrategyCosts
    from repro.strategies.base import FailureOutcome
    from repro.strategies.registry import register, unregister

    @register("custom_traj_window")
    class CustomWindow(FaultToleranceStrategy):
        """Restores onto the target, billed by the default cost table."""

        def costs(self, ctx):
            return StrategyCosts(predict_s=0.0, reinstate_s=11.0, overhead_s=7.0)

        def on_failure(self, event, target):
            rt = self.rt
            shard = rt.hosts[event.node].shard
            rt.release(event.node)
            rt.occupy(target, shard, f"{self.name}:{event.node}")
            rt.graph.remap(event.node, target)
            t = float(event.t)
            return FailureOutcome(
                new_host=int(target), lost_s=t - self._window_start(t),
                reinstate_s=11.0, overhead_s=7.0, outcome="restored",
            )

    # failures inside checkpoint cuts (invalidation) and a predictable
    # failure of the star's hub, Z = 11 > 10 (the hybrid picks the agent)
    spec = ScenarioSpec(
        name="one_program_traj",
        n_nodes=12,
        n_spares=2,
        horizon_s=3 * 3600.0,
        processes=[
            FailureProcessSpec("ckpt_window", {"offset_s": 5.0}),
            FailureProcessSpec(
                "cascade", {"node": 11, "t": 600.0, "depth": 1, "delay_s": 300.0, "predictable": True}
            ),
        ],
        repair_s=900.0,
    )
    m = micro_for(spec.n_nodes)
    try:
        _compiled_replayer.cache_clear()
        first = {s: assert_trials_match(spec, s, N_DIFF_SEEDS, m) for s in _ONE_PROGRAM_STRATEGIES}
        assert replay_cache_stats() == {"hits": 5, "misses": 1, "programs": 1}
        # the six tables bill six different ways
        totals = {out["total_s"].tobytes() for out in first.values()}
        assert len(totals) == len(_ONE_PROGRAM_STRATEGIES)

        batch = compile_batch(spec, N_DIFF_SEEDS)
        for s in reversed(_ONE_PROGRAM_STRATEGIES):
            again = replay_batch(spec, batch, s, micro=m)
            assert again.keys() == first[s].keys()
            for key, a in first[s].items():
                assert np.array_equal(a, again[key], equal_nan=True), (s, key)
        assert replay_cache_stats()["misses"] == 1
    finally:
        unregister("custom_traj_window")


@pytest.mark.parametrize(
    "first, second",
    [
        (dict(record_slots=True), dict(record_slots=False)),
        (dict(placement="partition-aware"), dict(placement="nearest-spare")),
    ],
    ids=["record_slots", "partition_aware"],
)
def test_program_shapes_stay_apart(micro, first, second):
    """What changes the program's structure — recording per-slot
    decisions, partition-scoped placement over an open cut — still builds
    a program of its own; a second strategy on either shape hits it."""
    spec = registry.get("partition_split")
    batch = compile_batch(spec, 4)
    m = micro_for(spec.n_nodes)
    _compiled_replayer.cache_clear()
    replay_batch(spec, batch, "core", micro=m, **first)
    replay_batch(spec, batch, "core", micro=m, **second)
    assert replay_cache_stats()["misses"] == 2
    replay_batch(spec, batch, "central_single", micro=m, **first)
    replay_batch(spec, batch, "hybrid", micro=m, **second)
    assert replay_cache_stats() == {"hits": 2, "misses": 2, "programs": 2}


def test_replay_rejects_unknown_billing_mode(micro):
    """A cost table whose mode the kernel cannot bill is refused, not
    billed under another mode's clock."""
    from repro.strategies import FaultToleranceStrategy, StrategyCostTable

    class Odd(FaultToleranceStrategy):
        name = "odd_mode_traj_test"

        def costs(self, ctx):  # pragma: no cover - unused
            raise NotImplementedError

        def cost_table(self, ctx):
            return StrategyCostTable(mode="teleport")

        def on_failure(self, event, target):  # pragma: no cover - unused
            raise NotImplementedError

    spec = registry.get("table2_random")
    with pytest.raises(ValueError, match="teleport"):
        replay_batch(spec, compile_batch(spec, 2), Odd(), micro=micro)
