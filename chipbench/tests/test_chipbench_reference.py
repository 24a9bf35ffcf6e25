"""The plain reference against the engine, the comparison against its
control, and the compared numbers on hand-made answers."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import check, reference  # noqa: E402
from chipbench.run import load_cell  # noqa: E402

CANDIDATES = ("central_single", "agent", "core", "hybrid")


#: the program's repair times as field data fits them (``ScenarioSpec.repair_s``)
LOGNORMAL = ["lognormal", 7.0, 1.0]


def _config(name):
    """A configuration's spec: the benchmark's own file, or a family of
    the program's registry that the reference also covers (the fleet:
    racks, bursts, flaky and degrading nodes, repairs); ``<family>.lognormal``
    is the family with its repairs drawn from :data:`LOGNORMAL`."""
    if name == "table2_random":
        return load_cell("table2_random.child200")["configuration"]["spec"]
    from repro.scenarios import registry

    family, _, repairs = name.partition(".")
    cfg = registry.get(family).to_dict()
    if repairs == "lognormal":
        cfg["repair_s"] = list(LOGNORMAL)
    return cfg


def _setup(name):
    """``(cfg, spec, reinstate_s, costs, micro)``: the reinstate seconds
    the program measured, and the costs the reference bills with them."""
    from repro.scenarios.spec import ScenarioSpec
    from repro.workloads import resolve

    cfg = _config(name)
    spec = ScenarioSpec.from_dict(cfg)
    m = resolve(spec.workload, spec).micro("placentia", n_nodes=spec.n_nodes)
    reinstate_s = {"agent": m.agent_reinstate_s, "core": m.core_reinstate_s}
    return cfg, spec, reinstate_s, reference.billing_costs(cfg, reinstate_s), m


def _engine_parity(cfg, spec, costs, seeds, stream=reference.failure_stream):
    """Survival, counters and makespan of every trial, under every
    candidate: the reference's fold of ``stream`` against the engine's."""
    from repro.scenarios.engine import CampaignEngine

    slow = reference.slowdown_s(cfg, mitigate=True)
    for seed in seeds:
        trial = reference.campaign(cfg, seed, stream)
        for strategy in CANDIDATES:
            e = CampaignEngine(spec, strategy, seed=seed, detector="ewma_straggler").run()
            total, survived = reference.bill(cfg, trial, strategy, costs, slow)
            assert survived == e.survived
            assert [trial[k] for k in ("n_events", "n_handled", "n_blacklisted", "n_reprovisioned")] == [
                e.n_events, e.n_handled, e.n_blacklisted, e.n_reprovisioned]
            if survived:
                assert total == e.total_s


@pytest.mark.parametrize("name", ["table2_random", "fleet_stress"])
def test_billing_costs_equal_the_programs(name):
    """The costs the reference derives from the paper's cluster equal the
    program's calibrated record, bit for bit, given its two measured
    reinstate times."""
    _, _, _, costs, m = _setup(name)
    assert costs["ckpt_overhead_s"]["central_single"] == m.ckpt_overhead_s["central_single"]
    assert costs["ckpt_reinstate_s"]["central_single"] == m.ckpt_reinstate_s["central_single"]
    assert costs["agent_overhead_s"] == m.agent_overhead_s
    assert costs["core_overhead_s"] == m.core_overhead_s


@pytest.mark.parametrize(
    "name, seeds",
    [("table2_random", range(3_000_000_000, 3_000_000_040)),
     ("fleet_stress", [4_100_000_007]),
     ("mc_stress.lognormal", [0, 17, 2**31 + 5, 4_100_000_007]),
     ("fleet_stress.lognormal", [17, 2**31 + 5])],
)
def test_reference_equals_engine_trial_for_trial(name, seeds):
    """Survival, counters and makespan of every trial, under every
    candidate, equal the engine's (the repo's own reference semantics),
    with constant, lognormal or no repairs."""
    cfg, spec, _, costs, _ = _setup(name)
    _engine_parity(cfg, spec, costs, seeds)


@pytest.mark.parametrize(
    "name", ["table2_random", "fleet_stress", "mc_stress.lognormal", "fleet_stress.lognormal"])
def test_reference_streams_equal_the_program_tapes(name):
    """The failures, and the repairs the fold draws in its order, equal the
    program tape's events and the head of its pre-drawn repairs."""
    from repro.scenarios.trajectory import compile_tape

    cfg, spec, _, _, _ = _setup(name)
    for seed in (0, 17, 2**31 + 5):
        tape = compile_tape(spec, seed)
        stream = reference.failure_stream(cfg, seed)
        assert [e[0] for e in stream] == tape.times.tolist()
        assert [e[1] for e in stream] == tape.victim.tolist()
        drawn = []
        repair = reference.spec_repair(cfg)

        def recorded(rng):
            drawn.append(repair(rng))
            return drawn[-1]

        reference.campaign(cfg, seed, repair=recorded)
        assert (len(drawn) > 0) == (cfg["repair_s"] is not None)
        assert drawn == tape.repair_draws[:len(drawn)].tolist()


def test_composed_reference_adds_a_failure_kind():
    """A configuration brings a kind the default reference lacks with a
    module of its own: ``tests/periodic_reference.py`` adds the paper's
    periodic pattern through ``failure_stream``'s ``kinds`` and equals the
    engine on ``table1_periodic``, trial by trial and in its answer."""
    from repro.scenarios.engine import CampaignEngine

    from chipbench.run import load_reference

    cfg, spec, reinstate_s, costs, _ = _setup("table1_periodic")
    with pytest.raises(NotImplementedError, match="periodic"):
        reference.failure_stream(cfg, 0)
    composed = load_reference({"name": "table1_periodic", "reference": "tests.periodic_reference"})
    seeds = [0, 17, 2**31 + 5, 4_100_000_007]
    _engine_parity(cfg, spec, costs, seeds, composed.stream)
    winner, scores = composed.decide(cfg, CANDIDATES, len(seeds), seeds[0], reinstate_s)
    seeds = range(seeds[0], seeds[0] + len(seeds))
    for strategy in CANDIDATES:
        totals = [CampaignEngine(spec, strategy, seed=s, detector="ewma_straggler").run().total_s
                  for s in seeds]
        assert scores[strategy]["survival_rate"] == 1.0
        assert scores[strategy]["mean_s"] == float(np.mean(np.asarray(totals)))
    assert winner == min(CANDIDATES, key=lambda n: scores[n]["mean_s"])


#: ``reference.decide`` on ``table2_random`` at 200 seeds from each base,
#: with these reinstate seconds, as the reference before configurations
#: named their own gave it: winner, and per candidate survival, mean and
#: 95th percentile as ``float.hex``
PARENT_REINSTATE_S = {"agent": 0.4375, "core": 0.3125}
PARENT_ANSWERS = {
    0: ("core", {
        "central_single": ("0x1.c28f5c28f5c29p-3", "0x1.790406d7a4367p+14", "0x1.9ea6325cc83dcp+14"),
        "agent": ("0x1.c28f5c28f5c29p-3", "0x1.5b1aa1dac652cp+14", "0x1.80bccd5fea5a1p+14"),
        "core": ("0x1.c28f5c28f5c29p-3", "0x1.5819a1dac652cp+14", "0x1.7dbbcd5fea5a1p+14"),
        "hybrid": ("0x1.c28f5c28f5c29p-3", "0x1.5819a1dac652cp+14", "0x1.7dbbcd5fea5a1p+14"),
    }),
    3_000_000_000: ("core", {
        "central_single": ("0x1.051eb851eb852p-2", "0x1.7cbcc165cd5b1p+14", "0x1.9b6c850bb4c9ep+14"),
        "agent": ("0x1.051eb851eb852p-2", "0x1.5f2355dedd903p+14", "0x1.7d83200ed6e62p+14"),
        "core": ("0x1.051eb851eb852p-2", "0x1.5c25f3fcfbae4p+14", "0x1.7a82200ed6e62p+14"),
        "hybrid": ("0x1.051eb851eb852p-2", "0x1.5c25f3fcfbae4p+14", "0x1.7a82200ed6e62p+14"),
    }),
}


@pytest.mark.parametrize("base", sorted(PARENT_ANSWERS))
def test_decide_is_the_parents_answer(base):
    """The composed reference answers on ``table2_random`` bit for bit as
    the one it replaced."""
    winner, scores = reference.decide(_config("table2_random"), CANDIDATES, 200, base,
                                      PARENT_REINSTATE_S)
    want_winner, want = PARENT_ANSWERS[base]
    assert winner == want_winner
    assert {n: tuple(float(s[k]).hex() for k in ("survival_rate", "mean_s", "p95_s"))
            for n, s in scores.items()} == want


def test_control_fails_the_comparison():
    """The reference computed in float32 in the program's place: some
    compared number exceeds its limit on every decision tried."""
    cfg, _, reinstate_s, _, _ = _setup("table2_random")
    for base in (0, 200, 1_234_567_800):
        want = reference.decide(cfg, CANDIDATES, 200, base, reinstate_s)
        got = reference.decide(cfg, CANDIDATES, 200, base, reinstate_s, dtype=np.float32)
        correct, shown = check.verdict(check.compare([(got, want)], failed=0))
        assert not correct, shown
        assert shown["mean_s_rel_err"]["value"] > shown["mean_s_rel_err"]["limit"]


def _answer(**means):
    scores = {n: {"survival_rate": 0.5, "mean_s": m, "p95_s": 2 * m} for n, m in means.items()}
    return min(scores, key=lambda n: scores[n]["mean_s"]), scores


A_WINS = _answer(a=100.0, b=200.0)
TIE = _answer(a=100.0, b=100.0)


@pytest.mark.parametrize(
    "got, want, expect",
    [
        (A_WINS, A_WINS, {"winner_mismatch": 0, "mean_s_rel_err": 0.0}),
        (("b", A_WINS[1]), A_WINS, {"winner_mismatch": 1}),
        (("b", TIE[1]), TIE, {"winner_mismatch": 0}),
        (_answer(a=101.0, b=200.0), A_WINS, {"mean_s_rel_err": 0.01, "p95_s_rel_err": 0.01}),
    ],
)
def test_compare_numbers(got, want, expect):
    numbers = check.compare([(got, want)], failed=0)
    assert numbers["decisions_compared"] == 1
    for k, v in expect.items():
        assert numbers[k] == pytest.approx(v)


def test_compare_survival_and_winner_without_best_survival():
    ref_winner, ref = _answer(a=100.0, b=200.0)
    ref["b"]["survival_rate"] = 0.25
    got = {n: dict(s) for n, s in ref.items()}
    got["a"]["survival_rate"] = 0.25
    numbers = check.compare([(("b", got), (ref_winner, ref))], failed=1)
    assert numbers["survival_mismatch"] == 1
    assert numbers["winner_mismatch"] == 1
    assert numbers["failed_decisions"] == 1
    assert not check.verdict(numbers)[0]
