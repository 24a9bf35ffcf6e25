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


def _config(name):
    """A configuration's spec: the benchmark's own file, or a family of
    the program's registry that the reference also covers (the fleet:
    racks, bursts, flaky and degrading nodes, repairs)."""
    if name == "table2_random":
        return load_cell("table2_random.child200")["configuration"]["spec"]
    from repro.scenarios import registry

    return registry.get(name).to_dict()


def _setup(name):
    from repro.scenarios.spec import ScenarioSpec
    from repro.workloads import resolve

    cfg = _config(name)
    spec = ScenarioSpec.from_dict(cfg)
    m = resolve(spec.workload, spec).micro("placentia", n_nodes=spec.n_nodes)
    costs = reference.billing_costs(cfg, {"agent": m.agent_reinstate_s, "core": m.core_reinstate_s})
    return cfg, spec, costs, m


@pytest.mark.parametrize("name", ["table2_random", "fleet_stress"])
def test_billing_costs_equal_the_programs(name):
    """The costs the reference derives from the paper's cluster equal the
    program's calibrated record, bit for bit, given its two measured
    reinstate times."""
    _, _, costs, m = _setup(name)
    assert costs["ckpt_overhead_s"]["central_single"] == m.ckpt_overhead_s["central_single"]
    assert costs["ckpt_reinstate_s"]["central_single"] == m.ckpt_reinstate_s["central_single"]
    assert costs["agent_overhead_s"] == m.agent_overhead_s
    assert costs["core_overhead_s"] == m.core_overhead_s


@pytest.mark.parametrize(
    "name, seeds",
    [("table2_random", range(3_000_000_000, 3_000_000_040)),
     ("fleet_stress", [4_100_000_007])],
)
def test_reference_equals_engine_trial_for_trial(name, seeds):
    """Survival, counters and makespan of every trial, under every
    candidate, equal the engine's (the repo's own reference semantics)."""
    from repro.scenarios.engine import CampaignEngine

    cfg, spec, costs, _ = _setup(name)
    slow = reference.slowdown_s(cfg, mitigate=True)
    for seed in seeds:
        trial = reference.campaign(cfg, seed)
        for strategy in CANDIDATES:
            e = CampaignEngine(spec, strategy, seed=seed, detector="ewma_straggler").run()
            total, survived = reference.bill(cfg, trial, strategy, costs, slow)
            assert survived == e.survived
            assert [trial[k] for k in ("n_events", "n_handled", "n_blacklisted", "n_reprovisioned")] == [
                e.n_events, e.n_handled, e.n_blacklisted, e.n_reprovisioned]
            if survived:
                assert total == e.total_s


@pytest.mark.parametrize("name", ["table2_random", "fleet_stress"])
def test_reference_streams_equal_the_program_tapes(name):
    from repro.scenarios.trajectory import compile_tape

    cfg, spec, _, _ = _setup(name)
    for seed in (0, 17, 2**31 + 5):
        tape = compile_tape(spec, seed)
        stream = reference.failure_stream(cfg, seed)
        assert [e[0] for e in stream] == tape.times.tolist()
        assert [e[1] for e in stream] == tape.victim.tolist()


def test_control_fails_the_comparison():
    """The reference computed in float32 in the program's place: some
    compared number exceeds its limit on every decision tried."""
    cfg, _, costs, _ = _setup("table2_random")
    for base in (0, 200, 1_234_567_800):
        want = reference.decide(cfg, CANDIDATES, 200, base, costs)
        got = reference.decide(cfg, CANDIDATES, 200, base, costs, dtype=np.float32)
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
