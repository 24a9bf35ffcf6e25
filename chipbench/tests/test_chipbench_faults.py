"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
(the set-up request, a short window of requests, each answered in its own
planning child, the comparison with the plain reference) on the CPU, with
one fault planted in the child where the oracle's answer is produced
(``planted.py``). The cell runs on one chip, so the exchange between chips
is not among its faults."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run  # noqa: E402

CELL = "table2_random.child200"


def _run(seed, entry=None):
    cell = run.load_cell(CELL)
    if entry is not None:
        cell["mix"] = dict(cell["mix"], entry=f"chipbench.tests.planted:{entry}")
    return run.run_cell(cell, seed, 0.3, False, look_for_chip=False)


def test_sound_run_is_correct():
    result, checks = _run(seed=3_000_000_007)
    assert result["correct"], checks
    assert result["attempted"] == 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    bench = run.load_cell(CELL)["bench"]
    cell_metrics = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) == cell_metrics
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("entry", ["state_unchanged", "half_batch", "answer_altered"])
def test_fault_is_caught(entry):
    result, checks = _run(seed=11, entry=entry)
    assert not result["correct"], checks
    assert result["failed"] == 0
