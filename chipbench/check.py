"""The comparison that decides ``correct``: the oracle's answers against the
plain reference (:mod:`chipbench.reference`).

An answer is ``(winner, scores)``, ``scores[candidate]`` holding
``survival_rate``, ``mean_s`` and ``p95_s``. Each compared number has its
limit in ``limits.json``; a run is correct when every number is within its
limit and at least one answer was compared.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Iterable, Tuple

LIMITS_FILE = Path(__file__).resolve().parent / "limits.json"


def limits() -> Dict[str, float]:
    return json.loads(LIMITS_FILE.read_text())["limits"]


def _rel(got: float, want: float) -> float:
    if math.isnan(want) or math.isnan(got):
        return 0.0 if math.isnan(want) and math.isnan(got) else math.inf
    if got == want:
        return 0.0
    return abs(got - want) / abs(want)


def compare(pairs: Iterable[Tuple[Tuple, Tuple]], failed: int) -> Dict[str, float]:
    """The compared numbers over ``(answer, reference answer)`` pairs.

    ``winner_mismatch`` counts answers whose winner is not one of the
    reference's winners: the candidates with the best survival whose mean
    makespan equals the lowest exactly (two candidates the reference bills
    alike, as ``core`` and ``hybrid`` on a job whose combiner never fails,
    may trade places on the program's rounding)."""
    out = {"failed_decisions": float(failed), "decisions_compared": 0.0,
           "survival_mismatch": 0.0, "winner_mismatch": 0.0,
           "mean_s_rel_err": 0.0, "p95_s_rel_err": 0.0}
    for (winner, scores), (ref_winner, ref) in pairs:
        out["decisions_compared"] += 1
        for name, want in ref.items():
            got = scores[name]
            out["survival_mismatch"] += got["survival_rate"] != want["survival_rate"]
            for key in ("mean_s", "p95_s"):
                out[f"{key}_rel_err"] = max(out[f"{key}_rel_err"], _rel(got[key], want[key]))
        best = ref[ref_winner]
        chosen = ref.get(winner)
        out["winner_mismatch"] += chosen is None or (
            chosen["survival_rate"], _rel(chosen["mean_s"], best["mean_s"])
        ) != (best["survival_rate"], 0.0)
    return out


def verdict(numbers: Dict[str, float]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``(correct, {name: {"value", "limit"}})``: every limited number within
    its limit, and at least one decision compared."""
    lim = limits()
    shown = {k: {"value": numbers[k], "limit": lim[k]} for k in lim}
    ok = numbers["decisions_compared"] >= 1 and all(v["value"] <= v["limit"] for v in shown.values())
    return ok, shown
