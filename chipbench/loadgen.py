"""The general request generator: one traffic file's parameters, one seed.

A traffic file (``traffic/<mix>.json``) names the entry point each request
calls and everything the call is given besides the configuration::

    {"entry": "repro.orchestrator.plan:choose_strategy",
     "candidates": [...], "n_seeds": 200, "detector": "ewma_straggler",
     "seed_block": 16777216}

Requests come from one caller in a closed loop, as the daemon waits for
each plan, and each is answered in a fresh planning child
(:mod:`chipbench.child`). Request ``k`` of a run with seed ``s`` plans over
campaign seeds ``s * seed_block + (k + 1) * n_seeds`` onwards, so no
campaign repeats within a run or across runs of different seeds; block 0 of
the seed's range is the set-up request's, outside every request's.
"""
from __future__ import annotations

from typing import Dict


def validate(traffic: Dict) -> None:
    if traffic["seed_block"] < 2 * traffic["n_seeds"]:
        raise ValueError("seed_block must hold the set-up request and one request")


def warm_base(traffic: Dict, seed: int) -> int:
    """The set-up request's first campaign seed."""
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, not {seed}")
    return seed * traffic["seed_block"]


def request_base(traffic: Dict, seed: int, k: int) -> int:
    """Request ``k``'s first campaign seed."""
    offset = (k + 1) * traffic["n_seeds"]
    if offset + traffic["n_seeds"] > traffic["seed_block"]:
        raise ValueError(f"request {k} runs past the seed's block of campaign seeds")
    return warm_base(traffic, seed) + offset


def request(traffic: Dict, spec, base: int) -> Dict:
    """The keyword arguments of the request on campaign seeds from ``base``,
    as the daemon passes them: the candidates, the seeds, the detector and
    the configuration's workload."""
    return {"candidates": tuple(traffic["candidates"]), "n_seeds": traffic["n_seeds"],
            "seed": base, "detector": traffic["detector"], "workload": spec.workload}


def trials(traffic: Dict) -> int:
    """Campaign trials one request replays: seeds times candidates."""
    return traffic["n_seeds"] * len(traffic["candidates"])
