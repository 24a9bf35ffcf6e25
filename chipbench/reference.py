"""The plain reference of the planning oracle's answer: shared pieces and
their default composition.

A straightforward, one-trial-at-a-time restatement of what
``choose_strategy(spec, candidates, n_seeds=N, seed=base)`` must return for
the deployments the benchmark's configurations describe. It imports nothing
of the program under test: the failure streams are drawn here from the
configuration's own process list (the same numpy generator streams the
registered families document), each trial is folded through plain Python
state (health, blacklist, spare pool, occupancy, repairs), billed per
strategy, and reduced to the oracle's scores and winner.

Every configuration names its reference: ``"reference"`` in
``configs/<name>.json`` is a module under ``chipbench/`` (dotted, as
``tests.periodic_reference``) whose
``decide(spec, candidates, n_seeds, base_seed, reinstate_s, detector=...)``
``run.py`` calls for each answer of the window. This module is the default
(``"reference": "reference"``). A configuration that needs more brings a
module of its own that imports the pieces here and adds only what is new,
each passed to :func:`decide` as an argument:

- a failure process kind: a draw ``(spec, params, rng, seed, idx) ->
  [(t, node)]`` added to :data:`KINDS`, as
  ``stream=functools.partial(failure_stream, kinds={**KINDS, "weibull": draw})``;
- a repair distribution: ``repair=`` a function of the trial's repair
  generator that returns one repair's seconds;
- a workload's per-node state: ``state_bytes={**STATE_BYTES, name: bytes}``.

Every per-event cost is derived here from the published figures of the
paper's cluster (:func:`billing_costs`), save two: the agent's and the
core's reinstate times, which the system measures by wall clock once per
process. Those two the run hands over, from the process that answered.

``dtype`` sets the precision of every time, cost and reduction:
``np.float64`` is the reference, ``np.float32`` the control that has to be
caught by the comparison.

Supported here: the failure processes ``random``, ``rack``, ``burst``,
``flaky`` and ``degrade``; repairs constant, ``("lognormal", mu, sigma)``
(every repair distribution the program offers) or none; placement
``nearest-spare`` with no co-hosting; the ``ewma_straggler`` detector (it
claims no failure, so every failure is handled blind, and it flags
stragglers); the strategies ``central_single``, ``agent``, ``core`` and
``hybrid``; the ``analytic`` workload's state. A configuration outside that
set, and not brought in by its own module, raises ``NotImplementedError``
or ``KeyError``.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

#: background probing, seconds per hour of campaign, by probing mechanism
PROBE_S_PER_HOUR = {"agent": 25.0, "core": 5.0}
#: strategy -> (kind, mechanism billed per event, probing mechanism)
STRATEGIES = {
    "central_single": ("checkpoint", None, None),
    "agent": ("proactive", "agent", "agent"),
    "core": ("proactive", "core", "core"),
    "hybrid": ("proactive", "rules", "core"),
}
#: paper-measured growth of checkpoint reinstate / overhead with the period
RST_GROWTH = {1.0: 1.0, 2.0: 1.108, 4.0: 1.164}
OVH_GROWTH = {1.0: 1.0, 2.0: 1.272, 4.0: 1.470}
#: Rules 1-3: dependency count and sub-job payload thresholds
Z_THRESHOLD = 10
PAYLOAD_THRESHOLD_BYTES = (2 ** 24) * 1024
#: the sub-job payload every host carries: 1024 float32 partials
PAYLOAD_BYTES = 1024 * 4
#: the paper's cluster (Placentia), whose figures price every campaign the
#: oracle plans: message latency, process spawn, NIC, checkpoint-server
#: write and restore bandwidths (B/s), relative node speed
PLACENTIA = {"msg_latency_s": 8e-6, "proc_spawn_s": 0.10, "node_bw": 1.8e9,
             "ckpt_server_bw": 3.32e6, "ckpt_restore_bw": 2.045e6, "node_speed": 1.0}
#: seconds of health-log mining and staging per proactive migration
LOG_MINING_S = {"agent": 312.6, "core": 266.6}
#: each node's data (S_d) by workload: the genome job's 512 MB input
STATE_BYTES = {"analytic": 512 * 2 ** 20}


# ----------------------------------------------------------- the streams ---
def _racks(spec: Dict) -> Dict[int, int]:
    racks = spec.get("racks")
    if racks is not None:
        return {int(k): int(v) for k, v in racks.items()}
    return {i: i % 2 for i in range(spec["n_nodes"])}


def _random(spec: Dict, p: Dict, rng, seed: int, idx: int) -> List[Tuple]:
    """The paper's random pattern: per hourly window, ``per_window``
    failures at uniform instants on uniform nodes, from a generator of its
    own (the first ``random`` process on the trial's seed, so the paper's
    tables hold; repeats on seeds derived from it)."""
    rng = np.random.default_rng(p.get("seed", seed + 1_000_003 * idx))
    horizon, n_nodes = spec["horizon_s"], spec["n_nodes"]
    period = p.get("period_s", spec["period_s"])
    per_window = p.get("per_window", 1)
    out = []
    for w in range(int(math.ceil(horizon / period))):
        base = w * period
        for _ in range(per_window):
            t = base + rng.uniform(0.0, period)
            if t >= horizon:
                continue
            node = int(rng.integers(0, n_nodes))
            rng.random()  # whether the failure is predictable: no detector here reads it
            out.append((float(t), node))
    return sorted(out, key=lambda e: e[0])


def _burst(spec: Dict, p: Dict, rng, seed: int, idx: int) -> List[Tuple]:
    n_nodes = spec["n_nodes"]
    t = float(p.get("t", spec["period_s"] / 2))
    k = int(p.get("k", min(3, n_nodes)))
    out = []
    for j, n in enumerate(rng.choice(n_nodes, size=min(k, n_nodes), replace=False)):
        rng.random()
        out.append((t + 1e-3 * j, int(n)))
    return out


def _rack(spec: Dict, p: Dict, rng, seed: int, idx: int) -> List[Tuple]:
    racks = _racks(spec)
    rack = p.get("rack")
    if rack is None:
        rack = int(rng.choice(sorted(set(racks.values()))))
    t0 = float(p.get("t", spec["period_s"] / 2))
    spread = float(p.get("spread_s", 60.0))
    out = []
    for n in [n for n, r in racks.items() if r == rack and n < spec["n_nodes"]]:
        t = t0 + float(rng.uniform(0.0, spread))
        rng.random()
        out.append((t, int(n)))
    return out


def _flaky(spec: Dict, p: Dict, rng, seed: int, idx: int) -> List[Tuple]:
    node = int(p.get("node", rng.integers(0, spec["n_nodes"])))
    every = float(p.get("every_s", spec["period_s"] / 2))
    t = float(p.get("first_t", every))
    out = []
    while t < spec["horizon_s"]:
        rng.random()
        out.append((t, node))
        t += every
    return out


def _degrade(spec: Dict, p: Dict, rng, seed: int, idx: int) -> List[Tuple]:
    return []  # no failure: a slow node, billed by slowdown_s


#: process kind -> draw ``(spec, params, rng, seed, idx) -> [(t, node)]``:
#: ``rng`` is the process's generator, ``default_rng((seed, i))`` for the
#: ``i``-th process of the list; ``seed`` the trial's; ``idx`` counts the
#: earlier processes of the same kind
KINDS: Dict[str, Callable] = {
    "random": _random, "burst": _burst, "rack": _rack, "flaky": _flaky, "degrade": _degrade,
}


def failure_stream(spec: Dict, seed: int, kinds: Dict[str, Callable] = KINDS) -> List[Tuple[float, int]]:
    """``(t, node)`` of every failure of one trial, in time order (ties keep
    the process order), each process drawn by its kind's entry in ``kinds``.
    Each failure's predictability is drawn, to keep the generators' order,
    and dropped: no supported detector reads it."""
    out: List[Tuple] = []
    occurrence: Dict[str, int] = {}
    for i, proc in enumerate(spec["processes"]):
        kind = proc["kind"]
        if kind not in kinds:
            raise NotImplementedError(f"failure process {kind!r}")
        idx = occurrence.get(kind, 0)
        occurrence[kind] = idx + 1
        out += kinds[kind](spec, proc.get("params", {}), np.random.default_rng((seed, i)), seed, idx)
    out = [e for e in out if e[0] < spec["horizon_s"]]
    return sorted(out, key=lambda e: e[0])


def spec_repair(spec: Dict) -> Optional[Callable]:
    """``repair(rng) -> seconds`` of the spec's ``repair_s``, or None where
    failed hosts never return: the constant, or ``("lognormal", mu, sigma)``,
    one draw of numpy's ``Generator.lognormal(mu, sigma)`` (the spec's
    documented distribution: the log of a repair's seconds is normal with
    mean ``mu`` and deviation ``sigma``)."""
    r = spec.get("repair_s")
    if r is None:
        return None
    if isinstance(r, (list, tuple)):
        kind, mu, sigma = r
        if kind != "lognormal":
            raise NotImplementedError(f"repair distribution {kind!r}")
        return lambda rng: float(rng.lognormal(float(mu), float(sigma)))
    return lambda rng: float(r)


# --------------------------------------------------------- one campaign ---
def campaign(spec: Dict, seed: int, stream: Callable = failure_stream,
             repair: Optional[Callable] = None) -> Dict:
    """Fold one trial's failures, ``stream(spec, seed)``, through the
    cluster's control state.

    A failed host returns after ``repair(rng)`` seconds (default: the
    spec's ``repair_s``, :func:`spec_repair`), unless the spec's
    ``repair_s`` is None or the host has struck out. ``rng`` is the trial's
    repair generator, ``default_rng((seed, 0x5EED))``, and ``repair`` is
    called once for each repair the fold schedules, in the fold's order:
    the order in which the program consumes its pre-drawn repairs.

    Placement is strategy-independent (every supported strategy moves the
    failed host's sub-job to the first free healthy spare, else a free
    healthy ring neighbour, else the first free healthy host), so one fold
    serves every candidate. Returns the handled failures in order as
    ``(t, z)``, ``z`` the dependency count of the sub-job that moved, and
    the trial's survival and counters."""
    if spec.get("placement") not in (None, "nearest-spare"):
        raise NotImplementedError(f"placement {spec['placement']!r}")
    n_nodes, H = spec["n_nodes"], spec["n_nodes"] + spec["n_spares"]
    horizon = spec["horizon_s"]
    never_repaired = spec.get("repair_s") is None
    if repair is None:
        repair = spec_repair(spec)
    repair_rng = np.random.default_rng((seed, 0x5EED))

    healthy = [True] * H
    black = [False] * H
    # sub-job held by each host (-1: free); sub-job n_nodes-1 is the
    # combiner every search sub-job feeds (the genome job's star)
    work = list(range(n_nodes)) + [-1] * spec["n_spares"]
    spares = list(range(n_nodes, H))
    strikes = [0] * H
    pending: Dict[int, float] = {}  # host -> when its repair completes
    out = dict(survived=True, failed_at_s=None, n_events=0, n_handled=0,
               n_blacklisted=0, n_reprovisioned=0, handled=[])

    def degree(job: int) -> int:
        if n_nodes <= 1:
            return 0
        return n_nodes - 1 if job == n_nodes - 1 else 1

    def usable(h: int) -> bool:
        return healthy[h] and not black[h] and work[h] < 0

    def provision(h: int) -> None:
        healthy[h] = True
        work[h] = -1
        if h not in spares:
            spares.append(h)
        out["n_reprovisioned"] += 1

    def pick(failing: int):
        for s in spares:
            if usable(s):
                return s
        for nb in ((failing - 1) % H, (failing + 1) % H):
            if usable(nb):
                return nb
        for h in range(H):
            if h != failing and usable(h):
                return h
        return None

    for t, host in stream(spec, seed):
        for h, tr in sorted(pending.items(), key=lambda kv: (kv[1], kv[0])):
            if tr < t:
                del pending[h]
                provision(h)
        out["n_events"] += 1
        if not healthy[host]:
            continue
        strikes[host] += 1
        permanent = never_repaired or strikes[host] >= spec["max_strikes"]
        if work[host] >= 0:
            target = pick(host)
            if target is None:
                out["survived"] = False
                out["failed_at_s"] = t
                break
            out["handled"].append((t, degree(work[host])))
            work[target], work[host] = work[host], -1
            if target in spares:
                spares.remove(target)
        healthy[host] = False
        if host in spares:
            spares.remove(host)
        if permanent:
            black[host] = True
            out["n_blacklisted"] += 1
        else:
            pending[host] = t + repair(repair_rng)
    out["n_handled"] = len(out["handled"])
    if out["survived"]:
        for h, tr in sorted(pending.items(), key=lambda kv: (kv[1], kv[0])):
            if tr < horizon:
                provision(h)
    return out


# -------------------------------------------------------------- billing ---
def billing_costs(spec: Dict, reinstate_s: Dict[str, float],
                  state_bytes: Dict[str, int] = STATE_BYTES) -> Dict:
    """The per-failure costs of the paper's cost model on its cluster:
    the central checkpoint server's write (overhead) and restore
    (reinstate) of every other node's data, and each proactive mechanism's
    log mining, staging and respawn. ``reinstate_s`` holds the measured
    ``agent`` and ``core`` reinstate seconds; ``state_bytes`` each
    workload's data per node."""
    p = PLACENTIA
    n = spec["n_nodes"]
    s_d = state_bytes[spec["workload"]]
    speed = max(p["node_speed"], 0.1)
    staging = s_d / p["node_bw"]
    total = s_d * max(n - 1, 1)
    coord = 2 * p["msg_latency_s"] * n
    respawn = p["proc_spawn_s"] * n + 60.0 / max(p["node_speed"], 0.2)
    return {
        "ckpt_overhead_s": {"central_single": total / p["ckpt_server_bw"] + coord},
        "ckpt_reinstate_s": {"central_single": total / p["ckpt_restore_bw"] + respawn},
        "agent_overhead_s": LOG_MINING_S["agent"] / speed + staging + p["proc_spawn_s"],
        "core_overhead_s": LOG_MINING_S["core"] / speed + staging + p["proc_spawn_s"],
        "agent_reinstate_s": float(reinstate_s["agent"]),
        "core_reinstate_s": float(reinstate_s["core"]),
    }


def _growth(period_h: float) -> float:
    return 1.0 + 0.27 * math.log2(max(period_h, 1.0))


def per_event_costs(strategy: str, costs: Dict, period_h: float) -> Dict:
    """``{mechanism: (reinstate_s, overhead_s)}`` of one blind failure."""
    kind, mech, _ = STRATEGIES[strategy]
    if kind == "checkpoint":
        rst_g = RST_GROWTH.get(period_h, 1.0 + 0.108 * math.log2(max(period_h, 1.0)))
        ovh_g = OVH_GROWTH.get(period_h, _growth(period_h))
        return {None: (costs["ckpt_reinstate_s"][strategy] * rst_g,
                       costs["ckpt_overhead_s"][strategy] * ovh_g)}
    g = _growth(period_h)
    return {m: (costs[f"{m}_reinstate_s"], costs[f"{m}_overhead_s"] * g)
            for m in ("agent", "core")}


def mechanism(strategy: str, z: int):
    _, mech, _ = STRATEGIES[strategy]
    if mech != "rules":
        return mech
    if z <= Z_THRESHOLD:
        return "core"
    return "agent" if PAYLOAD_THRESHOLD_BYTES >= PAYLOAD_BYTES else "core"


def slowdown_s(spec: Dict, mitigate: bool, after_s: float = 120.0,
               factor: float = 0.5, dt_s: float = 30.0, units: int = 8) -> float:
    """Extra synchronous-step seconds of the ``degrade`` windows: the
    slowest shard paces each step; a straggler-flagging detector moves
    half of the slow shard's work to the others after ``after_s``."""
    n = spec["n_nodes"]
    base = [units] * n
    extra = 0.0
    for proc in spec["processes"]:
        if proc["kind"] != "degrade":
            continue
        p = proc.get("params", {})
        t0 = float(p.get("t", 0.0))
        t1 = min(t0 + float(p.get("duration_s", spec["horizon_s"] - t0)), spec["horizon_s"])
        node, f = int(p.get("node", 0)), float(p.get("factor", 0.5))
        ramp = float(p.get("ramp_s", 0.0))
        if t1 <= t0:
            continue
        moved = list(base)
        take = max(int(moved[node] * factor), 1)
        moved[node] -= take
        others = [i for i in range(n) if i != node]
        for j, h in enumerate(others):
            moved[h] += take // len(others) + (1 if j < take % len(others) else 0)
        t = t0
        while t < t1:
            step = min(dt_s, t1 - t)
            tm = t + 0.5 * step
            frac = 1.0 if ramp <= 0 else min(1.0, (tm - t0) / ramp)
            speeds = np.ones(n)
            speeds[node] = 1.0 - (1.0 - f) * frac
            split = moved if (mitigate and tm >= t0 + after_s) else base
            w = np.asarray(split, float)
            mult = float(np.max(w / np.maximum(speeds, 1e-6))) / max(np.mean(w), 1e-9)
            extra += (mult - 1.0) * step
            t += step
    return float(extra)


def bill(spec: Dict, trial: Dict, strategy: str, costs: Dict, slow: float,
         dtype=np.float64):
    """``(total_s, survived)`` of one trial under ``strategy``; every blind
    failure loses the work since its window's start and pays the
    mechanism's reinstate and overhead. ``total_s`` is NaN for a lost
    campaign."""
    f = dtype
    period = f(spec["period_s"])
    table = {m: (f(r), f(o)) for m, (r, o) in
             per_event_costs(strategy, costs, spec["period_s"] / 3600.0).items()}
    lost = reinstate = overhead = f(0.0)
    for t, z in trial["handled"]:
        t = f(t)
        lost = f(lost + f(t - f(np.floor(t / period) * period)))
        r, o = table[mechanism(strategy, z)]
        reinstate = f(reinstate + r)
        overhead = f(overhead + o)
    if not trial["survived"]:
        return f(np.nan), False
    probe_mech = STRATEGIES[strategy][2]
    probe = f(PROBE_S_PER_HOUR[probe_mech] if probe_mech else 0.0)
    probe_s = f(probe * f(f(spec["horizon_s"]) / f(3600.0)))
    total = f(f(spec["horizon_s"]) + lost)
    for part in (reinstate, overhead, probe_s, f(slow)):
        total = f(total + part)
    return total, True


# ----------------------------------------------------------- the answer ---
def decide(spec: Dict, candidates: Sequence[str], n_seeds: int, base_seed: int,
           reinstate_s: Dict[str, float], detector: str = "ewma_straggler",
           dtype=np.float64, stream: Callable = failure_stream,
           repair: Optional[Callable] = None, state_bytes: Dict[str, int] = STATE_BYTES):
    """The oracle's ``(winner, scores)`` for seeds ``base_seed ..
    base_seed + n_seeds - 1``: survival rate, then the mean and 95th
    percentile of the surviving trials' makespans; the winner has the best
    survival and, among those, the lowest mean (first in candidate order on
    a tie). ``reinstate_s`` holds the two measured reinstate seconds;
    ``stream``, ``repair`` and ``state_bytes`` are as :func:`campaign` and
    :func:`billing_costs` take them."""
    if detector != "ewma_straggler":
        raise NotImplementedError(f"detector {detector!r}")
    costs = billing_costs(spec, reinstate_s, state_bytes)
    slow = slowdown_s(spec, mitigate=True)
    trials = [campaign(spec, base_seed + s, stream, repair) for s in range(n_seeds)]
    scores = {}
    for name in candidates:
        billed = [bill(spec, tr, name, costs, slow, dtype) for tr in trials]
        totals = np.asarray([b[0] for b in billed], dtype)
        ok = np.asarray([b[1] for b in billed], bool)
        alive = totals[ok]
        scores[name] = {
            "survival_rate": float(np.mean(ok)),
            "mean_s": float(np.mean(alive)) if alive.size else float("nan"),
            "p95_s": float(np.percentile(alive, 95)) if alive.size else float("nan"),
        }
    best = max(s["survival_rate"] for s in scores.values())
    finalists = [n for n in candidates if scores[n]["survival_rate"] >= best]
    winner = min(finalists, key=lambda n: scores[n]["mean_s"])
    return winner, scores
