"""The planning oracle: the simulator, consulted by the daemon.

Before launch the daemon asks this module three questions:

1. **Which strategy?** :func:`choose_strategy` runs
   :func:`~repro.scenarios.montecarlo.mc_trajectories` for every
   candidate over one shared compiled tape batch, keeps the candidates
   with the best survival rate, and picks the lowest mean makespan.
2. **What should the run cost?** :func:`predicted_makespan_s` bills the
   exact ``(spec, seed)`` trial the injector will replay through the
   Python :class:`~repro.scenarios.engine.CampaignEngine` — same seed,
   same detector, same workload — so live and predicted makespans are
   the same campaign priced two ways.
3. **How fast is a step really?** :func:`measure_step_wall_s` times the
   workload's real step program in-process (and attaches
   ``Workload.measured_step_surface()`` /
   :func:`~repro.obs.profile.time_pallas_kernel` numbers when the
   workload has a kernel hot path), calibrating the billed
   ``step_time_s`` cost tables against the machine the daemon runs on.

:func:`make_live_plan` folds the answers into a :class:`LivePlan`: the
run executes in *scaled time* (``time_scale`` simulated seconds per wall
second), each of ``n_steps`` paced steps representing ``step_sim_s`` of
the horizon, with the strategy's probe cost folded into the pace so a
failure-free live run lands exactly on the engine's
``horizon + probe`` bill.

:class:`DriftMonitor` watches the live run for the spec lying —
observed failure rate or measured step latency diverging beyond a
ratio band — and tells the daemon to re-plan;
:func:`scale_failure_rate` rewrites the spec to the observed intensity
for the re-plan.

The daemon asks none of this in its own process: a chip belongs to one
process, and the daemon's workers need it. :func:`plan_in_child` runs a
question in a fresh process that exits before the answer is returned, and
reports which platform and how many chips that process saw.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.scenarios.spec import FailureProcessSpec, ScenarioSpec

#: strategies the oracle considers when the caller doesn't narrow the field
DEFAULT_CANDIDATES = ("central_single", "agent", "core", "hybrid")

#: steps per checkpoint period when the caller doesn't set a resolution
DEFAULT_STEPS_PER_PERIOD = 2
#: the cost profile live campaigns are priced and run under: the plan's
#: billing measure and the daemon's runtime both use it (the engine's and
#: the Monte-Carlo's default)
PROFILE = "placentia"


# ----------------------------------------------------------- isolation ---
def _answer_in_child(fn, args, kwargs, jax_platforms):
    import jax

    from repro.utils.backend import use_compile_cache

    if jax_platforms:
        jax.config.update("jax_platforms", jax_platforms)
    use_compile_cache()
    out = fn(*args, **kwargs)
    devices = jax.devices()
    return out, {"platform": devices[0].platform, "count": len(devices)}


def plan_in_child(fn, /, *args, jax_platforms: Optional[str] = None, **kwargs):
    """``fn(*args, **kwargs)`` answered in a fresh spawned process.

    Returns ``(answer, device)``, ``device`` being ``{"platform", "count"}``
    as JAX reported them in the child. ``jax_platforms`` (e.g. ``"cpu"``)
    restricts the child's JAX to those platforms, for when the chips are
    already held. The child has exited, and so let go of the chip, before
    this returns; the calling process initialises no JAX backend."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=1, mp_context=ctx) as pool:
        return pool.submit(_answer_in_child, fn, args, kwargs, jax_platforms).result()


# -------------------------------------------------------------- strategy ---
def choose_strategy(
    spec: ScenarioSpec,
    candidates: Tuple[str, ...] = DEFAULT_CANDIDATES,
    *,
    n_seeds: int = 200,
    seed: int = 0,
    detector: str = "ewma_straggler",
    workload=None,
) -> Tuple[str, Dict[str, Dict]]:
    """Monte-Carlo every candidate over one shared tape batch; return
    ``(winner, scores)``. Survival dominates cost: only candidates tied
    for the best survival rate compete on mean makespan."""
    from repro.scenarios.montecarlo import mc_trajectories
    from repro.scenarios.trajectory import compile_batch

    batch = compile_batch(spec, n_seeds, seed)
    scores: Dict[str, Dict] = {}
    for name in candidates:
        r = mc_trajectories(
            spec, name, n_seeds=n_seeds, seed=seed, batch=batch,
            detector=detector, workload=workload,
        )
        scores[name] = {
            "mean_s": r["mean_s"],
            "p95_s": r["p95_s"],
            "survival_rate": r["survival_rate"],
        }
    best_survival = max(s["survival_rate"] for s in scores.values())
    finalists = [n for n, s in scores.items() if s["survival_rate"] >= best_survival]
    winner = min(finalists, key=lambda n: scores[n]["mean_s"])
    return winner, scores


def predicted_makespan_s(
    spec: ScenarioSpec,
    strategy: str,
    *,
    seed: int = 0,
    detector: str = "ewma_straggler",
    workload=None,
) -> float:
    """Engine-billed makespan for the exact trial the injector replays."""
    from repro.scenarios.engine import CampaignEngine

    res = CampaignEngine(
        spec, strategy, seed=seed, detector=detector, workload=workload
    ).run()
    return float(res.total_s)


# ----------------------------------------------------------- calibration ---
def measure_step_wall_s(
    workload: str,
    *,
    n_shards: int,
    n_steps: int,
    seed: int = 0,
    n_probe_steps: int = 2,
    clock=time.monotonic,
) -> Dict:
    """Time the workload's real step program in-process.

    Returns ``{"step_wall_s", "backend", "surface"}`` where ``surface``
    is the kernel step-time surface for workloads with a Pallas hot path
    (None otherwise — analytic/genome time their own jit here)."""
    from repro.orchestrator.worker import make_program

    prog = make_program(workload, seed, n_shards, max(n_steps, n_probe_steps + 1), 0)
    prog.step()  # warm the jit cache outside the timed window
    t0 = clock()
    for _ in range(n_probe_steps):
        prog.step()
    measured_s = (clock() - t0) / n_probe_steps

    surface = None
    backend = "python"
    try:
        from repro.workloads import registry as workload_registry

        surface = workload_registry.get(workload).measured_step_surface(
            n_shards=(n_shards,)
        )
        if surface is not None:
            backend = surface.get("backend", "unknown")
    except KeyError:
        pass  # live-only workload name with no registered cost model
    return {"step_wall_s": float(measured_s), "backend": backend, "surface": surface}


# ------------------------------------------------------------- the plan ---
@dataclass
class LivePlan:
    """Everything the daemon needs to run one live campaign."""

    spec: ScenarioSpec
    strategy: str
    seed: int
    detector: str
    workload: str
    time_scale: float  # simulated seconds per wall second
    n_steps: int  # per shard
    step_sim_s: float  # simulated seconds one step represents
    step_wall_s: float  # paced wall duration of one step (probe folded in)
    ckpt_every_steps: int
    predicted_total_s: float  # engine bill for this exact (spec, seed)
    # the workload's billing measure (MicroCosts) under PROFILE.
    # Calibrating it may run the job's own JAX step, so the planning
    # process takes it
    micro: object = None
    scores: Dict[str, Dict] = field(default_factory=dict)  # per-candidate MC
    calibration: Dict = field(default_factory=dict)  # measure_step_wall_s output
    # {"platform", "count"} the planning process saw (plan_in_child); empty
    # when the plan was made in-process
    device: Dict = field(default_factory=dict)

    def to_dict(self) -> Dict:
        return {
            "strategy": self.strategy,
            "seed": self.seed,
            "detector": self.detector,
            "workload": self.workload,
            "time_scale": self.time_scale,
            "n_steps": self.n_steps,
            "step_sim_s": self.step_sim_s,
            "step_wall_s": self.step_wall_s,
            "ckpt_every_steps": self.ckpt_every_steps,
            "predicted_total_s": self.predicted_total_s,
            "scores": self.scores,
            "calibration": {
                k: v for k, v in self.calibration.items() if k != "surface"
            },
            "device": self.device,
        }


def make_live_plan(
    spec: ScenarioSpec,
    *,
    time_scale: float,
    seed: Optional[int] = None,
    strategy: Optional[str] = None,
    candidates: Tuple[str, ...] = DEFAULT_CANDIDATES,
    detector: str = "ewma_straggler",
    workload: Optional[str] = None,
    n_seeds: int = 200,
    steps_per_period: int = DEFAULT_STEPS_PER_PERIOD,
    calibrate: bool = True,
) -> LivePlan:
    """Consult the oracle and lay out the scaled-time execution grid.

    ``n_steps * step_sim_s == horizon_s`` exactly, and a checkpoint lands
    on every simulated period boundary (``ckpt_every_steps`` steps), so
    lost-work granularity matches the engine's billing windows."""
    seed = spec.seed if seed is None else int(seed)
    workload = workload or spec.workload or "analytic"

    scores: Dict[str, Dict] = {}
    if strategy is None:
        strategy, scores = choose_strategy(
            spec, candidates, n_seeds=n_seeds, seed=seed,
            detector=detector, workload=workload,
        )

    n_periods = max(1, round(spec.horizon_s / spec.period_s))
    n_steps = n_periods * steps_per_period
    step_sim_s = spec.horizon_s / n_steps

    # fold the strategy's probe bill into the pace: every shard steps in
    # parallel, so per-step padding grows the max-completion time by
    # exactly the probe total — the engine's single probe line item
    from repro.strategies import registry as strategy_registry

    probe_sim_s = strategy_registry.get(strategy).tick_costs() * spec.horizon_s / 3600.0
    step_wall_s = (step_sim_s + probe_sim_s / n_steps) / time_scale

    calibration: Dict = {}
    if calibrate:
        calibration = measure_step_wall_s(
            workload, n_shards=spec.n_nodes, n_steps=n_steps, seed=seed
        )

    predicted = predicted_makespan_s(
        spec, strategy, seed=seed, detector=detector, workload=workload
    )
    from repro.workloads import resolve as resolve_workload

    micro = resolve_workload(workload, spec).micro(PROFILE, n_nodes=spec.n_nodes)
    return LivePlan(
        spec=spec,
        strategy=strategy,
        seed=seed,
        detector=detector,
        workload=workload,
        time_scale=float(time_scale),
        n_steps=int(n_steps),
        step_sim_s=float(step_sim_s),
        step_wall_s=float(step_wall_s),
        ckpt_every_steps=int(steps_per_period),
        predicted_total_s=float(predicted),
        micro=micro,
        scores=scores,
        calibration=calibration,
    )


# ------------------------------------------------------------------ drift ---
def scale_failure_rate(spec: ScenarioSpec, ratio: float) -> ScenarioSpec:
    """A copy of ``spec`` with its failure intensity scaled by ``ratio``
    (the observed/declared rate the drift monitor measured). Count-like
    process knobs (``per_window``, burst ``k``) scale and round; other
    processes are left alone."""
    d = spec.to_dict()
    for p in d["processes"]:
        params = p["params"]
        for knob in ("per_window", "k"):
            if knob in params:
                params[knob] = max(1, round(params[knob] * ratio))
    out = ScenarioSpec.from_dict(d)
    assert all(isinstance(p, FailureProcessSpec) for p in out.processes)
    return out


class DriftMonitor:
    """Watches a live run for the spec diverging from reality.

    Two drift signals, both ratio-banded:

    * **failure rate** — observed failures per simulated second vs the
      spec's declared expectation (needs ``min_failures`` observations
      before it will fire, so one unlucky event isn't "drift");
    * **step time** — EWMA of measured step latencies vs the calibrated
      pace (a machine slower than calibration skews every makespan).
    """

    def __init__(
        self,
        *,
        expected_failures: float,
        horizon_s: float,
        step_wall_s: float,
        rate_band: float = 1.8,
        step_band: float = 1.8,
        min_failures: int = 2,
        ewma_alpha: float = 0.3,
    ):
        self.expected_rate_per_s = max(expected_failures, 1e-9) / horizon_s
        self.step_wall_s = step_wall_s
        self.rate_band = rate_band
        self.step_band = step_band
        self.min_failures = min_failures
        self.ewma_alpha = ewma_alpha
        self.n_failures = 0
        self.step_ewma_s: Optional[float] = None

    def observe_failure(self) -> None:
        self.n_failures += 1

    def observe_step(self, step_latency_s: float) -> None:
        if self.step_ewma_s is None:
            self.step_ewma_s = step_latency_s
        else:
            a = self.ewma_alpha
            self.step_ewma_s = a * step_latency_s + (1 - a) * self.step_ewma_s

    def rate_ratio(self, t_sim_s: float) -> float:
        if t_sim_s <= 0:
            return 1.0
        return (self.n_failures / t_sim_s) / self.expected_rate_per_s

    def drifted(self, t_sim_s: float) -> Optional[Dict]:
        """None, or ``{"cause", "ratio"}`` when a signal leaves its band."""
        if self.n_failures >= self.min_failures:
            r = self.rate_ratio(t_sim_s)
            if r >= self.rate_band:
                return {"cause": "failure_rate", "ratio": float(r)}
        if self.step_ewma_s is not None and self.step_wall_s > 0:
            r = self.step_ewma_s / self.step_wall_s
            if r >= self.step_band:
                return {"cause": "step_time", "ratio": float(r)}
        return None
