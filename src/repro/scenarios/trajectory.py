"""Batched trajectory engine: compile campaign event streams to padded/
masked structure-of-arrays tapes, then replay thousands of trials in one
jitted ``jax.vmap`` program.

The paper's headline comparison (multi-agent ~10 % overhead vs ~90 % for
checkpointing) is a mean over thousands of stochastic trials, and the
fault-recovery literature (Treaster, cs/0501002) stresses that recovery-
cost *distributions* — tails, not just means — are what distinguish
reactive from proactive schemes. ``montecarlo.mc_totals`` vectorises only
the closed-form window model; the scenario families that actually
differentiate the approaches (cascade, rack, flaky, burst, partition) ran
one Python :class:`~repro.scenarios.engine.CampaignEngine` at a time.

This module splits scenario execution into two layers:

**Trajectory compiler** (:func:`compile_tape` / :func:`compile_batch`)
    resolves one ``(ScenarioSpec, seed)`` into a fixed-shape event tape:
    per-slot times, victim hosts, predictability / during-checkpoint
    flags, pre-sampled repair-delay draws (consumed in schedule order, so
    heavy-tailed lognormal repairs keep the engine's exact rng sequence),
    *parent pointers* for dynamically-retargeted cascade chains (a
    cascade's victim is the host the parent's sub-job migrated TO —
    unknowable statically, so the slot stores which earlier slot to ask),
    and the statically-resolved network-partition component map per slot.
    Everything the Python engine decides dynamically but *timelessly* is
    folded into arrays here; everything stateful is left to the kernel.

**Replay kernel** (:func:`replay_batch`)
    a pure jnp fold over the tape slots under ``jax.vmap`` + ``jit``:
    cluster control state — blacklist strikes, the spare-pool FIFO
    (entry-sequence numbers reproduce the engine's list order through
    removals and repair re-appends), occupancy, per-host repair clocks,
    dependency degrees for the hybrid's Rules 1-3 Z-negotiation, cold-
    restart attempt clocks — advances as small integer/float arrays in
    lockstep across all seeds. Per-event costs come from the strategy's
    vectorised :class:`~repro.strategies.base.StrategyCostTable`.

:class:`CampaignEngine` remains the single-trial reference semantics (it
consumes the same compiled tape, driving the real Agent/VirtualCore/
HybridUnit machinery), and the differential tests assert the kernel
matches it trial-for-trial on identical seeds. The kernel runs under
:func:`repro.utils.backend.x64` so its arithmetic is the engine's float64
arithmetic, not an approximation of it.

**Fleet-scale execution shape.** The kernel is built to hold its
per-seed cost at thousands of nodes: repair-order ranking switches at
trace time from the small-cluster O(H²) pairwise matrix to a stable
``argsort`` over the host axis (O(H log H); bit-identical — see
``_PAIRWISE_RANK_MAX_HOSTS``), the per-slot partition component map
collapses to a
width-1 placeholder whenever the family opens no partition cut (so the
tape stays O(events + nodes), not O(nodes × horizon)), the slot axis is
**tiled** — an outer ``lax.scan`` over fixed-size tiles wrapping the
inner per-slot scan, bit-identical across tile sizes because padding
slots are provable no-ops — and the seed axis is **sharded** across
devices with ``shard_map`` (``n_devices=``; force a multi-device CPU
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``). Cost-table
*values* travel as a traced ``float64[8]`` coefficient vector rather
than baked-in constants, so one compiled program serves every strategy
that shares a structural :class:`_TableStatic` shape —
:func:`replay_cache_stats` reports the resulting hit rate. Tape buffers
are donated to the jit program (``donate_argnums``) so fleet-size
record-mode replays reuse their input storage.
"""
from __future__ import annotations

import warnings
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.rules import SD_THRESHOLD_BYTES, Z_THRESHOLD
from repro.scenarios.spec import ScenarioSpec
from repro.strategies import registry as strategy_registry
from repro.strategies.base import CostContext, FaultToleranceStrategy, StrategyCostTable
from repro.utils.backend import x64
from repro.utils.tree import tree_bytes

__all__ = [
    "TrajectoryTape",
    "TapeBatch",
    "compile_tape",
    "compile_batch",
    "default_seed_devices",
    "replay_batch",
    "replay_cache_stats",
    "replay_program",
]


# ======================================================================
# Layer 1: the trajectory compiler
# ======================================================================
@dataclass
class TrajectoryTape:
    """One seed's campaign, resolved to fixed-shape slot arrays.

    Slots are time-ordered; cascade children carry ``parent >= 0`` and
    ``victim == -1`` (the replay — Python engine or jnp kernel — fills
    the victim in from the parent slot's migration target, and skips the
    slot entirely when the parent never migrated)."""

    spec_name: str
    seed: int
    n_hosts: int  # n_nodes + n_spares
    times: np.ndarray  # float64 [n]
    victim: np.ndarray  # int32   [n]  (-1: resolved from parent at replay)
    parent: np.ndarray  # int32   [n]  (-1: root event from the spec stream)
    predictable: np.ndarray  # bool [n]
    during_ckpt: np.ndarray  # bool [n]
    repair_draws: np.ndarray  # float64 [n], consumed in schedule order
    causes: List[str] = field(default_factory=list)
    # rack-correlated slots (cause == "rack"): detector verdict tapes use
    # this to apply correlated telemetry drift per event
    rack_corr: Optional[np.ndarray] = None  # bool [n]
    # static partition state per slot: component id per host (-1 unmapped)
    # and whether any cut is open at the slot's time. Families with no
    # partition timeline compact the host axis to width 1 (all -1) so a
    # tape never materialises an O(n_slots x H) array it will not use.
    part_active: Optional[np.ndarray] = None  # bool [n]
    part_comp: Optional[np.ndarray] = None  # int32 [n, H] ([n, 1] if no cuts)
    # engine-facing form of the same timeline: [(t, comp_map-or-None)]
    partition_changes: List[Tuple[float, Optional[Dict[int, int]]]] = field(
        default_factory=list
    )

    @property
    def n_slots(self) -> int:
        return int(self.times.shape[0])


def compile_tape(spec: ScenarioSpec, seed: Optional[int] = None) -> TrajectoryTape:
    """Resolve one ``(spec, seed)`` trial into a :class:`TrajectoryTape`.

    Strategy-independent: control flow (victims, targets, blacklisting,
    repairs) evolves identically under every strategy that uses the same
    placement policy, so one tape replays under any cost table."""
    base_seed = spec.seed if seed is None else seed
    evs = spec.events(base_seed)
    horizon_s = spec.horizon_s
    H = spec.n_nodes + spec.n_spares

    n0 = len(evs)
    times: List[float] = [e.t for e in evs]
    victim: List[int] = [e.node for e in evs]
    parent: List[int] = [-1] * n0
    pred: List[bool] = [e.predictable for e in evs]
    during: List[bool] = [e.during_checkpoint for e in evs]
    causes: List[str] = [e.cause for e in evs]
    # pre-allocate cascade chains: times are static (t + k*delay); only the
    # victim is dynamic. Children appended AFTER the originals so a stable
    # sort reproduces the engine heap's tie-break (pushed-later pops later).
    for i, ev in enumerate(evs):
        if not ev.cascade or int(ev.cascade.get("depth", 0)) <= 0:
            continue
        delay_s = float(ev.cascade.get("delay_s", 120.0))
        par, t = i, float(ev.t)
        for _ in range(int(ev.cascade["depth"])):
            t = t + delay_s
            if t >= horizon_s:
                break  # never processed, so it spawns no grandchildren
            j = len(times)
            times.append(t)
            victim.append(-1)
            parent.append(par)
            pred.append(bool(ev.predictable))
            during.append(False)
            causes.append("cascade")
            par = j

    n = len(times)
    t_arr = np.asarray(times, np.float64)
    v_arr = np.asarray(victim, np.int32)
    p_arr = np.asarray(parent, np.int32)
    pr_arr = np.asarray(pred, bool)
    du_arr = np.asarray(during, bool)
    if n > n0:  # cascade children were appended: merge-sort them in
        order = np.argsort(t_arr, kind="stable")
        inv = np.empty(n, np.int32)
        inv[order] = np.arange(n, dtype=np.int32)
        t_arr = t_arr[order]
        v_arr = v_arr[order]
        p_arr = np.where(p_arr[order] < 0, -1, inv[p_arr[order]]).astype(np.int32)
        pr_arr = pr_arr[order]
        du_arr = du_arr[order]
        causes = [causes[k] for k in order]

    # repair-delay draws, pre-sampled in the exact sequence the engine's
    # repair rng would emit (one draw per *scheduled* repair, consumed in
    # event-processing order — at most one per slot)
    if spec.repair_s is None:
        draws = np.zeros(n, np.float64)
    elif isinstance(spec.repair_s, (tuple, list)):
        rng = np.random.default_rng((base_seed, 0x5EED))
        draws = np.asarray([spec.sample_repair(rng) for _ in range(n)], np.float64)
    else:
        draws = np.full(n, float(spec.repair_s), np.float64)

    # statically resolve the partition component map active at each slot
    changes = spec.partition_timeline()
    part_active = np.zeros(n, bool)
    part_comp = np.full((n, H if changes else 1), -1, np.int32)
    if changes:
        cur: Optional[Dict[int, int]] = None
        ci = 0
        for k in range(n):
            while ci < len(changes) and changes[ci][0] <= t_arr[k]:
                cur = changes[ci][1]
                ci += 1
            if cur is not None:
                part_active[k] = True
                for h, c in cur.items():
                    if 0 <= h < H:
                        part_comp[k, h] = c

    return TrajectoryTape(
        spec_name=spec.name,
        seed=base_seed,
        n_hosts=H,
        times=t_arr,
        victim=v_arr,
        parent=p_arr,
        predictable=pr_arr,
        during_ckpt=du_arr,
        repair_draws=draws,
        causes=causes,
        rack_corr=np.asarray([c == "rack" for c in causes], bool),
        part_active=part_active,
        part_comp=part_comp,
        partition_changes=changes,
    )


@dataclass
class TapeBatch:
    """``n_seeds`` tapes, padded to a common slot count and stacked into
    structure-of-arrays form (the ``valid`` mask marks real slots)."""

    spec_name: str
    seeds: np.ndarray  # int64 [S]
    n_hosts: int
    times: np.ndarray  # float64 [S, n]
    victim: np.ndarray  # int32  [S, n]
    parent: np.ndarray  # int32  [S, n]
    predictable: np.ndarray  # bool [S, n]
    during_ckpt: np.ndarray  # bool [S, n]
    valid: np.ndarray  # bool [S, n]
    repair_draws: np.ndarray  # float64 [S, n]
    rack_corr: np.ndarray  # bool [S, n]
    part_active: np.ndarray  # bool [S, n]
    # [S, n, H] when the family has a partition timeline, [S, n, 1] (all
    # -1) otherwise — the fleet-scale memory term is gated, not implicit
    part_comp: np.ndarray  # int32 [S, n, H] or [S, n, 1]

    @property
    def n_seeds(self) -> int:
        return int(self.times.shape[0])

    @property
    def n_slots(self) -> int:
        return int(self.times.shape[1])


def compile_batch(
    spec: ScenarioSpec, n_seeds: int, base_seed: int = 0
) -> TapeBatch:
    """Compile tapes for seeds ``base_seed .. base_seed + n_seeds - 1`` and
    pad/stack them (padding slots: ``t = +inf``, ``valid = False``). The
    slot count is rounded up to a multiple of 8 so the jitted replay
    program is shared across batches whose max event count jitters."""
    tapes = [compile_tape(spec, base_seed + s) for s in range(n_seeds)]
    H = spec.n_nodes + spec.n_spares
    n = max(1, max(t.n_slots for t in tapes))
    n = -(-n // 8) * 8
    S = n_seeds

    times = np.full((S, n), np.inf, np.float64)
    victim = np.full((S, n), -1, np.int32)
    parent = np.full((S, n), -1, np.int32)
    pred = np.zeros((S, n), bool)
    during = np.zeros((S, n), bool)
    valid = np.zeros((S, n), bool)
    draws = np.zeros((S, n), np.float64)
    rcorr = np.zeros((S, n), bool)
    p_act = np.zeros((S, n), bool)
    # all tapes share the spec's (deterministic) partition timeline, so
    # their part_comp widths agree: H with cuts, 1 (compact) without
    W = max(tp.part_comp.shape[1] for tp in tapes)
    p_comp = np.full((S, n, W), -1, np.int32)
    for s, tp in enumerate(tapes):
        k = tp.n_slots
        times[s, :k] = tp.times
        victim[s, :k] = tp.victim
        parent[s, :k] = tp.parent
        pred[s, :k] = tp.predictable
        during[s, :k] = tp.during_ckpt
        valid[s, :k] = True
        draws[s, :k] = tp.repair_draws
        rcorr[s, :k] = tp.rack_corr
        p_act[s, :k] = tp.part_active
        p_comp[s, :k] = tp.part_comp

    return TapeBatch(
        spec_name=spec.name,
        seeds=np.arange(base_seed, base_seed + n_seeds, dtype=np.int64),
        n_hosts=H,
        times=times,
        victim=victim,
        parent=parent,
        predictable=pred,
        during_ckpt=during,
        valid=valid,
        repair_draws=draws,
        rack_corr=rcorr,
        part_active=p_act,
        part_comp=p_comp,
    )


# ======================================================================
# Layer 2: the vmapped replay kernel
# ======================================================================
@dataclass(frozen=True)
class _ReplayStatic:
    """Hashable compile-time configuration of one replay program."""

    n_hosts: int
    n_workers: int
    n_spares: int
    n_slots: int  # padded to a multiple of tile_slots
    period_s: float
    horizon_s: float
    max_strikes: int
    repair_none: bool
    # partition arrays are threaded through the scan ONLY when the
    # placement is partition-aware AND the batch has an open cut on some
    # slot (otherwise the scope/quorum branches are provable no-ops), so
    # the O(n_slots x H) component tape never reaches the device for the
    # families that cannot use it
    partition_aware: bool
    rules_agent_small: bool  # Rules 2-3 verdict for the (static) payload size
    # when True the scan additionally stacks per-slot decision arrays
    # (processed/handled/victim/target/...) for trace reconstruction — a
    # separate cached program, so the default replay path is unchanged
    record: bool = False
    # event-tape tiling: the slot axis is folded as an outer scan over
    # n_slots/tile_slots tiles of an inner fixed-length scan. Padding
    # slots are fully masked (valid=False), so totals are bit-identical
    # across tile sizes by construction.
    tile_slots: int = 8
    # seed-axis sharding: >1 wraps the vmapped fold in shard_map over a
    # 1-d 'seeds' device mesh. Per-seed work is independent, so results
    # are bit-identical at any device count.
    n_devices: int = 1
    # donate the tape argument's device buffers (False only for the A/B
    # peak-memory comparison in tests/profiling)
    donate: bool = True


@dataclass(frozen=True)
class _TableStatic:
    """The branch-selecting flags of a :class:`StrategyCostTable`. Only
    these reach the tracer as Python values — the numeric coefficients
    travel as a runtime jnp vector (``_COEFF_FIELDS`` order), so one
    compiled program serves every cost table sharing this structure
    (e.g. all four workloads' pricings of one strategy)."""

    mode: str  # "window" | "proactive" | "cold"
    mechanism: str  # "agent" | "core" | "rules"
    ckpt_invalidation: bool


#: StrategyCostTable numeric fields, in the order they are packed into
#: host-axis width at or below which repair-completion ranking uses the
#: vectorised O(H^2) pairwise comparison matrix instead of a stable
#: argsort — XLA CPU's comparator sort pays a per-instance cost that the
#: small-cluster matrix beats by ~3x, while at fleet widths (1k+ hosts)
#: the O(H log H) sort is the only affordable form. Both are bit-identical
#: on the due hosts (the inverse permutation of a stable sort restricted
#: to finite keys equals the pairwise earlier-or-tied-lower-index count).
_PAIRWISE_RANK_MAX_HOSTS = 128

#: spare-pool sequence number of a host that is not in the pool. The pool
#: is int32, so the placement argmin reduces in s32: over a float64 pool
#: the TPU compiler narrows that argmin's value accumulators to bf16
_NOT_POOLED = np.iinfo(np.int32).max

#: the replay program's runtime ``coeffs`` argument (float64 [8])
_COEFF_FIELDS = (
    "probe_s_per_hour",
    "predict_s",
    "reinstate_s",
    "overhead_s",
    "agent_reinstate_s",
    "agent_overhead_s",
    "core_reinstate_s",
    "core_overhead_s",
)


def _table_coeffs(table: StrategyCostTable) -> np.ndarray:
    return np.asarray([getattr(table, f) for f in _COEFF_FIELDS], np.float64)


def replay_cache_stats() -> Dict[str, int]:
    """Compile-cache counters for the replay program. A sweep over N
    cost tables sharing one (scenario shape, table structure) should
    show N-1 hits, not N compiles — the bench report records these."""
    info = _compiled_replayer.cache_info()
    return {
        "hits": int(info.hits),
        "misses": int(info.misses),
        "programs": int(info.currsize),
    }


@lru_cache(maxsize=128)
def _compiled_replayer(static: _ReplayStatic, tstatic: _TableStatic):
    """Build (and cache) the jitted, vmapped replay program for one
    (scenario-shape, cost-table-structure) pair. Cost-table *values*
    arrive as the runtime ``coeffs`` vector, so swapping strategies or
    workloads that share structure reuses the compiled program. Must be
    called — and the result invoked — under
    :func:`repro.utils.backend.x64` so times and cost accumulators trace
    as float64 (the engine's arithmetic).

    The program's signature is ``fn(coeffs, tape)``: ``coeffs`` the
    float64 [8] ``_COEFF_FIELDS`` vector, ``tape`` a dict of ``[S, ...]``
    slot arrays. The tape argument's device buffers are donated
    (``donate_argnums=(1,)``) so the scan working set aliases them
    instead of holding inputs and carries live simultaneously."""
    import jax
    import jax.numpy as jnp

    H = static.n_hosts
    n_slots = static.n_slots
    tile = static.tile_slots
    n_tiles = n_slots // tile
    period_s = static.period_s
    horizon_s = static.horizon_s
    max_strikes = static.max_strikes
    mode = tstatic.mode
    idxH = jnp.arange(H, dtype=jnp.int32)
    idxS = jnp.arange(n_slots, dtype=jnp.int64)

    # initial dependency degrees of the engine's star topology (genome
    # search: workers feed one combiner, spares carry no edges)
    deg0 = np.zeros(H, np.int32)
    if static.n_workers > 1:
        deg0[: static.n_workers - 1] = 1
        deg0[static.n_workers - 1] = static.n_workers - 1

    def one_seed(coeffs, tape):
        draws = tape["draws"]  # full slot axis: indexed by repair count
        c_probe = coeffs[0]
        c_predict = coeffs[1]
        c_reinstate = coeffs[2]
        c_overhead = coeffs[3]
        c_agent_rst = coeffs[4]
        c_agent_ovh = coeffs[5]
        c_core_rst = coeffs[6]
        c_core_ovh = coeffs[7]
        init = dict(
            down=jnp.zeros(H, bool),
            repair_at=jnp.full(H, jnp.inf, dtype=jnp.float64),
            black=jnp.zeros(H, bool),
            strikes=jnp.zeros(H, jnp.int32),
            occupied=idxH < static.n_workers,
            # spare-pool FIFO: entry-sequence number per host
            # (_NOT_POOLED = not in the pool); argmin over eligible entries
            # reproduces the engine's list order through removals and
            # repair re-appends
            spare_seq=jnp.where(
                idxH >= static.n_workers, idxH - static.n_workers, _NOT_POOLED
            ).astype(jnp.int32),
            next_seq=jnp.asarray(static.n_spares, dtype=jnp.int32),
            deg=jnp.asarray(deg0, dtype=jnp.int32),
            attempt=jnp.zeros(H, dtype=jnp.float64),
            rcount=jnp.asarray(0, jnp.int32),
            n_events=jnp.asarray(0, jnp.int32),
            n_handled=jnp.asarray(0, jnp.int32),
            n_migrations=jnp.asarray(0, jnp.int32),
            n_blacklisted=jnp.asarray(0, jnp.int32),
            n_reprovisioned=jnp.asarray(0, jnp.int32),
            lost=jnp.asarray(0.0, dtype=jnp.float64),
            reinstate=jnp.asarray(0.0, dtype=jnp.float64),
            overhead=jnp.asarray(0.0, dtype=jnp.float64),
            alive=jnp.asarray(True, dtype=jnp.bool_),
            failed_at=jnp.asarray(0.0, dtype=jnp.float64),
            fired=jnp.zeros(n_slots, bool),
            tgt_rec=jnp.full(n_slots, -1, jnp.int32),
        )

        def step(c, x):
            j = x["j"]
            t = x["t"]
            v0 = x["v0"]
            par = x["par"]
            prd = x["prd"]
            vrd = x["vrd"]
            dur = x["dur"]
            ok = x["ok"]
            live = ok & c["alive"]

            # -- repairs completing strictly before t rejoin the spare
            #    pool in completion order (heap: repair events pushed
            #    after the original stream pop later at equal times).
            #    Completion order: due hosts carry their finite repair_at,
            #    everyone else +inf. Two bit-identical rankings, chosen by
            #    host-axis width at trace time: the stable-argsort inverse
            #    permutation restricted to ``due`` equals the pairwise
            #    (earlier, or equal-time-and-lower-host) count, and the
            #    O(H log H) sort wins at fleet widths while the vectorised
            #    O(H^2) comparison matrix beats XLA CPU's comparator sort
            #    on small clusters.
            due = live & (c["repair_at"] < t)
            ra = jnp.where(due, c["repair_at"], jnp.inf)
            if H <= _PAIRWISE_RANK_MAX_HOSTS:
                before = (ra[None, :] < ra[:, None]) | (
                    (ra[None, :] == ra[:, None]) & (idxH[None, :] < idxH[:, None])
                )
                rank = jnp.sum(before & due[None, :], axis=1)
            else:
                order = jnp.argsort(ra, stable=True)
                rank = jnp.zeros(H, dtype=jnp.int32).at[order].set(idxH)
            nrep = jnp.sum(due)
            spare_seq = jnp.where(due, c["next_seq"] + rank.astype(jnp.int32), c["spare_seq"])
            next_seq = c["next_seq"] + nrep.astype(jnp.int32)
            down = c["down"] & ~due
            repair_at = jnp.where(due, jnp.inf, c["repair_at"])
            n_reprovisioned = c["n_reprovisioned"] + nrep.astype(jnp.int32)

            # -- resolve the victim: cascade children chase the host their
            #    parent's sub-job migrated to, and only exist if it did
            has_par = par >= 0
            pi = jnp.maximum(par, 0)
            victim = jnp.where(has_par, c["tgt_rec"][pi], v0)
            spawned = jnp.where(has_par, c["fired"][pi], True)
            active = live & spawned & (victim >= 0)
            v = jnp.clip(victim, 0, H - 1)
            n_events = c["n_events"] + active.astype(jnp.int32)
            processed = active & ~down[v]  # down victims coalesce
            # per-host writes are selects against one-hot rows, never
            # scatters: on a TPU v5e a vmapped scatter into a bool carry
            # drops some seeds' writes (int32 and float scatters do not)
            at_v = idxH == v

            strikes = c["strikes"] + (at_v & processed).astype(jnp.int32)
            if static.repair_none:
                permanent = processed
            else:
                permanent = processed & (strikes[v] >= max_strikes)
            has_work = c["occupied"][v]

            # -- placement: nearest-spare with require_free (pool FIFO ->
            #    ring neighbours -> first free host), partition-scoped and
            #    quorum-gated when the campaign runs partition-aware
            okf = ~c["black"] & ~down & ~c["occupied"]
            if static.partition_aware:
                pa = x["pa"]
                comp = x["comp"]
                allowed = jnp.where(pa, comp == comp[v], True)
                okf = okf & allowed
            pool = (spare_seq != _NOT_POOLED) & okf
            i1 = jnp.argmin(jnp.where(pool, spare_seq, _NOT_POOLED)).astype(jnp.int32)
            nb1 = (v - 1) % H
            nb2 = (v + 1) % H
            m3 = okf & (idxH != v)
            target = jnp.where(
                jnp.any(pool),
                i1,
                jnp.where(
                    okf[nb1],
                    nb1,
                    jnp.where(
                        okf[nb2],
                        nb2,
                        jnp.where(jnp.any(m3), jnp.argmax(m3).astype(jnp.int32), -1),
                    ),
                ),
            )
            if static.partition_aware:
                members = jnp.sum(~down & jnp.where(pa, comp == comp[v], True))
                n_alive = jnp.sum(~down)
                target = jnp.where(pa & (2 * members <= n_alive), -1, target)
            target = jnp.where(processed & has_work, target, -1)

            stranded = processed & has_work & (target < 0)
            handled = processed & has_work & (target >= 0)
            tgt = jnp.clip(target, 0, H - 1)

            # -- per-event billing from the StrategyCostTable
            wstart = jnp.floor(t / period_s) * period_s
            if mode == "window":
                if tstatic.ckpt_invalidation:
                    # mid-checkpoint failure: restore from one window back
                    # plus the wasted partial write
                    lost_ev = (t - wstart) + jnp.where(dur, period_s, 0.0)
                    ovh_ev = c_overhead * jnp.where(dur, 1.5, 1.0)
                else:
                    lost_ev = t - wstart
                    ovh_ev = c_overhead
                rst_ev = c_reinstate
            elif mode == "proactive":
                if tstatic.mechanism == "agent":
                    is_agent = jnp.asarray(True, dtype=jnp.bool_)
                elif tstatic.mechanism == "core":
                    is_agent = jnp.asarray(False, dtype=jnp.bool_)
                else:  # "rules": Z-negotiation per event (Rules 1-3)
                    if static.rules_agent_small:
                        is_agent = c["deg"][v] > Z_THRESHOLD
                    else:
                        is_agent = jnp.asarray(False, dtype=jnp.bool_)
                rst_m = jnp.where(is_agent, c_agent_rst, c_core_rst)
                ovh_ev = jnp.where(is_agent, c_agent_ovh, c_core_ovh)
                # a failure is only *saved* when the detector claimed it AND
                # a real lead window existed (ground-truth signature); every
                # claim — true or false — pays the prediction work
                lost_ev = jnp.where(vrd & prd, 0.0, t - wstart)
                rst_ev = rst_m + jnp.where(vrd, c_predict, 0.0)
            else:  # "cold": lose everything since the sub-job's last start
                lost_ev = t - c["attempt"][v]
                rst_ev = c_reinstate
                ovh_ev = jnp.asarray(0.0, dtype=jnp.float64)

            lost = c["lost"] + jnp.where(handled, lost_ev, 0.0)
            reinstate = c["reinstate"] + jnp.where(handled, rst_ev, 0.0)
            overhead = c["overhead"] + jnp.where(handled, ovh_ev, 0.0)
            n_handled = c["n_handled"] + handled.astype(jnp.int32)
            n_migrations = c["n_migrations"] + (
                handled.astype(jnp.int32) if mode == "proactive" else 0
            )

            # -- migrate the sub-job (occupancy, pool, dependency degree,
            #    cold attempt clock follow the work)
            moved_from = at_v & handled
            moved_to = (idxH == tgt) & handled
            occupied = (c["occupied"] & ~moved_from) | moved_to
            spare_seq = jnp.where(moved_to, _NOT_POOLED, spare_seq)
            deg = jnp.where(moved_to, c["deg"][v], c["deg"])
            deg = jnp.where(moved_from, 0, deg)
            attempt = c["attempt"]
            if mode == "cold":
                attempt = jnp.where(moved_to, t, attempt)

            # -- fail the victim; blacklist or schedule its repair
            down = down | (at_v & processed)
            spare_seq = jnp.where(at_v & processed, _NOT_POOLED, spare_seq)
            newly_black = permanent & ~stranded
            black = c["black"] | (at_v & newly_black)
            n_blacklisted = c["n_blacklisted"] + newly_black.astype(jnp.int32)
            sched = processed & ~stranded & ~permanent
            rdraw = draws[jnp.clip(c["rcount"], 0, n_slots - 1)]
            repair_at = jnp.where(at_v & sched, t + rdraw, repair_at)
            rcount = c["rcount"] + sched.astype(jnp.int32)

            alive = c["alive"] & ~stranded
            failed_at = jnp.where(stranded, t, c["failed_at"])
            at_j = idxS == j
            fired = jnp.where(at_j, handled, c["fired"])
            tgt_rec = jnp.where(at_j, jnp.where(handled, tgt, -1), c["tgt_rec"])

            # per-slot decision record for trace reconstruction: exactly
            # the facts the engine's emit sites see (resolved victim,
            # chosen target, scheduled repair completion)
            y = None
            if static.record:
                y = dict(
                    processed=processed,
                    handled=handled,
                    victim=jnp.where(processed, v, -1).astype(jnp.int32),
                    target=jnp.where(handled, tgt, -1).astype(jnp.int32),
                    blacklisted=newly_black,
                    repair_sched=sched,
                    repair_at=jnp.where(sched, t + rdraw, jnp.inf),
                    stranded=stranded,
                )

            return (
                dict(
                    down=down,
                    repair_at=repair_at,
                    black=black,
                    strikes=strikes,
                    occupied=occupied,
                    spare_seq=spare_seq,
                    next_seq=next_seq,
                    deg=deg,
                    attempt=attempt,
                    rcount=rcount,
                    n_events=n_events,
                    n_handled=n_handled,
                    n_migrations=n_migrations,
                    n_blacklisted=n_blacklisted,
                    n_reprovisioned=n_reprovisioned,
                    lost=lost,
                    reinstate=reinstate,
                    overhead=overhead,
                    alive=alive,
                    failed_at=failed_at,
                    fired=fired,
                    tgt_rec=tgt_rec,
                ),
                y,
            )

        def tile_step(c, tx):
            return jax.lax.scan(step, c, tx)

        def tiled(a):
            return a.reshape((n_tiles, tile) + a.shape[1:])

        xs = dict(
            j=tiled(jnp.arange(n_slots, dtype=jnp.int64)),
            t=tiled(tape["times"]),
            v0=tiled(tape["victim"]),
            par=tiled(tape["parent"]),
            prd=tiled(tape["pred"]),
            vrd=tiled(tape["verd"]),
            dur=tiled(tape["during"]),
            ok=tiled(tape["valid"]),
        )
        if static.partition_aware:
            xs["pa"] = tiled(tape["pa"])
            xs["comp"] = tiled(tape["comp"])
        c, ys = jax.lax.scan(tile_step, init, xs)

        # repairs still pending at the end of the stream complete (and are
        # counted) if they land inside the horizon — unless the campaign
        # was lost, in which case the engine abandons the queue
        tail_repairs = jnp.sum(c["repair_at"] < horizon_s).astype(jnp.int32)
        n_reprovisioned = c["n_reprovisioned"] + jnp.where(c["alive"], tail_repairs, 0)

        # background probing accrues only while the campaign is running
        span_s = jnp.where(c["alive"], horizon_s, c["failed_at"])
        probe = c_probe * span_s / 3600.0
        total = jnp.where(
            c["alive"],
            horizon_s + c["lost"] + c["reinstate"] + c["overhead"] + probe,
            jnp.nan,
        )
        out = dict(
            survived=c["alive"],
            total_s=total,
            failed_at_s=jnp.where(c["alive"], jnp.nan, c["failed_at"]),
            lost_s=c["lost"],
            reinstate_s=c["reinstate"],
            overhead_s=c["overhead"],
            probe_s=probe,
            n_events=c["n_events"],
            n_handled=c["n_handled"],
            n_migrations=c["n_migrations"],
            n_blacklisted=c["n_blacklisted"],
            n_reprovisioned=n_reprovisioned,
        )
        if static.record:
            # inner scan stacks [tile, ...], outer stacks tiles: flatten
            # [n_tiles, tile, ...] back to the slot axis
            for k, v in ys.items():
                out["slot_" + k] = v.reshape((n_slots,) + v.shape[2:])
        return out

    vmapped = jax.vmap(one_seed, in_axes=(None, 0))
    if static.n_devices > 1:
        from jax.sharding import Mesh, PartitionSpec

        mesh = Mesh(
            np.asarray(jax.devices()[: static.n_devices]), axis_names=("seeds",)
        )
        # seeds never communicate: no collective to check, and the scan's
        # replicated initial carry need not be cast to the varying axis
        vmapped = jax.shard_map(
            vmapped,
            mesh=mesh,
            in_specs=(PartitionSpec(), PartitionSpec("seeds")),
            out_specs=PartitionSpec("seeds"),
            check_vma=False,
        )
    # donate the tape: slot-shaped outputs alias the input buffers and
    # consumed tape buffers free mid-execution instead of staying live
    # alongside the scan working set
    return jax.jit(vmapped, donate_argnums=(1,) if static.donate else ())


def _payload_bytes(payload_elems: int) -> int:
    """S_d of the engine's per-host sub-job payload (Rules 2-3 input)."""
    # engine fidelity: the real sub-job payload ships f32 partials
    return tree_bytes({"partial": np.zeros(payload_elems, np.float32), "cursor": 0})  # repro: ignore[dtype-x64]


def _default_micro(workload, profile: str, n_nodes: int):
    """Default MicroCosts per (workload, profile, n_nodes). The
    underlying ``measure_micro`` is memoized on its full argument tuple,
    so repeated replay_batch/mc_trajectories calls under the same
    workload share one record — and therefore one compiled program —
    instead of a numerically distinct wall-clock remeasurement (and a
    full jit recompile) per call."""
    return workload.micro(profile, n_nodes=n_nodes)


@contextmanager
def _quiet_donation():
    """Silence the expected 'donated buffers were not usable' warning:
    small-family shapes cannot alias every donated tape buffer into the
    outputs — donation is a fleet-scale peak-memory optimisation there,
    not a correctness contract, and the unusable buffers are simply
    copied. Any other warning still propagates."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


def default_seed_devices(n_seeds: int) -> int:
    """Largest local device count that divides the seed axis evenly — the
    default shard count for :func:`replay_batch`. Sharding never changes
    results (per-seed work is independent), only placement, so scaling to
    whatever ``XLA_FLAGS=--xla_force_host_platform_device_count`` / the
    TPU topology provides is always safe."""
    import jax

    d = int(jax.local_device_count())
    while d > 1 and n_seeds % d:
        d -= 1
    return max(d, 1)


def _resolve_program(
    spec: ScenarioSpec,
    batch: TapeBatch,
    strategy,
    *,
    micro=None,
    profile: str = "placentia",
    placement: Optional[str] = None,
    payload_elems: int = 1 << 10,
    detector="oracle",
    workload=None,
    record_slots: bool = False,
    tile_slots: int = 8,
    n_devices: Optional[int] = None,
    donate: bool = True,
):
    """Shared front half of the replay path: resolve strategy / detector /
    workload micro, pre-sample per-seed verdict tapes, pad the slot axis
    to the tile multiple, build (or fetch from cache) the jitted vmapped
    program. Returns ``(fn, args, detector, verdicts, ctx)`` with
    ``args = (coeffs, tape)`` and ``ctx`` the resolved billing inputs
    (strategy cost table, ``rules_agent_small``) the SLO biller shares
    with the engine; ``fn(*args)`` — and any ``fn.lower(*args)`` —
    must run under :func:`~repro.utils.backend.x64`."""
    from repro.telemetry import registry as detector_registry
    from repro.telemetry.detector import Detector
    from repro.workloads import resolve as resolve_workload

    if isinstance(strategy, FaultToleranceStrategy):
        strat = strategy
    else:
        strat = strategy_registry.get(strategy)
    det = detector if isinstance(detector, Detector) else detector_registry.get(detector)
    if micro is None:
        micro = _default_micro(resolve_workload(workload, spec), profile, spec.n_nodes)
    table = strat.cost_table(CostContext(micro=micro, period_h=spec.period_s / 3600.0))

    # per-seed verdict tapes (the oracle's is the predictable bits verbatim)
    verdicts = np.zeros_like(batch.predictable)
    for s in range(batch.n_seeds):
        v, _ = det.verdict_tape(
            spec,
            times=batch.times[s],
            predictable=batch.predictable[s],
            rack_corr=batch.rack_corr[s],
            seed=int(batch.seeds[s]),
        )
        verdicts[s] = v

    placement = placement or spec.placement or "nearest-spare"
    if placement not in ("nearest-spare", "partition-aware"):
        raise ValueError(
            f"replay kernel supports 'nearest-spare' / 'partition-aware' "
            f"placement, not {placement!r}; run through CampaignEngine instead"
        )

    # pad the slot axis to a multiple of the tile size. Padding slots are
    # fully masked (valid=False => every state update under them is a
    # no-op), so totals are bit-identical across tile sizes.
    tile = max(1, int(tile_slots))
    n_slots = -(-batch.n_slots // tile) * tile
    pad = n_slots - batch.n_slots

    def padded(a: np.ndarray, fill) -> np.ndarray:
        if pad == 0:
            return a
        out = np.full((a.shape[0], n_slots) + a.shape[2:], fill, a.dtype)
        out[:, : batch.n_slots] = a
        return out

    tape = dict(
        times=padded(batch.times, np.inf),
        victim=padded(batch.victim, -1),
        parent=padded(batch.parent, -1),
        pred=padded(batch.predictable, False),
        verd=padded(verdicts, False),
        during=padded(batch.during_ckpt, False),
        valid=padded(batch.valid, False),
        draws=padded(batch.repair_draws, 0.0),
    )
    # the O(n_slots x H) component tape only ships when the placement can
    # consume it AND a cut is actually open somewhere in the batch
    use_partition = placement == "partition-aware" and bool(batch.part_active.any())
    if use_partition:
        if batch.part_comp.shape[2] != batch.n_hosts:
            raise ValueError(
                "batch has active partition slots but a compacted part_comp "
                f"tape (width {batch.part_comp.shape[2]} != {batch.n_hosts})"
            )
        tape["pa"] = padded(batch.part_active, False)
        tape["comp"] = padded(batch.part_comp, -1)

    import jax

    if n_devices is None:
        n_devices = default_seed_devices(batch.n_seeds)
    n_devices = max(1, int(n_devices))
    if n_devices > jax.local_device_count():
        raise ValueError(
            f"n_devices={n_devices} > available devices "
            f"({jax.local_device_count()}); set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU"
        )
    if batch.n_seeds % n_devices:
        raise ValueError(
            f"n_devices={n_devices} must divide the seed axis ({batch.n_seeds})"
        )

    static = _ReplayStatic(
        n_hosts=batch.n_hosts,
        n_workers=spec.n_nodes,
        n_spares=spec.n_spares,
        n_slots=n_slots,
        period_s=float(spec.period_s),
        horizon_s=float(spec.horizon_s),
        max_strikes=int(spec.max_strikes),
        repair_none=spec.repair_s is None,
        partition_aware=use_partition,
        rules_agent_small=_payload_bytes(payload_elems) <= SD_THRESHOLD_BYTES,
        record=record_slots,
        tile_slots=tile,
        n_devices=n_devices,
        donate=bool(donate),
    )
    tstatic = _TableStatic(
        mode=table.mode,
        mechanism=table.mechanism,
        ckpt_invalidation=bool(table.ckpt_invalidation),
    )
    with x64():  # program construction traces x64 constants
        fn = _compiled_replayer(static, tstatic)
    args = (_table_coeffs(table), tape)
    ctx = {"table": table, "rules_agent_small": static.rules_agent_small}
    return fn, args, det, verdicts, ctx


def replay_program(
    spec: ScenarioSpec,
    batch: TapeBatch,
    strategy,
    *,
    micro=None,
    profile: str = "placentia",
    placement: Optional[str] = None,
    payload_elems: int = 1 << 10,
    detector="oracle",
    workload=None,
    record_slots: bool = False,
    tile_slots: int = 8,
    n_devices: Optional[int] = None,
    donate: bool = True,
) -> Tuple:
    """The AOT-profilable handle on the replay kernel: ``(fn, args)``.

    ``fn`` is the cached jitted vmapped program and ``args`` the exact
    ``(coeffs, tape)`` pair :func:`replay_batch` would feed it, so
    ``fn.lower(*args).compile()`` splits compile from execute time —
    what :func:`repro.obs.profile.profile_replay` measures. Everything
    (lower, compile, invoke) must run under
    :func:`repro.utils.backend.x64`, the kernel's required precision."""
    fn, args, _, _, _ = _resolve_program(
        spec,
        batch,
        strategy,
        micro=micro,
        profile=profile,
        placement=placement,
        payload_elems=payload_elems,
        detector=detector,
        workload=workload,
        record_slots=record_slots,
        tile_slots=tile_slots,
        n_devices=n_devices,
        donate=donate,
    )
    return fn, args


def replay_batch(
    spec: ScenarioSpec,
    batch: TapeBatch,
    strategy,
    *,
    micro=None,
    profile: str = "placentia",
    placement: Optional[str] = None,
    payload_elems: int = 1 << 10,
    detector="oracle",
    workload=None,
    autoscaler=None,
    record_slots: bool = False,
    tile_slots: int = 8,
    n_devices: Optional[int] = None,
) -> Dict[str, np.ndarray]:
    """Replay a compiled :class:`TapeBatch` under one strategy's cost table.

    ``strategy`` is a registered name (aliases ok) or a strategy
    instance; ``detector`` likewise (a :class:`~repro.telemetry.detector.
    Detector` name or instance); ``workload`` a :mod:`repro.workloads`
    name or instance supplying the micro-costs when none are given
    (default: the spec's declared workload, then ``analytic`` — the seed
    cost model bit-for-bit). Because the engine resolves the identical
    record, trial-for-trial parity holds under every workload.
    Per-event verdict tapes are pre-sampled
    per seed in schedule order — the exact draws the Python engine makes —
    and fed to the kernel alongside the ground-truth ``predictable`` bits
    (a failure is *saved* only when claimed AND a real lead window
    existed; every claim pays the prediction work), so the replay stays
    trial-for-trial identical to
    ``CampaignEngine(spec, strategy, seed=k, detector=...)`` under any
    detector. Returns per-seed numpy arrays keyed like
    :class:`~repro.scenarios.engine.CampaignResult` fields (``total_s`` /
    ``failed_at_s`` are NaN where inapplicable). One jitted vmapped
    program evaluates every seed; programs are cached per
    (scenario-shape, cost-table) pair, so repeated calls only pay the
    fold itself.

    ``record_slots=True`` additionally returns per-slot decision arrays
    (``slot_processed`` / ``slot_handled`` / ``slot_victim`` /
    ``slot_target`` / ``slot_blacklisted`` / ``slot_repair_sched`` /
    ``slot_repair_at`` / ``slot_stranded``, each ``[S, n_slots]``) plus
    the pre-sampled ``slot_verdict`` tape — everything
    :func:`repro.obs.trace.reconstruct_traces` needs to rebuild the
    engine's event timeline exactly. A separate cached program; the
    default path is untouched.

    ``tile_slots`` sets the event-tape tile width (the slot axis is
    padded to a multiple and scanned as an outer fold over tiles) and
    ``n_devices`` the seed-axis shard count (default: the largest local
    device count that divides the seed axis — see
    :func:`default_seed_devices`). Both are pure execution-shape knobs:
    results are bit-identical across every tile size and device count."""
    import jax

    from repro.scenarios.spec import degrade_slowdown_s

    fn, args, det, verdicts, ctx = _resolve_program(
        spec,
        batch,
        strategy,
        micro=micro,
        profile=profile,
        placement=placement,
        payload_elems=payload_elems,
        detector=detector,
        workload=workload,
        record_slots=record_slots,
        tile_slots=tile_slots,
        n_devices=n_devices,
    )
    with x64(), _quiet_donation():
        out = fn(*args)
        out = jax.block_until_ready(out)
    out = {k: np.asarray(v) for k, v in out.items()}
    if record_slots:
        # drop the tile-padding slots so per-slot arrays keep the batch's
        # slot-axis contract (padding rows are all-masked no-ops anyway)
        for k in list(out):
            if k.startswith("slot_"):
                out[k] = out[k][:, : batch.n_slots]

    # degrade windows bill identically to the engine: a deterministic
    # extra-step-time scalar per campaign (NaN totals stay NaN)
    slow = degrade_slowdown_s(spec, mitigate_stragglers=det.flags_stragglers)
    if slow:
        out["total_s"] = out["total_s"] + slow
    out["slowdown_s"] = np.full(batch.n_seeds, slow, np.float64)

    # request-level SLO billing: the identical shared deterministic
    # function (and identical inputs — valid-prefix tape slices + the
    # per-seed verdict tapes) the engine calls, so the four SLO arrays
    # are trial-for-trial bitwise equal to CampaignEngine's fields
    if getattr(spec, "traffic", None) is not None:
        from repro.traffic.slo import bill_slo
        from repro.workloads import resolve as resolve_workload

        wtable = resolve_workload(workload, spec).cost_table(
            profile, n_nodes=spec.n_nodes
        )
        S = batch.n_seeds
        slo = {
            "slo_p50_s": np.empty(S, np.float64),
            "slo_p99_s": np.empty(S, np.float64),
            "slo_dropped": np.empty(S, np.float64),
            "slo_availability": np.empty(S, np.float64),
        }
        for s in range(S):
            m = batch.valid[s]
            bill = bill_slo(
                spec,
                times=batch.times[s][m],
                victim=batch.victim[s][m],
                parent=batch.parent[s][m],
                predictable=batch.predictable[s][m],
                verdicts=verdicts[s][m],
                draws=batch.repair_draws[s][m],
                table=ctx["table"],
                wtable=wtable,
                seed=int(batch.seeds[s]),
                autoscaler=autoscaler,
                rules_agent_small=ctx["rules_agent_small"],
            )
            slo["slo_p50_s"][s] = bill.p50_s
            slo["slo_p99_s"][s] = bill.p99_s
            slo["slo_dropped"][s] = bill.dropped
            slo["slo_availability"][s] = bill.availability
        out.update(slo)

    if record_slots:
        out["slot_verdict"] = verdicts
    return out
