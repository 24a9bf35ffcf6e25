#!/usr/bin/env python3
"""Bring-up smoke test on a TPU: the planning path and the paper's genome job,
driven through the library's own entry points, with their outputs checked.

Phases, in order:

  device  a TPU must be JAX's default backend (platform, kind and count are
          printed first); the compiled flash_decode and flash_attention
          Pallas kernels agree with their references at real widths
  plan    mc_trajectories on table2_random, mc_stress, decode_fleet_churn
          and fleet_stress (central_single; core too on mc_stress): tape
          compile, XLA compile and execute seconds and seeds/s per family,
          the replay kernel's outputs checked to live on the TPU, and each
          family checked trial for trial against the host CampaignEngine
          (survival identical, total_s within the bench's relative 1e-6)
  oracle  choose_strategy on live_genome_single, timed to a decision
  genome  the paper's genome search at its 512 MB input: 3 sub-jobs
          combined, one of them handed mid-job to a fresh job object
          through state_dict/load_state. Every planted occurrence must be
          found, every hit must match the genome on the host, and the
          migrated table must equal the uninterrupted one

Usage::

    python chip_smoke.py             # one chip: every phase
    python chip_smoke.py --chips 4   # only fleet_stress with the seed axis
                                     # sharded over 4 chips, against the
                                     # same batch on one chip, bitwise

On success the last line of standard output is exactly one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line. It sets no XLA or libtpu flags of its own; JAX's
persistent compile cache goes where ``repro.utils.backend`` puts it
(``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` beside this file).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

#: (family, strategy, seeds replayed, leading seeds checked against the engine)
CELLS = (
    ("table2_random", "central_single", 1024, 64),
    ("mc_stress", "central_single", 256, 32),
    ("mc_stress", "core", 256, 32),
    ("decode_fleet_churn", "central_single", 256, 8),
    ("fleet_stress", "central_single", 512, 3),
)
#: bench_scenarios' engine_match bound on |kernel - engine| / engine
PARITY_RTOL = 1e-6
#: the sharded path's family and seed count (the bench's fleet certification)
SHARDED_FAMILY, SHARDED_SEEDS = "fleet_stress", 512
#: patterns searched: cut from the paper's 5000 to keep the run short
GENOME_PATTERNS = 16
#: chunks per search sub-job (a step of a sub-job searches one chunk)
GENOME_CHUNKS = 2
GENOME_SEED = 7
#: kernel-vs-reference bounds, per input dtype (tests/test_kernels.py's)
KERNEL_TOL = {"float32": 2e-5, "bfloat16": 2e-2}
#: the only platform this smoke accepts
PLATFORM = "tpu"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """XLA compile seconds and persistent-cache hits, from JAX's own
    monitoring events, so a phase can report what it spent compiling."""

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.n_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.n_compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def require_tpu(n_chips: int) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"[device] platform={d.platform} kind={d.device_kind} count={len(devices)}")
    if d.platform != PLATFORM:
        raise SystemExit(
            f"no TPU found: JAX's default backend is {d.platform!r}; "
            "this smoke runs only on a TPU"
        )
    if len(devices) < n_chips:
        raise SystemExit(f"--chips {n_chips} needs {n_chips} TPU chips, found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


# ---------------------------------------------------------------- device ---
def check_kernels() -> None:
    """The compiled Pallas kernels (no interpret flag passed: the default
    must compile on the TPU) against their references at HIGHEST
    matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention import flash_decode, flash_decode_ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import attention_ref

    def normal(i, shape, dtype):
        return jax.random.normal(jax.random.key(i), shape, jnp.float32).astype(dtype)

    B, H, K, S, hd = 8, 8, 1, 2048, 256
    q = normal(0, (B, H, hd), jnp.float32)
    k = normal(1, (B, K, S, hd), jnp.float32)
    v = normal(2, (B, K, S, hd), jnp.float32)
    pos = S - 7  # the last six cache slots are still empty
    kpos = jnp.where(jnp.arange(S) <= pos, jnp.arange(S), -1).astype(jnp.int32)
    kpos = jnp.broadcast_to(kpos, (B, S))
    cases = [
        ("flash_decode f32 q(8,8,256) cache(8,1,2048,256)", "float32",
         jax.jit(flash_decode), jax.jit(flash_decode_ref), (q, k, v, kpos, jnp.int32(pos))),
    ]
    for dtype in (jnp.float32, jnp.bfloat16):
        qkv = tuple(normal(10 + i, (1, 8, 2048, 256), dtype) for i in range(3))
        cases.append((f"flash_attention {jnp.dtype(dtype).name} (1,8,2048,256)",
                      jnp.dtype(dtype).name, jax.jit(flash_attention), jax.jit(attention_ref), qkv))
    bad = []
    for name, dtype, kernel, ref, args in cases:
        if "tpu_custom_call" not in kernel.lower(*args).as_text():
            raise AssertionError(f"{name}: no compiled Pallas kernel in the program")
        got = np.asarray(kernel(*args).astype(jnp.float32))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref(*args).astype(jnp.float32))
        err = float(np.max(np.abs(got - want)))
        tol = KERNEL_TOL[dtype]
        ok = bool(np.all(np.abs(got - want) <= tol + tol * np.abs(want)))
        log(f"[device] {name}: max |kernel - reference| = {err!r} "
            f"(bound {tol} abs + {tol} rel): {'ok' if ok else 'FAIL'}")
        if not ok:
            bad.append(name)
    if bad:
        raise AssertionError(f"kernels off their references: {bad}")


# ------------------------------------------------------------------ plan ---
def engine_parity(spec, strategy: str, trials: dict, n_check: int) -> None:
    """Seeds 0..n_check-1 through the host CampaignEngine: survival must be
    identical and total_s within PARITY_RTOL."""
    from repro.scenarios.engine import CampaignEngine

    worst = 0.0
    for s in range(n_check):
        r = CampaignEngine(spec, strategy, seed=s).run()
        got_alive = bool(trials["survived"][s])
        if got_alive != r.survived:
            raise AssertionError(
                f"{spec.name}/{strategy} seed {s}: kernel survived={got_alive}, "
                f"engine survived={r.survived}"
            )
        if r.survived:
            got = float(trials["total_s"][s])
            rel = float(abs(got - r.total_s) / abs(r.total_s))
            worst = max(worst, rel)
            if not rel <= PARITY_RTOL:
                raise AssertionError(
                    f"{spec.name}/{strategy} seed {s}: kernel total_s={got!r}, "
                    f"engine total_s={r.total_s!r} (rel {rel!r} > {PARITY_RTOL})"
                )
    log(f"[plan] {spec.name}/{strategy}: engine parity on {n_check} seeds, "
        f"survival identical, worst rel total_s error {worst!r}")


def replay_on_device(spec, batch, strategy: str, trials: dict) -> float:
    """Run the cached replay program itself and check that its outputs live
    on the TPU and agree with what mc_trajectories returned; returns the
    kernel's warm seconds (host-to-device transfer included)."""
    import jax
    import numpy as np

    from repro.scenarios.trajectory import replay_program
    from repro.utils.backend import x64

    fn, args = replay_program(spec, batch, strategy)
    with x64(), warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="Some donated buffers were not usable")
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        kernel_s = time.perf_counter() - t0
    platforms = {d.platform for a in out.values() for d in a.devices()}
    if platforms != {PLATFORM}:
        raise AssertionError(f"{spec.name}: replay outputs live on {platforms}, not the TPU")
    for key in ("survived", "n_events", "n_handled", "n_blacklisted"):
        if not np.array_equal(np.asarray(out[key]), trials[key]):
            raise AssertionError(f"{spec.name}: {key} differs between replays")
    return kernel_s


def plan_family(clock: CompileClock, family: str, strategy: str, n_seeds: int,
                n_check: int) -> None:
    import numpy as np

    from repro.scenarios import registry
    from repro.scenarios.montecarlo import mc_trajectories
    from repro.scenarios.trajectory import compile_batch

    spec = registry.get(family)
    t0 = time.perf_counter()
    batch = compile_batch(spec, n_seeds)
    tape_s = time.perf_counter() - t0

    c0, n0 = clock.compile_s, clock.n_compiles
    t0 = time.perf_counter()
    mc = mc_trajectories(spec, strategy, batch=batch)
    first_s = time.perf_counter() - t0
    xla_s, n_xla = clock.compile_s - c0, clock.n_compiles - n0

    t0 = time.perf_counter()
    again = mc_trajectories(spec, strategy, batch=batch)
    warm_s = time.perf_counter() - t0
    for key, a in mc["trials"].items():
        if not np.array_equal(a, again["trials"][key], equal_nan=True):
            raise AssertionError(f"{family}: trial array {key} differs between two runs")
    kernel_s = replay_on_device(spec, batch, strategy, mc["trials"])
    slo = mc.get("slo")
    log(
        f"[plan] {family}/{strategy}: nodes={spec.n_nodes} seeds={n_seeds} "
        f"slots={batch.n_slots} tape_compile_s={tape_s!r} xla_compile_s={xla_s!r} "
        f"({n_xla} programs) first_call_s={first_s!r} execute_s={warm_s!r} "
        f"kernel_s={kernel_s!r} seeds_per_s={n_seeds / warm_s!r} "
        f"survival={mc['survival_rate']!r} mean_s={mc['mean_s']!r}"
        + (f" slo_p99_s={slo['p99_s']} slo_availability_mean="
           f"{slo['availability_mean']!r}" if slo else "")
    )
    engine_parity(spec, strategy, mc["trials"], n_check)


def run_plan(clock: CompileClock) -> None:
    bad = []
    for family, strategy, n_seeds, n_check in CELLS:
        try:
            plan_family(clock, family, strategy, n_seeds, n_check)
        except Exception:  # report every family before failing the phase
            traceback.print_exc()
            bad.append(f"{family}/{strategy}")
    if bad:
        raise AssertionError(f"planning failed on {bad}")


# ---------------------------------------------------------------- oracle ---
def run_oracle(clock: CompileClock) -> None:
    import math

    from repro.orchestrator.plan import choose_strategy
    from repro.scenarios import registry

    spec = registry.get("live_genome_single")
    seconds = []
    for _ in range(2):  # cold (compiles included), then warm
        c0 = clock.compile_s
        t0 = time.perf_counter()
        winner, scores = choose_strategy(spec, workload=spec.workload)
        seconds.append((time.perf_counter() - t0, clock.compile_s - c0))
    best = max(s["survival_rate"] for s in scores.values())
    if scores[winner]["survival_rate"] < best or not math.isfinite(scores[winner]["mean_s"]):
        raise AssertionError(f"oracle picked {winner} from {scores}")
    log(f"[oracle] live_genome_single: winner={winner} "
        f"cold_decision_s={seconds[0][0]!r} (xla_compile_s={seconds[0][1]!r}) "
        f"warm_decision_s={seconds[1][0]!r} scores={json.dumps(scores)}")


# ---------------------------------------------------------------- genome ---
def run_genome(clock: CompileClock) -> None:
    import numpy as np

    from repro.configs.paper_genome import CONFIG
    from repro.data.genome import GenomeSearchJob, make_genome, reverse_complement
    from repro.orchestrator.worker import GenomeProgram

    t0 = time.perf_counter()
    genome, patterns, truth = make_genome(
        CONFIG.input_bytes,
        n_patterns=GENOME_PATTERNS,
        pat_len=(CONFIG.pattern_len_min, CONFIG.pattern_len_max),
        seed=GENOME_SEED,
    )
    log(f"[genome] {genome.size} bases ({CONFIG.input_bytes} B, the paper's input), "
        f"{len(patterns)} patterns (cut from the paper's {CONFIG.n_patterns} to keep "
        f"the run short), {len(truth)} planted occurrences; built in "
        f"{time.perf_counter() - t0!r} s")

    def new_job():
        return GenomeSearchJob(genome, patterns, n_search=CONFIG.n_search_nodes,
                               chunks_per_node=GENOME_CHUNKS)

    job = new_job()
    c0 = clock.compile_s
    t0 = time.perf_counter()
    subs = [GenomeProgram(job, i) for i in range(job.n_search)]
    for sub in subs:
        for _ in range(job.chunks_per_node):
            sub.step()
    table = job.combine([sub.state for sub in subs])
    search_s = time.perf_counter() - t0
    log(f"[genome] uninterrupted: {job.n_search} sub-jobs x {job.chunks_per_node} chunks "
        f"searched and combined in {search_s!r} s (xla_compile_s="
        f"{clock.compile_s - c0!r}); {len(table)} hits")

    # sub-job 0 migrates after its first chunk: its state crosses as the
    # spool would carry it (JSON) into a fresh program on a fresh job
    t0 = time.perf_counter()
    source = GenomeProgram(job, 0)
    source.step()
    handed = json.loads(json.dumps(source.state_dict()))
    target = GenomeProgram(new_job(), 0)
    target.load_state(handed)
    while target.state["cursor"] < job.chunks_per_node:
        target.step()
    migrated = job.combine([target.state] + [sub.state for sub in subs[1:]])
    log(f"[genome] migrated at cursor {handed['cursor']}: sub-job re-run and "
        f"combined in {time.perf_counter() - t0!r} s")

    t0 = time.perf_counter()
    found = {(start, pid, strand) for _, start, _, pid, strand in table}
    missing = truth - found
    if missing:
        raise AssertionError(f"planted occurrences not found: {sorted(missing)[:5]}")
    for _, start, end, pid, strand in table:
        want = patterns[pid] if strand == "+" else reverse_complement(patterns[pid])
        if not np.array_equal(genome[start:end + 1], want):
            raise AssertionError(f"hit {(start, end, pid, strand)} does not match the genome")
    if migrated != table:
        raise AssertionError("the migrated run's table differs from the uninterrupted one")
    log(f"[genome] checks: all {len(truth)} planted occurrences found, all "
        f"{len(table)} hits verified on the host, migrated table == uninterrupted "
        f"table; {time.perf_counter() - t0!r} s")


# --------------------------------------------------------------- sharded ---
def run_sharded(clock: CompileClock, n_chips: int) -> None:
    import numpy as np

    from repro.scenarios import registry
    from repro.scenarios.montecarlo import mc_trajectories
    from repro.scenarios.trajectory import compile_batch

    spec = registry.get(SHARDED_FAMILY)
    batch = compile_batch(spec, SHARDED_SEEDS)
    trials = {}
    for n_devices in (n_chips, 1):
        c0 = clock.compile_s
        t0 = time.perf_counter()
        mc_trajectories(spec, "central_single", batch=batch, n_devices=n_devices)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        trials[n_devices] = mc_trajectories(
            spec, "central_single", batch=batch, n_devices=n_devices
        )["trials"]
        warm_s = time.perf_counter() - t0
        log(f"[sharded] {SHARDED_FAMILY}: seeds={SHARDED_SEEDS} n_devices={n_devices} "
            f"xla_compile_s={clock.compile_s - c0!r} first_call_s={first_s!r} "
            f"execute_s={warm_s!r} seeds_per_s={SHARDED_SEEDS / warm_s!r}")
    differ = [k for k, a in trials[1].items()
              if not np.array_equal(a, trials[n_chips][k], equal_nan=True)]
    if differ:
        raise AssertionError(f"{n_chips}-chip and 1-chip results differ in {differ}")
    log(f"[sharded] {n_chips}-chip and 1-chip results bitwise equal on all "
        f"{len(trials[1])} trial arrays")


# ------------------------------------------------------------------ main ---
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the seed axis sharded over four chips")
    a = ap.parse_args(argv)
    try:
        from repro.utils.backend import use_compile_cache
    except ImportError as e:
        raise SystemExit(f"the repro package was not found beside {__file__}: {e}")

    log(f"[setup] compile cache: {use_compile_cache()}")
    device = require_tpu(a.chips)
    clock = CompileClock()
    if a.chips > 1:
        phases = [("sharded", lambda: run_sharded(clock, a.chips))]
    else:
        phases = [
            ("device", check_kernels),
            ("plan", lambda: run_plan(clock)),
            ("oracle", lambda: run_oracle(clock)),
            ("genome", lambda: run_genome(clock)),
        ]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"[{name}] FAILED after {time.perf_counter() - t0!r} s")
        else:
            log(f"[{name}] passed in {time.perf_counter() - t0!r} s")
    log(f"[setup] XLA compiles: {clock.n_compiles} taking {clock.compile_s!r} s; "
        f"persistent cache hits={clock.cache_hits} misses={clock.cache_misses}")
    if failed:
        print(f"chip smoke FAILED in phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
