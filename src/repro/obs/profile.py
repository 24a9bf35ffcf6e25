"""Profiling hooks: one wall-clock timing idiom for the whole repo.

Every measured number the repo reports — speedup certs, calibrated
workload surfaces, bench section timings — used to be an ad-hoc
``time.perf_counter()`` pair, each with its own (often missing) warmup
and ``block_until_ready`` handling. This module is the single home for
that idiom:

:func:`stopwatch` / :func:`now_s`
    the primitive perf-counter pair as a context manager
    (``utils.timing`` re-exports these, so existing callers keep
    working);
:func:`timed`
    measure a callable properly: warmup iterations first (jit compiles,
    caches fill), ``jax.block_until_ready`` on the result of every timed
    iteration (async dispatch never leaks into a measurement), and a
    :class:`Timed` record with mean/min/total;
:func:`profile_replay`
    the vmapped replay kernel's compile-vs-execute split via the jit AOT
    path (``fn.lower() -> .compile() -> execute``), plus the headline
    seeds/sec throughput metric — the number the ROADMAP's fleet-scale
    item budgets against;
:func:`time_pallas_kernel` / :func:`kernel_step_surface`
    measured per-shard-count step-time surfaces for the Pallas kernels
    in ``kernels/`` — the *measured* counterpart of the analytic
    surfaces in ``workloads/builtin.py`` (interpret mode on CPU,
    compiled on TPU; the backend is recorded next to every number so a
    CPU-interpret figure is never mistaken for a TPU one).

Pass ``trace_dir=`` to :func:`profile_replay` to additionally capture a
``jax.profiler`` trace of the execute phase (viewable in
TensorBoard/Perfetto); the hook is inert by default so profiling stays
zero-overhead when unused.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple


def now_s() -> float:
    """The repo's one wall-clock: ``time.perf_counter()``."""
    return time.perf_counter()


class _Elapsed:
    """Mutable elapsed-seconds cell filled when a stopwatch block exits."""

    __slots__ = ("s",)

    def __init__(self):
        self.s = 0.0


@contextmanager
def stopwatch():
    """``with stopwatch() as sw: ... ; use sw.s`` — the perf-counter pair."""
    sw = _Elapsed()
    t0 = time.perf_counter()
    try:
        yield sw
    finally:
        sw.s = time.perf_counter() - t0


@dataclass
class Timed:
    """One properly-measured callable: warmed up, synchronised, repeated."""

    name: str
    n: int
    warmup: int
    times_s: List[float] = field(default_factory=list)
    result: object = None  # last iteration's (blocked) return value

    @property
    def mean_s(self) -> float:
        return sum(self.times_s) / len(self.times_s) if self.times_s else 0.0

    @property
    def min_s(self) -> float:
        return min(self.times_s) if self.times_s else 0.0

    @property
    def total_s(self) -> float:
        return sum(self.times_s)

    def to_dict(self) -> Dict:
        return {
            "name": self.name,
            "n": self.n,
            "warmup": self.warmup,
            "mean_s": self.mean_s,
            "min_s": self.min_s,
        }


def timed(
    fn: Callable,
    *args,
    n: int = 3,
    warmup: int = 1,
    block: bool = True,
    name: Optional[str] = None,
    **kwargs,
) -> Timed:
    """Measure ``fn(*args, **kwargs)``: ``warmup`` unrecorded calls (jit
    compilation, lru caches), then ``n`` timed calls, each synchronised
    via ``jax.block_until_ready`` on the result when ``block``."""
    import jax

    out = Timed(name=name or getattr(fn, "__name__", "fn"), n=n, warmup=warmup)
    for _ in range(warmup):
        r = fn(*args, **kwargs)
        if block:
            jax.block_until_ready(r)
    for _ in range(n):
        with stopwatch() as sw:
            r = fn(*args, **kwargs)
            if block:
                r = jax.block_until_ready(r)
        out.times_s.append(sw.s)
        out.result = r
    return out


# ======================================================================
# The vmapped replay kernel: compile-vs-execute split + seeds/sec
# ======================================================================
def _memory_analysis(compiled) -> Optional[Dict]:
    """Peak-memory breakdown of a compiled replay program, when the
    backend exposes ``memory_analysis`` (CPU/TPU do; absent → None)."""
    try:
        ma = compiled.memory_analysis()
        alias = int(getattr(ma, "alias_size_in_bytes", 0))
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            # bytes of donated inputs XLA aliased into outputs — these are
            # NOT double-counted at peak, so donation shrinks peak_bytes
            "alias_bytes": alias,
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
            "peak_bytes": int(
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - alias
            ),
        }
    except Exception:  # pragma: no cover - backend without the API
        return None


def profile_replay(
    spec,
    strategy,
    n_seeds: int = 256,
    *,
    micro=None,
    profile: str = "placentia",
    placement: Optional[str] = None,
    detector="oracle",
    workload=None,
    n_exec: int = 3,
    trace_dir: Optional[str] = None,
    tile_slots: int = 8,
    n_devices: Optional[int] = None,
    donate: bool = True,
    record_slots: bool = False,
) -> Dict:
    """Profile one family × strategy through the batched replay path.

    Splits the wall-clock into the phases that matter for scaling:

    ``tape_compile_s``   the Python trajectory compiler (per-seed tapes)
    ``lower_s``          jax tracing (``jit(fn).lower``)
    ``compile_s``        XLA compilation of the lowered program
    ``execute_s``        steady-state execution (mean of ``n_exec`` runs,
                         synchronised), i.e. the marginal cost of more
                         Monte-Carlo — and ``seeds_per_s`` derived from it

    ``tile_slots`` / ``n_devices`` profile the tile/shard execution shape
    (results are bit-identical across both; only the cost moves), and
    ``memory`` carries the compiled program's argument/output/temp
    byte split so donation savings are observable. ``trace_dir`` wraps
    the execute phase in ``jax.profiler.trace`` so the op-level timeline
    can be opened in TensorBoard/Perfetto."""
    import jax

    from repro.scenarios.trajectory import _quiet_donation, compile_batch, replay_program
    from repro.utils.backend import x64

    with stopwatch() as sw_tape:
        batch = compile_batch(spec, n_seeds)
    fn, args = replay_program(
        spec,
        batch,
        strategy,
        micro=micro,
        profile=profile,
        placement=placement,
        detector=detector,
        workload=workload,
        tile_slots=tile_slots,
        n_devices=n_devices,
        donate=donate,
        record_slots=record_slots,
    )
    with x64(), _quiet_donation():
        with stopwatch() as sw_lower:
            lowered = fn.lower(*args)
        with stopwatch() as sw_compile:
            compiled = lowered.compile()
        memory = _memory_analysis(compiled)
        compiled(*args)  # warm-up: first dispatch pays transfers
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
        try:
            t_exec = timed(compiled, *args, n=n_exec, warmup=0, name="replay_exec")
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
    exec_s = t_exec.mean_s
    return {
        "family": spec.name,
        "strategy": getattr(strategy, "name", str(strategy)),
        "n_seeds": int(n_seeds),
        "n_slots": int(batch.n_slots),
        "backend": jax.default_backend(),
        "n_devices": int(n_devices or 1),
        "tile_slots": int(tile_slots),
        "donate": bool(donate),
        "tape_compile_s": round(sw_tape.s, 5),
        "lower_s": round(sw_lower.s, 5),
        "compile_s": round(sw_compile.s, 5),
        "execute_s": round(exec_s, 6),
        "seeds_per_s": round(n_seeds / max(exec_s, 1e-9), 1),
        "compile_over_execute": round((sw_lower.s + sw_compile.s) / max(exec_s, 1e-9), 1),
        "memory": memory,
        "trace_dir": trace_dir,
    }


# ======================================================================
# Pallas kernels: measured per-shard-count step surfaces
# ======================================================================
#: kernel name -> builder(shape kwargs) returning (fn, args) to time
def _decode_case(batch: int, seq_len: int, heads: int, head_dim: int, impl: str):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention import flash_decode, flash_decode_ref

    rng = np.random.default_rng(0)
    # Pallas decode kernels are natively f32 — not replay-kernel state
    q = jnp.asarray(rng.standard_normal((batch, heads, head_dim)), jnp.float32)  # repro: ignore[dtype-x64]
    k = jnp.asarray(rng.standard_normal((batch, heads, seq_len, head_dim)), jnp.float32)  # repro: ignore[dtype-x64]
    v = jnp.asarray(rng.standard_normal((batch, heads, seq_len, head_dim)), jnp.float32)  # repro: ignore[dtype-x64]
    kpos = jnp.tile(jnp.arange(seq_len, dtype=jnp.int32), (batch, 1))
    pos = seq_len - 1  # scalar decode position (the cache is full)
    if impl == "pallas":
        return lambda: flash_decode(q, k, v, kpos, pos, block_k=128)
    return lambda: flash_decode_ref(q, k, v, kpos, pos)


def _attention_case(batch: int, seq_len: int, heads: int, head_dim: int, impl: str):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import attention

    rng = np.random.default_rng(0)
    shape = (batch, heads, seq_len, head_dim)
    # Pallas attention kernels are natively f32 — not replay-kernel state
    q = jnp.asarray(rng.standard_normal(shape), jnp.float32)  # repro: ignore[dtype-x64]
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)  # repro: ignore[dtype-x64]
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)  # repro: ignore[dtype-x64]
    return lambda: attention(q, k, v, causal=True, impl=impl)


_KERNEL_CASES = {
    "decode_attention": _decode_case,
    "flash_attention": _attention_case,
}


def time_pallas_kernel(
    kernel: str,
    *,
    n_shards: Sequence[int] = (1, 2, 4),
    batch: int = 8,
    seq_len: int = 256,
    heads: int = 4,
    head_dim: int = 64,
    impl: str = "pallas",
    n: int = 2,
    warmup: int = 1,
) -> Dict:
    """Time one ``kernels/`` entry point per shard count.

    Sharding splits the batch (decode: also the per-shard cache slice
    stays whole — each shard serves ``batch / n`` sessions), so the
    measured curve is the per-shard step time a fleet of ``n`` would
    see. On CPU the Pallas path runs in interpret mode — orders of
    magnitude slower than compiled TPU — so ``backend`` travels with
    the numbers and callers must not compare across backends."""
    import jax

    if kernel not in _KERNEL_CASES:
        raise ValueError(f"unknown kernel {kernel!r}; one of {tuple(_KERNEL_CASES)}")
    times = []
    for ns in n_shards:
        b = max(batch // int(ns), 1)
        fn = _KERNEL_CASES[kernel](b, seq_len, heads, head_dim, impl)
        times.append(round(timed(fn, n=n, warmup=warmup).min_s, 6))
    return {
        "kernel": kernel,
        "impl": impl,
        "backend": jax.default_backend(),
        "batch": batch,
        "seq_len": seq_len,
        "heads": heads,
        "head_dim": head_dim,
        "n_shards": [int(x) for x in n_shards],
        "step_time_s": times,
    }


def kernel_step_surface(
    workload: str,
    n_shards: Sequence[int] = (1, 2, 4),
    **shape,
) -> Optional[Dict]:
    """The measured step-time surface for a workload's kernel hot path —
    the wall-clock sibling of the analytic ``step_time_s`` tuples in
    ``workloads/builtin.py`` (``serve_decode`` → the flash-decode
    kernel, ``train_llm`` → the flash-attention kernel). Returns None
    for workloads with no kernel hot path (``analytic``,
    ``genome_search`` time their own jit in calibration)."""
    kernel = {"serve_decode": "decode_attention", "train_llm": "flash_attention"}.get(
        workload
    )
    if kernel is None:
        return None
    out = time_pallas_kernel(kernel, n_shards=n_shards, **shape)
    out["workload"] = workload
    return out
