"""Fleet-scale replay: the tiled/sharded kernel on the 1024-node
``fleet_stress`` family.

The execution-shape knobs (``tile_slots``, ``n_devices``, ``donate``)
must never change a single bit of any replay output — padding slots are
provable no-ops (t=inf, valid=False), seed shards are independent, and
donation only recycles input storage. These property tests pin that
contract, plus the cost-table-coefficient program cache (one XLA compile
serves every strategy sharing a structural table shape) and the
compacted partition tape (width-1 placeholder when no cut opens, so the
tape stays O(events + nodes) at fleet sizes).

Engine≡kernel *trace* parity on fleet_stress × 3 strategies runs in
tier-1 via the registry-parametrized ``test_obs.py`` sweep; here we pin
the scalar/counter parity and the scaling invariances.
"""
import numpy as np
import pytest

from repro.scenarios import registry
from repro.scenarios.engine import CampaignEngine
from repro.scenarios.trajectory import (
    compile_batch,
    compile_tape,
    default_seed_devices,
    replay_batch,
    replay_cache_stats,
    replay_program,
)
from repro.core.sim import measure_micro

N_FLEET_SEEDS = 16


@pytest.fixture(scope="module")
def fleet_spec():
    return registry.get("fleet_stress")


@pytest.fixture(scope="module")
def fleet_batch(fleet_spec):
    return compile_batch(fleet_spec, N_FLEET_SEEDS)


@pytest.fixture(scope="module")
def fleet_micro(fleet_spec):
    return measure_micro("placentia", n_nodes=fleet_spec.n_nodes)


def assert_bit_identical(ref, got, ctx):
    assert set(ref) == set(got), ctx
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(got[k])
        if a.dtype.kind == "f":
            assert np.array_equal(a, b, equal_nan=True), (ctx, k)
        else:
            assert np.array_equal(a, b), (ctx, k)


# ------------------------------------------------------------ the family ---
def test_fleet_stress_registered_at_scale(fleet_spec):
    """The certification family is a real fleet: >=1k nodes, >=64 spares,
    rack-correlated bursts composed with flaky and degrade processes."""
    assert fleet_spec.n_nodes >= 1024
    assert fleet_spec.n_spares >= 64
    kinds = {p.kind for p in fleet_spec.processes}
    assert {"rack", "burst", "flaky", "degrade"} <= kinds
    assert len(set(fleet_spec.racks.values())) == 64  # 16-node racks


def test_fleet_tape_is_events_plus_nodes(fleet_spec, fleet_batch):
    """The compiled tape's working set is O(events + nodes): the slot
    axis tracks the campaign's event count, not nodes x horizon, and the
    partition component map is the width-1 placeholder (no cut opens)."""
    assert fleet_batch.n_slots < 128  # ~40 events, padded to a tile multiple
    assert fleet_batch.part_comp.shape == (N_FLEET_SEEDS, fleet_batch.n_slots, 1)
    assert (fleet_batch.part_comp == -1).all()


def test_partition_tape_compacts_only_without_cuts():
    """Families that DO open a cut keep the full [n, H] component
    timeline; everything else gets the width-1 placeholder."""
    pspec = registry.get("partition_split")
    part = compile_tape(pspec, seed=0)
    flat = compile_tape(registry.get("mc_stress"), seed=0)
    assert part.part_comp.shape[1] == pspec.n_nodes + pspec.n_spares  # full host axis
    assert flat.part_comp.shape[1] == 1


# ------------------------------------------------- scaling invariances ----
@pytest.mark.parametrize("tile_slots", [1, 64])
def test_replay_bit_identical_across_tile_sizes(
    fleet_spec, fleet_batch, fleet_micro, tile_slots
):
    """Tiling is an execution-shape knob: padding slots are no-ops, so
    totals, counters and failure times match the default tiling exactly."""
    ref = replay_batch(fleet_spec, fleet_batch, "core", micro=fleet_micro)
    got = replay_batch(
        fleet_spec, fleet_batch, "core", micro=fleet_micro, tile_slots=tile_slots
    )
    assert_bit_identical(ref, got, f"tile_slots={tile_slots}")


@pytest.mark.skipif(
    __import__("jax").local_device_count() < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)
def test_replay_bit_identical_across_device_counts(
    fleet_spec, fleet_batch, fleet_micro
):
    """Sharding the seed axis over every local device reproduces the
    single-device replay bit for bit — seeds are independent programs."""
    import jax

    n_dev = default_seed_devices(N_FLEET_SEEDS)
    assert n_dev == min(jax.local_device_count(), N_FLEET_SEEDS)
    ref = replay_batch(fleet_spec, fleet_batch, "core", micro=fleet_micro, n_devices=1)
    got = replay_batch(
        fleet_spec, fleet_batch, "core", micro=fleet_micro, n_devices=n_dev
    )
    assert_bit_identical(ref, got, f"n_devices={n_dev}")


_SHARDED_VS_ONE = """
import numpy as np
from repro.scenarios import registry
from repro.scenarios.trajectory import compile_batch, default_seed_devices, replay_batch

spec = registry.get("fleet_stress")
batch = compile_batch(spec, 8)
assert default_seed_devices(8) == 4
ref = replay_batch(spec, batch, "core", n_devices=1)
got = replay_batch(spec, batch, "core")
bad = [k for k in ref if not np.array_equal(ref[k], got[k], equal_nan=True)]
assert not bad, bad
print("bit-identical", sorted(ref))
"""


def test_sharded_replay_bit_identical_in_a_four_device_process(tmp_path):
    """The default planning call on a multi-device host shards the seed
    axis with ``jax.shard_map``: run it in a process with four forced CPU
    devices (a fresh process, since the device count is fixed at start-up)
    and require the single-device replay bit for bit."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [src, env.get("PYTHONPATH")]))
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_VS_ONE], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "bit-identical" in proc.stdout


def test_default_seed_devices_divides_seeds():
    """The helper picks the largest local-device count dividing n_seeds
    (shard_map needs an even split), never exceeding what's attached."""
    import jax

    for n_seeds in (1, 7, 16, 1000):
        d = default_seed_devices(n_seeds)
        assert 1 <= d <= jax.local_device_count()
        assert n_seeds % d == 0


# ------------------------------------------------------ engine parity -----
@pytest.mark.parametrize("strategy", ["central_single", "core"])
def test_fleet_kernel_matches_engine(fleet_spec, fleet_batch, fleet_micro, strategy):
    """Trial-for-trial engine parity holds at 1024 nodes (2 seeds per
    strategy — the engine pays seconds per fleet trial)."""
    out = replay_batch(fleet_spec, fleet_batch, strategy, micro=fleet_micro)
    for s in range(2):
        r = CampaignEngine(fleet_spec, strategy, micro=fleet_micro, seed=s).run()
        assert bool(out["survived"][s]) == r.survived
        for f in ("n_events", "n_handled", "n_migrations", "n_blacklisted"):
            assert int(out[f][s]) == getattr(r, f), (strategy, s, f)
        if r.survived:
            assert out["total_s"][s] == pytest.approx(r.total_s, rel=1e-9)


# ------------------------------------------------------- program cache ----
def test_cost_table_values_share_one_program(fleet_spec, fleet_batch, fleet_micro):
    """Cost-table *values* are traced arguments, not compile-time
    constants: replaying the same strategy under two workloads' cost
    tables (same structural flags, different numbers) must hit the
    program cache, not lower a second XLA program."""
    replay_batch(fleet_spec, fleet_batch, "central_single", workload="analytic")
    s1 = replay_cache_stats()
    out = replay_batch(fleet_spec, fleet_batch, "central_single", workload="train_llm")
    s2 = replay_cache_stats()
    assert s2["misses"] == s1["misses"], "cost-table values forced a recompile"
    assert s2["hits"] == s1["hits"] + 1
    # ...and the numbers really differ: different billing, same tapes
    base = replay_batch(fleet_spec, fleet_batch, "central_single", workload="analytic")
    assert not np.array_equal(base["total_s"], out["total_s"], equal_nan=True)


# ------------------------------------------------------------- donation ---
def test_donation_drops_peak_memory(fleet_spec, fleet_micro):
    """Donated tape buffers alias into the record-mode [seeds, slots]
    outputs, so the compiled program's peak memory drops vs donate=False
    (visible as alias_size_in_bytes > 0 in XLA's memory analysis)."""
    from repro.obs.profile import _memory_analysis
    from repro.scenarios.trajectory import _quiet_donation
    from repro.utils.backend import x64

    batch = compile_batch(fleet_spec, 8)
    peaks = {}
    for donate in (True, False):
        fn, args = replay_program(
            fleet_spec,
            batch,
            "central_single",
            micro=fleet_micro,
            record_slots=True,
            donate=donate,
            n_devices=1,  # isolate donation from shard_map's buffer layout
        )
        with x64(), _quiet_donation():
            mem = _memory_analysis(fn.lower(*args).compile())
        if mem is None:
            pytest.skip("backend exposes no memory_analysis")
        peaks[donate] = mem
    assert peaks[True]["alias_bytes"] > 0
    assert peaks[False]["alias_bytes"] == 0
    assert peaks[True]["peak_bytes"] < peaks[False]["peak_bytes"]
