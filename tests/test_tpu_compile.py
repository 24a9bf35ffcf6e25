"""Compile-only checks against a described TPU v5e: the Pallas kernels at
real widths and the replay program, lowered and compiled by the chip's own
compiler with no chip attached. Nothing runs, so these say nothing about
results or times; they catch what interpret mode cannot (tiling rules,
primitives Mosaic does not lower, programs that do not fit).

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs this file
loads the TPU library."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_attention(dtype):
    from repro.kernels.flash_attention import flash_attention

    shape = ((1, 8, 2048, 256), dtype)
    return lambda q, k, v: flash_attention(q, k, v, interpret=False), [shape] * 3


def _flash_decode():
    from repro.kernels.decode_attention import flash_decode

    cache = ((8, 1, 2048, 256), jnp.float32)
    args = [((8, 8, 256), jnp.float32), cache, cache, ((8, 2048), jnp.int32), ((), jnp.int32)]
    return lambda q, k, v, kpos, pos: flash_decode(q, k, v, kpos, pos, interpret=False), args


def _wkv6():
    from repro.kernels.rwkv6 import wkv6

    seq = ((1, 32, 2048, 64), jnp.float32)
    args = [seq] * 4 + [((32, 64), jnp.float32), ((1, 32, 64, 64), jnp.float32)]
    return lambda r, k, v, w, u, s: wkv6(r, k, v, w, u, s, interpret=False), args


def _rglru():
    from repro.kernels.rglru import rglru_scan

    seq = ((1, 2048, 4096), jnp.float32)
    return lambda a, m, h: rglru_scan(a, m, h, interpret=False), [seq, seq, ((1, 4096), jnp.float32)]


def _rmsnorm():
    from repro.kernels.rmsnorm import rmsnorm

    args = [((4096, 2048), jnp.bfloat16), ((2048,), jnp.bfloat16)]
    return lambda x, s: rmsnorm(x, s, interpret=False), args


KERNEL_CASES = {
    "flash_attention_bf16": lambda: _flash_attention(jnp.bfloat16),
    "flash_attention_f32": lambda: _flash_attention(jnp.float32),
    "flash_decode": _flash_decode,
    "wkv6": _wkv6,
    "rglru_scan": _rglru,
    "rmsnorm": _rmsnorm,
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, args = KERNEL_CASES[case]()
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in the program"


def test_replay_program_compiles_for_v5e(one_chip):
    """The mc_stress replay (256 seeds, float64 scan) on one chip."""
    import numpy as np

    from repro.scenarios import registry
    from repro.scenarios.trajectory import _quiet_donation, compile_batch, replay_program
    from repro.utils.backend import x64

    spec = registry.get("mc_stress")
    batch = compile_batch(spec, 256)
    fn, args = replay_program(spec, batch, "central_single", n_devices=1)
    with x64(), _quiet_donation():
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=one_chip),
            args,
        )
        compiled = fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    assert mem is None or mem.temp_size_in_bytes < 16 * 2**30


@pytest.fixture(scope="module")
def table2_program(one_chip):
    """table2_random's replay (1024 seeds, 6 hosts) compiled for one chip."""
    import numpy as np

    from repro.scenarios import registry
    from repro.scenarios.trajectory import _quiet_donation, compile_batch, replay_program
    from repro.utils.backend import x64

    spec = registry.get("table2_random")
    batch = compile_batch(spec, 1024)
    fn, args = replay_program(spec, batch, "central_single", n_devices=1)
    with x64(), _quiet_donation():
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=one_chip),
            args,
        )
        return fn.lower(*shapes).compile().as_text()


def test_replay_program_keeps_float64_for_v5e(table2_program):
    """No value of the float64 scan is narrowed to bf16. A float64 spare
    pool's argmin was lowered with bf16 value accumulators; the int32 pool
    reduces in s32."""
    assert "bf16" not in table2_program


def test_replay_program_scatters_no_bool_for_v5e(table2_program):
    """No per-host write is a scatter into a bool carry: on a TPU v5e a
    vmapped scatter into a bool array drops some seeds' writes, which kept
    table2_random campaigns alive that the engine strands."""
    import re

    assert not re.search(r"pred\[[0-9,]*\]\S* scatter\(", table2_program)
