"""The process-level JAX settings the repo depends on, each in one place.

:func:`x64`
    the replay kernel's float64 context. The kernel is built and invoked
    inside it so its arithmetic is the engine's float64 arithmetic.
:func:`pallas_interpret`
    whether a Pallas kernel runs in interpret mode when its caller does not
    say: on the TPU it compiles, on any other backend it interprets.
:func:`matmul_precision`
    the matmul precision a Pallas kernel asks of the TPU's matrix unit for
    its input dtype.
:func:`use_compile_cache`
    points JAX's persistent compilation cache at a fixed directory. Entry
    points call it; importing a module never does.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from pathlib import Path
from typing import Optional

#: the checkout root, located from this file (src/repro/utils/backend.py)
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]

#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


@contextmanager
def x64():
    """Trace and run with 64-bit types enabled (``jax.enable_x64``)."""
    import jax

    with jax.enable_x64(True):
        yield


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` as given, else whether the default backend is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    import jax

    return jax.default_backend() != "tpu"


def matmul_precision(dtype):
    """f32 inputs keep f32 products on the TPU's matrix unit (its default
    single pass rounds operands to bf16); bf16 inputs are exact at the
    default."""
    import jax
    import jax.numpy as jnp

    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and
    no other directory is set here. Otherwise the cache lives in
    ``.jax_cache/`` at the checkout root, a fixed path, so a later run in
    the same checkout finds what an earlier one compiled."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
