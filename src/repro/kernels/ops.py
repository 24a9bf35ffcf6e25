"""Jit'd dispatch wrappers for the Pallas kernels.

``impl="pallas"`` runs the real kernels (interpret mode on CPU, compiled on
TPU); ``impl="xla"`` runs the reference math. The model layer calls these
through its ``attention_impl`` config; the 512-device dry-run uses the XLA
path (Pallas TPU kernels do not lower on the CPU backend — see DESIGN.md §7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru import rglru_scan as rglru_pallas
from repro.kernels.rwkv6 import wkv6 as wkv6_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl"))
def attention(q, k, v, *, causal=True, window=0, impl="pallas"):
    if impl == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window)
    return ref.attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("impl",))
def wkv6(r, k, v, wlog, u, state, *, impl="pallas"):
    if impl == "pallas":
        return wkv6_pallas(r, k, v, wlog, u, state)
    return ref.wkv6_ref(r, k, v, wlog, u, state)


@functools.partial(jax.jit, static_argnames=("impl",))
def rglru(log_a, m, h0, *, impl="pallas"):
    if impl == "pallas":
        return rglru_pallas(log_a, m, h0)
    return ref.rglru_ref(log_a, m, h0)
