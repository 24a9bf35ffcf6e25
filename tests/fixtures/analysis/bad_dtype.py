"""Fixture: dtype-less constructor in a module that manages the
x64 context — the f32 default would shear off the engine's
f64 the moment the array is built outside the context."""
import jax.numpy as jnp

from repro.utils.backend import x64


def build_state(n_hosts):
    with x64():
        return jnp.zeros(n_hosts)  # dtype-x64 violation
