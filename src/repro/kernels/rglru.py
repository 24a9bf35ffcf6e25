"""RG-LRU linear-recurrence Pallas TPU kernel (RecurrentGemma / Griffin).

TPU adaptation: channels are tiled across the 128-lane vector unit (width
blocks), the sequence is tiled into chunks; within a chunk a fori_loop
performs the per-channel recurrence h = a*h + m as vector FMAs over the
width lanes, and the carry h persists across the sequential chunk grid
axis in VMEM scratch. Rows are read and written through the refs with
``pl.ds`` (Mosaic cannot slice a loaded value by a traced index). No exp of
positive sums anywhere — stable for
arbitrary sequence lengths (the long_500k serving path).

Grid: (B, n_width_blocks, n_seq_chunks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.backend import pallas_interpret


def _rglru_kernel(loga_ref, m_ref, h0_ref, y_ref, hT_ref, h_scr, *, L, Wb, n_chunks):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    def body(t, h):  # h: (1, Wb); rows are read and written through the refs
        row = pl.ds(t, 1)
        a = jnp.exp(loga_ref[0, row, :].astype(jnp.float32))
        h = a * h + m_ref[0, row, :].astype(jnp.float32)
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, L, body, h_scr[...])
    h_scr[...] = h

    @pl.when(c == n_chunks - 1)
    def _final():
        hT_ref[0] = h.astype(hT_ref.dtype)


def rglru_scan(log_a, m, h0, *, chunk=128, block_w=128, interpret=None):
    """log_a, m: (B, S, W); h0: (B, W) float32. h_t = exp(log_a_t) h_{t-1} + m_t.
    ``interpret=None`` compiles on the TPU and interprets elsewhere.

    Returns (h_seq (B,S,W), h_final (B,W))."""
    B, S, W = log_a.shape
    L = min(chunk, S)
    while S % L:
        L //= 2
    Wb = min(block_w, W)
    while W % Wb:
        Wb //= 2
    n_chunks = S // L
    kernel = functools.partial(_rglru_kernel, L=L, Wb=Wb, n_chunks=n_chunks)
    # the carry travels as (B, 1, W) so its (1, Wb) block is a full
    # second-minor dimension under the TPU's (8, 128) tiling rule
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, W // Wb, n_chunks),
        in_specs=[
            pl.BlockSpec((1, L, Wb), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, L, Wb), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, 1, Wb), lambda b, w, c: (b, 0, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, Wb), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, 1, Wb), lambda b, w, c: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(log_a.shape, jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, Wb), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(log_a, m, h0.reshape(B, 1, W))
    return y, hT.reshape(B, W)
