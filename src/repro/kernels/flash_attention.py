"""Blocked flash attention Pallas TPU kernel (causal / sliding-window, GQA).

TPU adaptation: q/k/v blocks are tiled into VMEM with MXU-aligned block
shapes (block_q x head_dim and block_k x head_dim, 128-multiples); the
online-softmax running max/sum and the output accumulator live in VMEM
scratch and persist across the sequential k-block grid dimension (the
minormost grid axis iterates sequentially on TPU). Eliminates the HBM
materialisation of the (S, S) score tensor that dominates the XLA path's
memory roofline term (see EXPERIMENTS.md §Perf).

Grid: (batch, q_heads, n_q_blocks, n_k_blocks); GQA maps q-head h to kv
head h // (H // K) inside the k/v index_maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.backend import matmul_precision, pallas_interpret

NEG_INF = -1.0e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_q, block_k, n_kb, causal, window, scale, precision,
):
    kb = pl.program_id(3)
    qb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, hd)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    ) * scale  # (bq, bk)

    qpos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kb == n_kb - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention(
    q, k, v, *, causal=True, window=0, block_q=128, block_k=128, interpret=None
):
    """q: (B, H, S, hd); k/v: (B, K, S, hd) with H % K == 0. Returns (B,H,S,hd).
    ``interpret=None`` compiles on the TPU and interprets elsewhere.

    block sizes must divide S (pick S-sized blocks for short sequences)."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    g = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    n_qb, n_kb = S // block_q, S // block_k
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        n_kb=n_kb,
        causal=causal,
        window=window,
        scale=scale,
        precision=matmul_precision(q.dtype),
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=pallas_interpret(interpret),
    )(q, k, v)
