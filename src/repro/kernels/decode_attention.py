"""Flash-decode Pallas TPU kernel: one query token against a long KV cache.

The decode roofline is memory-bound on reading the cache; the kernel
streams (block_k x hd) cache tiles through VMEM with the online-softmax
state in scratch — one pass over K and V, fp32 accumulation, ring-buffer
validity via the kpos array (matching the model's cache semantics:
kpos >= 0, kpos <= pos, and optionally kpos > pos - window).

Grid: (batch, kv_heads, n_k_blocks); each step scores the kv head's whole
query group (the H // K query heads that share it) against one cache tile,
so the query block is (group, hd) and meets the TPU's (8, 128) tiling rule
as a full dimension. The k-block axis iterates sequentially.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.backend import matmul_precision, pallas_interpret

NEG_INF = -1.0e30


def _decode_kernel(
    q_ref, k_ref, v_ref, kpos_ref, pos_ref, o_ref, m_scr, l_scr, acc_scr,
    *, n_kb, window, scale, precision,
):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (g, hd): the kv head's query group
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    kpos = kpos_ref[0]  # (1, bk) int32
    pos = pos_ref[0]  # scalar int32

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    ) * scale  # (g, bk)
    valid = (kpos >= 0) & (kpos <= pos)
    if window:
        valid = valid & (kpos > pos - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]  # (g, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(kb == n_kb - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode(q, k, v, kpos, pos, *, window=0, block_k=512, interpret=None):
    """q: (B, H, hd) one token per row; k/v: (B, K, S, hd) cache;
    kpos: (B, S) int32 cache positions (-1 = empty); pos: scalar int32.
    ``interpret=None`` compiles on the TPU and interprets elsewhere.

    Returns (B, H, hd)."""
    B, H, hd = q.shape
    K = k.shape[1]
    S = k.shape[2]
    g = H // K
    bk = min(block_k, S)
    while S % bk:
        bk //= 2
    n_kb = S // bk
    scale = 1.0 / (hd ** 0.5)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (1,))

    kernel = functools.partial(
        _decode_kernel, n_kb=n_kb, window=window, scale=scale,
        precision=matmul_precision(q.dtype),
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, K, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, j: (b, 0, j)),
            pl.BlockSpec((1,), lambda b, h, j: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        interpret=pallas_interpret(interpret),
    )(q.reshape(B, K, g, hd), k, v, kpos.reshape(B, 1, S), pos_arr)
    return out.reshape(B, H, hd)


def flash_decode_ref(q, k, v, kpos, pos, *, window=0):
    """Oracle: masked full softmax over the cache."""
    B, H, hd = q.shape
    K = k.shape[1]
    g = H // K
    kk = jnp.repeat(k, g, axis=1).astype(jnp.float32)  # (B,H,S,hd)
    vv = jnp.repeat(v, g, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), kk) / (hd ** 0.5)
    valid = (kpos >= 0) & (kpos <= pos)
    if window:
        valid = valid & (kpos > pos - window)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vv).astype(q.dtype)
