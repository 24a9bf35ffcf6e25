"""Chunked WKV6 (RWKV-6 / Finch) Pallas TPU kernel.

TPU adaptation of the per-token CUDA recurrence: the sequence is split into
chunks of L tokens; within a chunk the per-channel data-dependent decays
are applied through PAIRWISE log-decay differences (exponent always <= 0 —
numerically stable) so the intra-chunk work becomes dense MXU-friendly
matmul/broadcast ops in VMEM; the (N x N) k->v state carries across the
sequential chunk grid dimension in VMEM scratch.

Grid: (B, H, n_chunks); chunk axis iterates sequentially (minormost).
VMEM working set per step: r/k/v/w (L,N) f32 + pairwise (L,L,N) f32
(L=64, N=64 -> ~1 MB) + state (N,N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.backend import pallas_interpret


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_scr,
                 *, L, N, n_chunks):
    h = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    r = r_ref[0, 0].astype(jnp.float32)  # (L, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)  # log decay <= 0
    u = u_ref[pl.ds(h, 1), :].astype(jnp.float32)  # (1, N): this head's bonus
    S = s_scr[...]  # (N, N) k-dim -> v-dim

    # inclusive cumulative log decay as a lower-triangular matmul (Mosaic
    # has no cumsum); the 0/1 operand keeps it exact at full precision
    tri_incl = (
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    ).astype(jnp.float32)
    ld = jax.lax.dot_general(
        tri_incl, w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (L, N)
    ldm1 = ld - w  # exclusive cumulative log decay
    # pairwise decay exp(ld[t-1] - ld[s]) for s < t — exponent <= 0
    pair = ldm1[:, None, :] - ld[None, :, :]  # (Lt, Ls, N)
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L, N), 0) > jax.lax.broadcasted_iota(
        jnp.int32, (L, L, N), 1
    )
    # mask BEFORE exp: s >= t entries have positive exponents that would
    # overflow to inf (inf * 0 = nan)
    A = jnp.exp(jnp.where(tri, pair, -jnp.inf))
    # W[t,s] = sum_n r[t,n] k[s,n] A[t,s,n]
    Wts = jnp.sum(r[:, None, :] * k[None, :, :] * A, axis=-1)  # (L, L)
    y = jax.lax.dot_general(Wts, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # diagonal bonus term u
    du = jnp.sum(r * k * u, axis=-1)  # (L,)
    y = y + du[:, None] * v
    # cross-chunk: (r * exp(ldm1)) @ S
    y = y + jax.lax.dot_general(r * jnp.exp(ldm1), S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # state update: S' = diag(exp(ld_L)) S + (k * exp(ld_L - ld))^T @ v,
    # the diagonal scaling as a matmul so the decay row needs no transpose
    ld_last = ld[L - 1 : L, :]  # (1, N)
    eye = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0) == jax.lax.broadcasted_iota(
        jnp.int32, (N, N), 1
    )
    decay_diag = jnp.where(eye, jnp.exp(ld_last), 0.0)  # (N, N)
    kscale = k * jnp.exp(ld_last - ld)  # (L, N), exponent <= 0
    S_new = jax.lax.dot_general(
        decay_diag, S, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        kscale, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_scr[...] = S_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c == n_chunks - 1)
    def _final():
        sT_ref[0, 0] = S_new.astype(sT_ref.dtype)


def wkv6(r, k, v, wlog, u, state, *, chunk=64, interpret=None):
    """r/k/v/wlog: (B, H, S, N); u: (H, N); state: (B, H, N, N) float32.
    ``interpret=None`` compiles on the TPU and interprets elsewhere.

    Returns (y (B,H,S,N), final state (B,H,N,N))."""
    B, H, S, N = r.shape
    L = min(chunk, S)
    while S % L:
        L //= 2
    n_chunks = S // L
    kernel = functools.partial(_wkv6_kernel, L=L, N=N, n_chunks=n_chunks)
    y, sT = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((H, N), lambda b, h, c: (0, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(r.shape, r.dtype),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(r, k, v, wlog, u, state)
    return y, sT
