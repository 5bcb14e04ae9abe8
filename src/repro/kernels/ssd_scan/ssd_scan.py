"""Chunked SSD (Mamba-2) Pallas TPU kernel.

State-space duality: within a chunk of length L, the output is a masked
quadratic form (MXU matmuls); across chunks an (P, N) state is carried
sequentially.  Grid: (B, H, nc) with the chunk dimension sequential and the
state living in VMEM scratch — the TPU-native layout for SSD: chunk-local
matmuls hit the MXU, the O(T/L) carry is the only sequential dependency.

Per-step VMEM working set (L=256, P=64, N=128 fp32):
    x (L,P) + B,C (L,N) + decay (L,L) + state (P,N)  ≈ 0.6 MB.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
            state_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)     # (L,)
    A = a_ref[0].astype(jnp.float32)             # scalar (per head)
    Bm = b_ref[0, 0].astype(jnp.float32)         # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (L, N)

    dA = dt * A                                  # (L,)
    cs = jnp.cumsum(dA)                          # (L,)

    # intra-chunk: y_diag = tril(C Bᵀ ⊙ exp(segsum)) · (dt ⊙ x)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (L,L)
    seg = cs[:, None] - cs[None, :]
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(lj <= li, jnp.exp(seg), 0.0)
    W = scores * decay * dt[None, :]
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())))          # (L,P)

    # inter-chunk: y += (C · stateᵀ) ⊙ exp(cs)
    y += jax.lax.dot_general(Cm, state_ref[...],
                             (((1,), (1,)), ((), ()))) * jnp.exp(cs)[:, None]

    # state ← state·exp(cs[-1]) + xᵀ · (B ⊙ (exp(cs[-1]-cs)·dt))
    w_state = (jnp.exp(cs[-1] - cs) * dt)[:, None] * Bm              # (L,N)
    upd = jax.lax.dot_general(x, w_state, (((0,), (0,)), ((), ())))  # (P,N)
    state_ref[...] = state_ref[...] * jnp.exp(cs[-1]) + upd

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit_state():
        state_out_ref[0, 0] = state_ref[...].astype(state_out_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, chunk: int, *,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B,T,H,P); dt: (B,T,H); A: (H,); Bm/Cm: (B,T,N) — B/C shared
    across heads (single SSM group).  Returns (y (B,T,H,P), state (B,H,P,N))."""
    Bsz, T, H, P = x.shape
    N = Bm.shape[-1]
    assert T % chunk == 0, "pad T to a chunk multiple first"
    nc = T // chunk

    # kernel-native layouts
    xk = x.transpose(0, 2, 1, 3).reshape(Bsz, H, nc, chunk, P)
    dtk = dt.transpose(0, 2, 1).reshape(Bsz, H, nc, chunk)
    bk = Bm.reshape(Bsz, nc, chunk, N)
    ck = Cm.reshape(Bsz, nc, chunk, N)

    kernel = functools.partial(_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xk, dtk, A, bk, ck)
    y = y.reshape(Bsz, H, T, P).transpose(0, 2, 1, 3)
    return y, state
