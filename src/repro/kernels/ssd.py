"""Mamba2 SSD (state-space dual) chunked-scan Pallas TPU kernel.

One grid cell owns a (batch, head) pair; the chunk axis is the innermost
*sequential* grid dimension, so the [P, N] recurrent state stays resident in
VMEM scratch across chunks (the inter-tile dependence of the EDT view is a
VMEM-resident carry, not an HBM round trip).

Within a chunk of length C the kernel evaluates the quadratic "dual" form:
    y = ((C_mat @ B_mat^T) ⊙ decay) @ (dt ⊙ x)  +  decay_in ⊙ (C_mat @ state)
which is two (C×N)(N×C) / (C×C)(C×P) MXU matmuls instead of C rank-1 updates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, A_ref, b_ref, c_ref, s0_ref,
            y_ref, sf_ref, state_ref, *, chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    A = A_ref[0].astype(jnp.float32)                       # scalar decay rate
    dt = dt_ref[0, :, 0].astype(jnp.float32)               # [C]
    x = x_ref[0, :, 0, :].astype(jnp.float32)              # [C, P]
    Bm = b_ref[0].astype(jnp.float32)                      # [C, N]
    Cm = c_ref[0].astype(jnp.float32)                      # [C, N]

    dA = dt * A                                            # [C] (<= 0)
    cums = jnp.cumsum(dA)                                  # [C]
    seg = jnp.exp(cums)                                    # decay from chunk start

    # inter-chunk: y_state[t] = seg[t] * C[t] . state
    y_state = seg[:, None] * jax.lax.dot_general(
        Cm, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [C, P]

    # intra-chunk quadratic form
    rel = cums[:, None] - cums[None, :]                    # [C, C]
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(iota_r >= iota_c, jnp.exp(rel), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * decay
    y_intra = jax.lax.dot_general(scores, dt[:, None] * x,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0, :, 0, :] = (y_state + y_intra).astype(y_ref.dtype)

    # state update: state = exp(cums[-1]) * state + sum_t w_t dt_t x_t B_t^T
    w = jnp.exp(cums[-1] - cums)                           # decay t..chunk end
    xw = (dt * w)[:, None] * x                             # [C, P]
    state_ref[...] = jnp.exp(cums[-1]) * state_ref[...] + jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [P, N]

    @pl.when(ic == nc - 1)
    def _fin():
        sf_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, Bm, Cm, init_state=None, *, chunk: int = 128,
               interpret: bool = False):
    """x [B,S,H,P], dt [B,S,H], A [H], Bm/Cm [B,S,N] -> (y, final_state)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), jnp.float32)

    kern = functools.partial(_kernel, chunk=chunk, nc=nc)
    y, sf = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, Bm, Cm, init_state)
    return y, sf
