"""RWKV6 (WKV) recurrence Pallas TPU kernel.

The recurrence
    out[t] = r_t . (S + u * k_t v_t^T);   S <- diag(w_t) S + k_t v_t^T
carries a [D, D] state per (batch, head).  TPU mapping:

  * grid = (B, H, S/C): chunks of the time axis are the innermost
    *sequential* axis; the state matrix lives in VMEM scratch across chunks
    (HBM traffic is O(S·D) for the streams, state never leaves VMEM);
  * within a chunk, a fori_loop of rank-1 updates runs on the VPU; D=64
    lanes fit one vreg row, so the [D, D] outer product is a single
    broadcast-multiply.

This is the paper-style "task body" for the attention-free arch: sequence
chunks are the EDT tiles, the state hand-off is the inter-tile dependence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
            o_ref, sf_ref, state_ref, *, chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                 # [D]

    def step(t, _):
        rt = r_ref[0, t, 0, :].astype(jnp.float32)   # [D]
        kt = k_ref[0, t, 0, :].astype(jnp.float32)
        vt = v_ref[0, t, 0, :].astype(jnp.float32)
        wt = w_ref[0, t, 0, :].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]               # [D, D]
        out = jnp.einsum("d,de->e", rt, state_ref[...] + u[:, None] * kv)
        o_ref[0, t, 0, :] = out.astype(o_ref.dtype)
        state_ref[...] = wt[:, None] * state_ref[...] + kv
        return ()

    jax.lax.fori_loop(0, chunk, step, ())

    @pl.when(ic == nc - 1)
    def _fin():
        sf_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, init_state=None, *, chunk: int = 64,
                interpret: bool = False):
    """r,k,v,w: [B,S,H,D]; u: [H,D]; init_state [B,H,D,D] (f32) optional."""
    B, S, H, D = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    if init_state is None:
        init_state = jnp.zeros((B, H, D, D), jnp.float32)

    kern = functools.partial(_kernel, chunk=chunk, nc=nc)
    out, sf = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, D), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, D), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, D), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, D), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, D), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, D), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, D), r.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, init_state)
    return out, sf
