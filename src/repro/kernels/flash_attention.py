"""Flash attention Pallas TPU kernel (causal, GQA-aware).

TPU adaptation notes (vs the CUDA original):
  * tiles are MXU-aligned: BQ × D and BK × D with D padded to 128 lanes;
  * the KV dimension is the *innermost, sequential* grid axis so the f32
    accumulators (m, l, acc) live in VMEM scratch across KV steps — the TPU
    equivalent of a CUDA thread-block's shared-memory accumulators;
  * causal blocks above the diagonal are skipped with ``pl.when`` (the grid
    still visits them; skipping the compute keeps the MXU idle time minimal).

Layouts: q [B, H, Sq, D], k/v [B, Hkv, Skv, D] — head-major so a block is a
contiguous (BQ, D) tile per (batch, head).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # block-level skip: no keys in this block can be visible
        run = (ik * bk) <= (iq * bq + bq - 1)

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [BQ, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)          # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        lsum = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / lsum[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_hm(q, k, v, *, causal: bool = True, bq: int = 128,
                       bk: int = 128, interpret: bool = False):
    """Head-major flash attention: q [B,H,Sq,D], k/v [B,Hkv,Skv,D]."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = H // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    scale = 1.0 / math.sqrt(D)

    grid = (B, H, nq, nk)
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m
            pltpu.VMEM((bq,), jnp.float32),       # l
            pltpu.VMEM((bq, D), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
