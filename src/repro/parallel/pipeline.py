"""Pipeline parallelism scheduled by the paper's polyhedral EDT machinery.

The (microbatch m, stage s) iteration space and its dependences
    (m, s) -> (m, s+1)    activation flow
    (m, s) -> (m+1, s)    stage occupancy
form a polyhedral program (``repro.core.programs.pipeline``).  We:

  1. tile the microbatch axis with the §3 *compression* method (never
     projection) to get the tile-level task graph,
  2. synthesize the wavefront schedule t(mT, s) = mT + s from the graph
     (closed form exists because the distances are uniform; the materialized
     wavefronts are asserted equal — the EDT view *is* the schedule),
  3. lower to XLA: shard_map over a 'stage' mesh axis, one `fori_loop` step
     per wavefront, `ppermute` for the (m,s)->(m,s+1) dependence.  The
     (m,s)->(m+1,s) dependence is satisfied by program order inside the
     loop — zero runtime synchronization objects (Table 2's limit point).

Training: differentiate straight through the pipelined forward — the VJP of
`ppermute` is the reverse permute, so the backward pass is the mirrored
wavefront (1F1B-family schedule) with no hand-written send/recv.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.edt import TiledTaskGraph, synthesize
from ..core.poly import Tiling
from ..core.programs import pipeline as pipeline_program

PyTree = Any


@dataclass
class PipelineSchedule:
    n_stages: int
    n_tiles: int           # microbatch tiles (after tiling by tile_m)
    tile_m: int
    depth: int             # wavefront count = n_tiles + n_stages - 1
    levels: list           # [[(stmt, (mT, s)), ...], ...]

    def active(self, t: int, s: int) -> bool:
        return 0 <= t - s < self.n_tiles


def build_schedule(n_microbatches: int, n_stages: int,
                   tile_m: int = 1) -> PipelineSchedule:
    """Polyhedral construction: tile, compress, synthesize wavefronts."""
    assert n_microbatches % tile_m == 0
    prog = pipeline_program()
    graph = TiledTaskGraph(prog, {"S": Tiling((tile_m, 1))})
    params = {"M": n_microbatches, "S": n_stages}
    ws = synthesize(graph, params)
    n_tiles = n_microbatches // tile_m
    # closed-form check: the wavefront index of tile (mT, s) must be mT + s
    for lvl, tasks in enumerate(ws.levels):
        for _, (mT, s) in tasks:
            assert mT + s == lvl, (mT, s, lvl)
    assert ws.depth == n_tiles + n_stages - 1
    return PipelineSchedule(n_stages, n_tiles, tile_m, ws.depth, ws.levels)


def pipelined_forward(stage_fn: Callable, stage_params: PyTree,
                      microbatches: jax.Array, schedule: PipelineSchedule,
                      mesh: Mesh, axis: str = "stage"):
    """Run the tiled pipeline under shard_map.

    stage_fn(params_one_stage, x) -> y          (same shape as x)
    stage_params: stacked [n_stages, ...]
    microbatches: [n_tiles, B_tile, ...]        (already tiled by tile_m)
    Returns [n_tiles, B_tile, ...] outputs of the final stage.
    """
    S = schedule.n_stages
    M = schedule.n_tiles
    T = schedule.depth
    perm = [(i, i + 1) for i in range(S - 1)]

    def per_stage(p_local, mbs):
        s = jax.lax.axis_index(axis)
        p1 = jax.tree.map(lambda a: a[0], p_local)   # [1,...] -> [...]
        x0 = jnp.zeros_like(mbs[0])
        outs0 = jnp.zeros_like(mbs)

        def step(t, carry):
            x_buf, outs = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            first_in = jax.lax.dynamic_index_in_dim(mbs, mb_idx, 0,
                                                    keepdims=False)
            x_in = jnp.where(s == 0, first_in, x_buf)
            active = jnp.logical_and(t - s >= 0, t - s < M)
            y = stage_fn(p1, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # dependence (m, s) -> (m, s+1): one wavefront step later
            x_next = jax.lax.ppermute(y, axis, perm)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            is_last = jnp.logical_and(s == S - 1, active)
            cur = jax.lax.dynamic_index_in_dim(outs, out_idx, 0,
                                               keepdims=False)
            new = jnp.where(is_last, y, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, new, out_idx, 0)
            return (x_next, outs)

        _, outs = jax.lax.fori_loop(0, T, step, (x0, outs0))
        # only the last stage holds real outputs; broadcast them
        outs = jnp.where(s == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    nd = microbatches.ndim
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P(*([None] * nd))),
        out_specs=P(*([None] * nd)), check_vma=False,
    )(stage_params, microbatches)


def sequential_reference(stage_fn: Callable, stage_params: PyTree,
                         microbatches: jax.Array) -> jax.Array:
    """Oracle: apply all stages to every microbatch sequentially."""

    def apply_all(x):
        def body(h, p):
            return stage_fn(p, h), None
        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    return (jax.vmap(apply_all)(microbatches) if False
            else jnp.stack([apply_all(mb) for mb in microbatches]))


def make_pipeline_loss(stage_fn, schedule, mesh, axis="stage"):
    """Training through the pipeline: grad flows back through ppermute
    (reverse wavefront = the backward pipeline, synthesized for free)."""

    def loss(stage_params, microbatches, targets):
        outs = pipelined_forward(stage_fn, stage_params, microbatches,
                                 schedule, mesh, axis)
        return jnp.mean((outs - targets) ** 2)

    return loss
