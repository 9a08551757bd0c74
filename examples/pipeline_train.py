"""Pipeline-parallel training scheduled by the polyhedral EDT machinery.

    PYTHONPATH=src python examples/pipeline_train.py

Runs on 8 virtual devices (host platform): 4 pipeline stages x 2 data.
The (microbatch, stage) wavefront schedule is *derived* from the paper's
compression-based tile dependences (see repro/parallel/pipeline.py), lowered
to shard_map + ppermute, and differentiated straight through for training —
the backward wavefront is the VJP of the forward one.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
import sys
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.parallel.pipeline import (build_schedule, make_pipeline_loss,
                                     pipelined_forward, sequential_reference)

N_STAGES = 4
N_MICRO = 8
TILE_M = 2
D = 64
B_TILE = 4


def stage_fn(p, x):
    """One pipeline stage: a two-layer MLP block (residual)."""
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return x + h @ p["w2"]


def main():
    use_compile_cache()
    mesh = jax.make_mesh((N_STAGES,), ("stage",))
    sched = build_schedule(N_MICRO, N_STAGES, tile_m=TILE_M)
    print(f"polyhedral schedule: {sched.n_tiles} microbatch tiles x "
          f"{sched.n_stages} stages -> {sched.depth} wavefronts "
          f"(= M' + S - 1 = {sched.n_tiles + N_STAGES - 1})")

    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "w1": 0.3 * jax.random.normal(k1, (N_STAGES, D, D)),
        "b1": jnp.zeros((N_STAGES, D)),
        "w2": 0.3 * jax.random.normal(k2, (N_STAGES, D, D)),
    }
    mbs = jax.random.normal(k3, (sched.n_tiles, B_TILE * TILE_M, D))

    # 1. forward correctness vs the sequential oracle
    out_pipe = pipelined_forward(stage_fn, params, mbs, sched, mesh)
    out_ref = sequential_reference(stage_fn, params, mbs)
    np.testing.assert_allclose(np.asarray(out_pipe), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)
    print("pipelined forward == sequential reference")

    # 2. train through the pipeline (grad flows through ppermute)
    targets = jax.random.normal(k4, out_ref.shape)
    loss_fn = make_pipeline_loss(stage_fn, sched, mesh)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    lr = 0.05
    losses = []
    for step in range(30):
        loss, g = grad_fn(params, mbs, targets)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        losses.append(float(loss))
    print(f"pipeline training loss: {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0] * 0.7, losses
    print("pipeline_train OK")


if __name__ == "__main__":
    main()
