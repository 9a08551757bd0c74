"""The paper end-to-end: compile a stencil to an event-driven task program.

    PYTHONPATH=src python examples/stencil_edt.py

1. Build the Jacobi-1D polyhedral program (time-skewed, as the affine
   scheduler would emit it).
2. Tile it; compute inter-tile dependences with §3 compression (printing the
   generated code of Figs 3/4/5 for each synchronization model).
3. Execute the REAL stencil through the EDT runtime (threaded autodec —
   atomic get-or-create, preschedule, O(1) startup) and check the result
   against a dense jnp reference.
4. Compare overhead counters across all five synchronization models.
5. Run the same schedule device-resident: pack the index graph into jax
   arrays and sweep the counted-sync loop on the DeviceExecutor (discover
   and replay modes), checking its frontiers against the host wavefront
   synthesis — docs/device_exec.md.
6. Fuse the tile bodies into that sweep: one jitted XLA program both
   decrements the counters and computes every tile (FusedExecutor),
   checked against the NumPy reference solve — docs/device_exec.md,
   "Fused execution".
"""
import sys
sys.path.insert(0, "src")

import time

import numpy as np

from repro.core.edt import (MODELS, DeviceExecutor, FusedExecutor,
                            TiledTaskGraph, run_model, synthesize_indexed,
                            ThreadedAutodec, validate_order)
from repro.core.edt.codegen import (emit_autodec, emit_fused,
                                    emit_prescribed, emit_tags)
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS
from repro.kernels.stencils import SPECS, default_state, reference_solve
from repro.launch.compile_cache import use_compile_cache

T_STEPS, N = 12, 64
TILE = (3, 8)


def main():
    use_compile_cache()
    prog = PROGRAMS["stencil1d"]()
    graph = TiledTaskGraph(prog, {"S": Tiling(TILE)})
    params = {"T": T_STEPS, "N": N}
    n = graph.num_tasks(params)
    print(f"Jacobi-1D (skewed): {T_STEPS}x{N} iters -> {n} tasks "
          f"(tile {TILE}), strategies: {graph.pred_count_strategies()}\n")

    print(emit_prescribed(graph), "\n")
    print(emit_tags(graph, method=2), "\n")
    print(emit_autodec(graph), "\n")

    # ---- execute the actual stencil through the autodec runtime ----------
    # state[t % 2] holds the field at time t; tiles update their (t, x) cells
    field = [np.zeros(N + 2 * T_STEPS), np.zeros(N + 2 * T_STEPS)]
    field[0][:] = np.linspace(0, 1, N + 2 * T_STEPS)
    field[1][:] = field[0]          # ping-pong halo must start identical
    init = field[0].copy()

    def body(task):
        _, (tT, xT) = task
        for t in range(tT * TILE[0], (tT + 1) * TILE[0]):
            if not (0 <= t < T_STEPS):
                continue
            src, dst = field[t % 2], field[(t + 1) % 2]
            for x in range(xT * TILE[1], (xT + 1) * TILE[1]):
                i = x - t          # unskew
                if 0 <= i < N:
                    j = i + T_STEPS   # halo offset
                    dst[j] = 0.25 * src[j - 1] + 0.5 * src[j] + 0.25 * src[j + 1]

    rt = ThreadedAutodec(
        pred_count=lambda t: graph.pred_count(t, params),
        successors=lambda t: list(graph.successors(t, params)),
        body=body, workers=1)   # single worker: in-place halo updates race-free
    rt.preschedule_all(graph.tasks(params))
    assert rt.wait(120)
    rt.shutdown()
    assert not rt.errors, rt.errors[:1]

    ref = init.copy()
    for _ in range(T_STEPS):
        nxt = ref.copy()
        nxt[T_STEPS:T_STEPS + N] = (0.25 * ref[T_STEPS - 1:T_STEPS + N - 1]
                                    + 0.5 * ref[T_STEPS:T_STEPS + N]
                                    + 0.25 * ref[T_STEPS + 1:T_STEPS + N + 1])
        ref = nxt
    got = field[T_STEPS % 2]
    np.testing.assert_allclose(got[T_STEPS:T_STEPS + N],
                               ref[T_STEPS:T_STEPS + N], rtol=1e-12)
    print(f"EDT execution matches dense reference on {N} cells "
          f"x {T_STEPS} steps (tasks executed: {len(rt.executed)})\n")

    # ---- Table 2 in practice ---------------------------------------------
    print(f"{'model':15s} {'startup':>8s} {'spatial':>8s} {'in-flight':>10s} "
          f"{'deps':>6s} {'garbage':>8s} {'makespan':>9s}")
    for model in MODELS:
        res = run_model(model, graph, params, workers=4, setup_cost=0.02)
        validate_order(graph, params, res)
        s = res.counters.summary()
        print(f"{model:15s} {s['startup_ops']:8d} {s['spatial_peak']:8d} "
              f"{s['inflight_tasks_peak']:10d} {s['inflight_deps_peak']:6d} "
              f"{s['garbage_peak']:8d} {s['makespan']:9.2f}")

    # ---- device-resident wavefront execution ------------------------------
    # The same tile graph as flat index arrays on the jax layer: the counted
    # model's counters live in device memory and the whole schedule sweeps
    # in one XLA loop — no host dicts, no per-task Python dispatch.
    dgraph = TiledTaskGraph(prog, {"S": Tiling(TILE)}, backend="numpy")
    ig, sched = synthesize_indexed(dgraph, params)
    for mode, kw in (("discover", {}), ("replay", {"schedule": sched})):
        dev = DeviceExecutor(ig, **kw)
        dev.run()                       # compile
        t0 = time.perf_counter()
        drun = dev.run()                # warm: the dispatch cost
        dt = time.perf_counter() - t0
        assert len(drun.levels) == sched.depth
        assert all(np.array_equal(a, b)
                   for a, b in zip(drun.levels, sched.levels))
        c = drun.counters.summary()
        print(f"\ndevice {mode:9s}: {c['tasks_finished']} tasks in "
              f"{c['depth']} wavefronts (max in-flight {c['max_in_flight']}) "
              f"— frontiers identical to host synthesis, "
              f"{1e6 * dt / max(1, ig.n):.1f} us/task dispatch")

    # ---- fused: the tiles compute inside the sweep -------------------------
    # Same packed schedule, but now each wavefront also executes its tiles'
    # stencil taps on a device-resident parity-buffered grid; the host sees
    # nothing until the final readback.
    print("\n" + emit_fused(dgraph), "\n")
    spec = SPECS["stencil1d"]
    state = default_state(spec, N, np.float32)
    fused = FusedExecutor(dgraph, params, schedule=sched, state=state)
    fused.run()                         # compile
    t0 = time.perf_counter()
    frun = fused.run()                  # warm
    dt = time.perf_counter() - t0
    np.testing.assert_allclose(frun.final,
                               reference_solve(spec, state, T_STEPS),
                               rtol=1e-5, atol=1e-6)
    assert all(np.array_equal(a, b)
               for a, b in zip(frun.levels, sched.levels))
    print(f"fused replay    : {frun.counters.tasks_finished} tasks computed "
          f"AND synchronized in {frun.counters.depth} wavefronts, result "
          f"matches the NumPy reference, "
          f"{1e6 * dt / max(1, ig.n):.1f} us/task")
    print("\nstencil_edt OK")


if __name__ == "__main__":
    main()
