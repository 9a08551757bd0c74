"""Paper §5.2: execution-time comparison across synchronization models,
plus the host-vs-device dispatch benchmark for wavefront schedules.

Part 1 (``models``) — simulated makespans (deterministic; the container
has two cores) with a nontrivial per-master-op cost, matching the paper's
observations: autodec >= tags > counted > prescribed on graphs with
dominators, and the tags-1 spatial cost exploding (their OOM cases)
visible in spatial_peak.  Also runs the real-thread autodec runtime for
wall-clock sanity.

Part 2 (``dispatch``) — what does it cost *per task* to drive a synthesized
wavefront schedule?  Three paths over the same index graph:

* ``host``            — ``simulate_indexed`` feeding the instrumented Sim
                        level by level (``Sim.make_ready_ids``: deque +
                        heapq per task, no per-task closures),
* ``device_replay``   — :class:`~repro.core.edt.DeviceExecutor` replay
                        sweep: one ``fori_loop`` over levels, counters
                        decremented and validated on the jax layer,
                        O(V+E) total,
* ``device_discover`` — the self-leveling counted sweep (frontiers derived
                        from counters alone, O(depth·(V+E))); skipped on
                        the ≥1M-task case where the dense-frontier cost is
                        the documented tradeoff.

Frontier identity across paths is asserted, not assumed.  The full run
includes a ≥1M-task jacobi2d case (the acceptance graph of
docs/device_exec.md); smoke keeps the same row schema on a small case.
Rows land in the CI JSON artifact via ``benchmarks/run.py --json``
(schema v3).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.edt import (DeviceExecutor, ExecutionConfig, TiledTaskGraph,
                            run_graph_threaded, run_model, simulate_indexed,
                            synthesize_indexed)
from repro.core.edt.shard import process_pool
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

CASES = [
    ("diamond", {"S": Tiling((1, 1))}, {"K": 24}),
    ("trisolv", {"S": Tiling((2, 2))}, {"N": 36}),
    ("stencil1d", {"S": Tiling((4, 8))}, {"T": 24, "N": 96}),
    ("pipeline", {"S": Tiling((1, 1))}, {"M": 24, "S": 8}),
]
SMOKE_CASES = [
    ("diamond", {"S": Tiling((1, 1))}, {"K": 10}),
    ("pipeline", {"S": Tiling((1, 1))}, {"M": 8, "S": 4}),
]
MODELS_ = ("prescribed", "tags1", "tags2", "counted", "autodec")

# (program, tile sizes, params, shards, run_discover) — the dispatch suite.
# The last full case is the ≥1M-task acceptance graph; discover mode is
# priced on the mid case only (its O(depth·E) cost at 1M is the tradeoff
# docs/device_exec.md documents, not a number worth re-measuring per PR).
DISPATCH_CASES = [
    ("jacobi2d", (2, 2, 2), {"T": 16, "N": 128}, 1, True),
    ("jacobi2d", (2, 2, 2), {"T": 32, "N": 512}, 4, False),
]
SMOKE_DISPATCH_CASES = [
    ("jacobi2d", (2, 2, 2), {"T": 8, "N": 64}, 2, True),
]


def _models(emit, cases):
    emit("program,model,n_tasks,makespan,startup_ops,spatial_peak")
    rows = []
    makespans = {}
    for name, tiling, params in cases:
        g = TiledTaskGraph(PROGRAMS[name](), tiling)
        for model in MODELS_:
            res = run_model(model, g, params, workers=8, setup_cost=0.05)
            s = res.counters.summary()
            makespans[(name, model)] = s["makespan"]
            rows.append({"program": name, "model": model,
                         "n_tasks": res.n_tasks,
                         "makespan": s["makespan"],
                         "startup_ops": s["startup_ops"],
                         "spatial_peak": s["spatial_peak"]})
            emit(f"{name},{model},{res.n_tasks},{s['makespan']:.2f},"
                 f"{s['startup_ops']},{s['spatial_peak']}")
        t0 = time.perf_counter()
        run_graph_threaded(g, params, workers=4)
        emit(f"{name},autodec_threads_wallclock,-,{time.perf_counter()-t0:.3f}s,-,-")
    for name, *_ in cases:
        sp = makespans[(name, "prescribed")] / makespans[(name, "autodec")]
        emit(f"# {name}: autodec vs prescribed makespan speedup {sp:.2f}x")
    return rows


def _verified(run, sched) -> bool:
    return (len(run.levels) == sched.depth
            and all(np.array_equal(a, b)
                    for a, b in zip(run.levels, sched.levels)))


def _dispatch(emit, cases, pool=None):
    emit("program,path,shards,tasks,edges,depth,seconds,per_task_us,verified")
    rows = []

    def row(name, path, shards, ig, sched, seconds, verified, **extra):
        r = {"program": name, "path": path, "shards": shards,
             "tasks": ig.n, "edges": ig.n_edges, "depth": sched.depth,
             "seconds": round(seconds, 4),
             "per_task_us": round(1e6 * seconds / max(1, ig.n), 3),
             "verified": bool(verified), **extra}
        rows.append(r)
        emit(f"{name},{path},{shards},{ig.n},{ig.n_edges},{sched.depth},"
             f"{r['seconds']},{r['per_task_us']},{r['verified']}")
        return r

    for name, tiles, params, shards, discover in cases:
        g = TiledTaskGraph(PROGRAMS[name](), {"S": Tiling(tiles)},
                           backend="numpy")
        t0 = time.perf_counter()
        ig, sched = synthesize_indexed(g, params, config=ExecutionConfig(
            shards=shards if shards > 1 else None, pool=pool))
        emit(f"# {name}: generation+leveling {time.perf_counter()-t0:.2f}s "
             f"({ig.n} tasks, {ig.n_edges} edges, depth {sched.depth})")

        t0 = time.perf_counter()
        sim = simulate_indexed(sched, workers=8)
        host_s = time.perf_counter() - t0
        host_order = np.asarray(sim.exec_order)
        row(name, "host", shards, ig, sched, host_s,
            len(sim.exec_order) == ig.n)

        paths = [("device_replay", dict(schedule=sched))]
        if discover:
            paths.append(("device_discover", {}))
        for path, kw in paths:
            t0 = time.perf_counter()
            dev = DeviceExecutor(ig, **kw)
            pack_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            run = dev.run()                       # cold: includes jit
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            run = dev.run()                       # warm: dispatch cost
            warm_s = time.perf_counter() - t0
            # discover: _verified compares independently computed levels.
            # replay returns the validated input schedule, so the load-
            # bearing checks are run() not raising (on-device counters)
            # and the order cross-check against the host Sim.
            ok = (_verified(run, sched)
                  and np.array_equal(run.exec_order, host_order))
            row(name, path, shards, ig, sched, warm_s, ok,
                pack_seconds=round(pack_s, 4),
                first_seconds=round(first_s, 4))
    return rows


def run(emit=print, smoke: bool = False):
    model_rows = _models(emit, SMOKE_CASES if smoke else CASES)
    dcases = SMOKE_DISPATCH_CASES if smoke else DISPATCH_CASES
    need_pool = any(s > 1 for _, _, _, s, _ in dcases)
    pool = process_pool(2) if need_pool else None
    try:
        dispatch_rows = _dispatch(emit, dcases, pool=pool)
    finally:
        if pool is not None:
            pool.shutdown()
    bad = [r for r in dispatch_rows if not r["verified"]]
    assert not bad, f"dispatch paths diverged: {bad}"
    return {"models": model_rows, "dispatch": dispatch_rows}
