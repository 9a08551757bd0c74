"""Fused execution pricing: what does the EDT runtime *cost* once the
tasks do real work?

Four ways to run the same stencil solve (same grid, same taps, same
answer), priced per task and per grid point:

* ``device_replay``     — the PR-5 decrement-only sweep: counters + on-
                          device validation, tiles are phantoms.  The
                          fused sweep's budget: compute is only "free" if
                          adding it does not slow the sweep down.
* ``fused``             — :class:`~repro.core.edt.FusedExecutor` replay
                          with the on-device schedule validation on
                          (the default posture),
* ``fused_novalidate``  — the same sweep minus the three violation
                          counters; the fair comparison against the
                          decrement-only sweep (which prices one gather
                          per level where the fused validating sweep
                          prices three) and the ISSUE acceptance row,
* ``host_dispatch``     — :func:`~repro.core.edt.host_execute`, the
                          NumPy level-major twin: every level a host
                          round-trip (what "dispatch per wavefront"
                          costs without device residency),
* ``handwritten``       — :func:`~repro.kernels.stencils.handwritten_solve`,
                          the no-task-graph ``lax.fori_loop`` a
                          performance engineer writes given the whole
                          problem up front.  The honest upper bound: the
                          EDT sweep pays per *task*, this pays per time
                          step, so the gap (reported as
                          ``vs_handwritten``) is the price of generality.

Warm timings are best-of-3 after a cold (compiling) run.  Numerics are
asserted, not assumed: every fused/host row is checked against the
handwritten solve of the same initial grid (float32, rtol 1e-4 — ~1M-task
accumulation drift documented in docs/device_exec.md).  The full run's
flagship is the ≥1M-task jacobi2d acceptance case, where
``fused_novalidate`` per-task time must not exceed ``device_replay``.
Rows land in the CI JSON artifact via ``benchmarks/run.py --json``
(schema v6, section ``fused``).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.edt import (DeviceExecutor, ExecutionConfig, FusedExecutor,
                            TiledTaskGraph, host_execute, pack_origins,
                            synthesize_indexed)
from repro.core.edt.shard import process_pool
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS
from repro.kernels.stencils import SPECS, default_state, handwritten_solve

#: (program, tile sizes, params, shards, extras, flagship) — ``extras``
#: adds the host_dispatch row (a per-level host loop not worth re-pricing
#: at 1M tasks); ``flagship`` marks the acceptance case.
CASES = [
    ("jacobi2d", (2, 2, 2), {"T": 16, "N": 128}, 1, True, False),
    ("seidel1d", (2, 4), {"T": 64, "N": 256}, 1, True, False),
    ("jacobi2d", (2, 2, 2), {"T": 32, "N": 512}, 4, False, True),
]
SMOKE_CASES = [
    ("jacobi2d", (2, 2, 2), {"T": 8, "N": 64}, 2, True, False),
]

#: 1M-task float32 accumulation drift vs the reassociated handwritten
#: solve; small cases sit at ~1 ULP (tests/test_fused_exec.py pins both).
TOL = dict(rtol=1e-4, atol=1e-5)


def _best_of(fn, k: int = 3) -> float:
    best = float("inf")
    for _ in range(k):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(emit=print, smoke: bool = False):
    cases = SMOKE_CASES if smoke else CASES
    emit("program,path,tasks,points,seconds,per_task_us,per_point_ns,"
         "vs_handwritten,verified")
    rows = []
    need_pool = any(s > 1 for *_, s, _, _ in cases)
    pool = process_pool(2) if need_pool else None
    try:
        for name, tiles, params, shards, extras, flagship in cases:
            rows += _case(emit, name, tiles, params, shards, extras,
                          flagship, pool)
    finally:
        if pool is not None:
            pool.shutdown()

    bad = [r for r in rows if not r["verified"]]
    assert not bad, f"fused paths diverged from the handwritten solve: {bad}"
    acceptance = None
    for r in rows:
        if r["flagship"] and r["path"] == "fused_novalidate":
            base = next(x for x in rows
                        if x["flagship"] and x["path"] == "device_replay")
            acceptance = {
                "tasks": r["tasks"],
                "fused_novalidate_per_task_us": r["per_task_us"],
                "device_replay_per_task_us": base["per_task_us"],
                "le_decrement_only": r["per_task_us"] <= base["per_task_us"],
                "vs_handwritten": r["vs_handwritten"],
            }
            emit(f"# acceptance: fused {r['per_task_us']}us/task vs "
                 f"decrement-only {base['per_task_us']}us/task on "
                 f"{r['tasks']} tasks -> "
                 f"{'OK' if acceptance['le_decrement_only'] else 'FAIL'}")
            assert acceptance["le_decrement_only"], acceptance
    return {"rows": rows, "acceptance": acceptance}


def _case(emit, name, tiles, params, shards, extras, flagship, pool):
    spec = SPECS[name]
    g = TiledTaskGraph(PROGRAMS[name](), {"S": Tiling(tiles)},
                       backend="numpy")
    t0 = time.perf_counter()
    ig, sched = synthesize_indexed(g, params, config=ExecutionConfig(
        shards=shards if shards > 1 else None, pool=pool))
    emit(f"# {name} {params}: generation+leveling "
         f"{time.perf_counter()-t0:.2f}s ({ig.n} tasks, {ig.n_edges} "
         f"edges, depth {sched.depth})")
    points = params["T"] * params["N"] ** spec.space
    state = default_state(spec, params["N"], np.float32)

    handwritten_solve(spec, state, params["T"])              # compile
    hand_s = _best_of(lambda: handwritten_solve(spec, state, params["T"]))
    want = handwritten_solve(spec, state, params["T"])

    rows = []

    def row(path, seconds, final=None):
        ok = final is None or np.allclose(final, want, **TOL)
        r = {"program": name, "path": path, "tasks": ig.n, "points": points,
             "flagship": flagship, "seconds": round(seconds, 4),
             "per_task_us": round(1e6 * seconds / max(1, ig.n), 3),
             "per_point_ns": round(1e9 * seconds / max(1, points), 2),
             "vs_handwritten": round(seconds / hand_s, 2),
             "verified": bool(ok)}
        rows.append(r)
        emit(f"{name},{path},{ig.n},{points},{r['seconds']},"
             f"{r['per_task_us']},{r['per_point_ns']},"
             f"{r['vs_handwritten']},{r['verified']}")
        return r

    row("handwritten", hand_s)

    dev = DeviceExecutor(ig, schedule=sched)
    dev.run()                                                # compile
    row("device_replay", _best_of(dev.run))

    for path, validate in (("fused", True), ("fused_novalidate", False)):
        ex = FusedExecutor(ig, params, body=name, tile=tiles,
                           schedule=sched, state=state, validate=validate)
        run_ = ex.run()                                      # compile
        row(path, _best_of(ex.run), run_.final)

    if extras:
        fo = pack_origins(ig, tiles)
        t0 = time.perf_counter()
        final = host_execute(spec, tiles, params["T"], params["N"], fo,
                             sched.levels, state)
        row("host_dispatch", time.perf_counter() - t0, final)
    return rows
