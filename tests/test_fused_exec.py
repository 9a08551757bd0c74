"""Fused-execution suite: the counted-sync sweep computing real tiles.

The ladder of trust, bottom to top:

* :func:`reference_solve` — time-major NumPy, the ground truth;
* :func:`host_execute` — the *level-major* NumPy twin of the fused sweep
  (same tiles, same masking, same level order), proven **bitwise** equal
  to the reference — this is the argument that wavefront leveling
  linearizes every buffer hazard;
* :class:`FusedExecutor` replay and discover — the device sweeps, matched
  to the reference within documented tolerances (float32: rtol 1e-5 /
  atol 1e-6, observed ~1 ULP from XLA reassociation; float64: rtol 1e-12,
  observed ~1e-16) and to the host schedule frontiers **byte for byte**;
* :func:`handwritten_solve` — the no-task-graph jax baseline, agreeing
  with the reference under the same float32 tolerance.

Plus the failure modes (wrong body/tile/dtype, schedule-vs-packed
conflicts, dropped decrements stalling the fused discover sweep), the
graph-cache ``fused`` product, and the ≥1M-task jacobi2d acceptance run.
"""
from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.edt import (CachePolicy, ExecutionConfig, FusedExecutor,
                            GraphCache, Session, TiledTaskGraph,
                            graph_cache_info, graph_tile, host_execute,
                            pack_origins, simulate_indexed,
                            synthesize_indexed)
from repro.core.edt.fused import SENTINEL_ORIGIN
from repro.core.edt.shard import process_pool
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS
from repro.kernels.stencils import (SPECS, default_state, handwritten_solve,
                                    reference_solve)

#: (program, tile sizes, params) — every stencil body, small enough that
#: the sequential reference loop stays fast, big enough for partial tiles
#: (extents not multiples of tile sizes) and several wavefronts.
CASES = [
    ("stencil1d", (2, 2), {"T": 6, "N": 15}),
    ("jacobi2d", (2, 2, 2), {"T": 5, "N": 11}),
    ("heat3d", (2, 2, 2, 2), {"T": 3, "N": 7}),
    ("seidel1d", (2, 3), {"T": 6, "N": 14}),
    # 2-D Gauss-Seidel: in-tile wavefronts under its own skew; the second
    # tile cuts both skewed faces of the parallelepiped at other sizes
    ("seidel2d", (2, 3, 4), {"T": 5, "N": 11}),
    ("seidel2d", (3, 4, 5), {"T": 7, "N": 9}),
]

F32_TOL = dict(rtol=1e-5, atol=1e-6)    # observed ~1 ULP (6e-8)
F64_TOL = dict(rtol=1e-12, atol=1e-13)  # observed ~1e-16


@pytest.fixture(scope="module")
def pool():
    p = process_pool(2)
    p.submit(int, 0).result()
    yield p
    p.shutdown()


def _graph(name, tiles):
    return TiledTaskGraph(PROGRAMS[name](), {"S": Tiling(tiles)},
                          backend="numpy")


# ===================================================== numerics ladder
@pytest.mark.parametrize("name,tiles,params", CASES)
def test_host_execute_bitwise_equals_reference(name, tiles, params):
    """Level-major tile execution == time-major execution, bit for bit:
    the wavefront levels linearize every parity-buffer hazard."""
    spec = SPECS[name]
    g = _graph(name, tiles)
    ig, sched = synthesize_indexed(g, params)
    state = default_state(spec, params["N"], np.float32)
    got = host_execute(spec, tiles, params["T"], params["N"],
                       pack_origins(ig, tiles), sched.levels, state)
    want = reference_solve(spec, state, params["T"])
    assert got.dtype == want.dtype
    assert np.array_equal(got, want), name


@pytest.mark.parametrize("name,tiles,params", CASES)
@pytest.mark.parametrize("mode", ["replay", "discover"])
def test_fused_matches_reference_f32(name, tiles, params, mode):
    spec = SPECS[name]
    g = _graph(name, tiles)
    ig, sched = synthesize_indexed(g, params)
    state = default_state(spec, params["N"], np.float32)
    ex = FusedExecutor(ig, params, body=name, tile=tiles,
                       schedule=sched if mode == "replay" else None,
                       state=state)
    run = ex.run()
    assert run.mode == mode
    want = reference_solve(spec, state, params["T"])
    np.testing.assert_allclose(run.final, want, **F32_TOL)
    # the non-answer parity buffer holds v_{T-2}
    if params["T"] >= 2:
        np.testing.assert_allclose(
            run.state[(params["T"] - 2) & 1],
            reference_solve(spec, state, params["T"] - 1), **F32_TOL)


@pytest.mark.parametrize("name,tiles,params", CASES)
def test_fused_matches_reference_f64(name, tiles, params):
    spec = SPECS[name]
    g = _graph(name, tiles)
    ig, sched = synthesize_indexed(g, params)
    state = default_state(spec, params["N"], np.float64)
    want = reference_solve(spec, state, params["T"])
    with jax.enable_x64(True):
        for sched_arg in (sched, None):
            run = FusedExecutor(ig, params, body=name, tile=tiles,
                                schedule=sched_arg, state=state).run()
            np.testing.assert_allclose(run.final, want, **F64_TOL)


@pytest.mark.parametrize("name,tiles,params", CASES)
def test_handwritten_baseline_agrees(name, tiles, params):
    """The bench_fused baseline solves the same problem (so the priced
    comparison is apples to apples)."""
    spec = SPECS[name]
    state = default_state(spec, params["N"], np.float32)
    got = handwritten_solve(spec, state, params["T"])
    want = reference_solve(spec, state, params["T"])
    np.testing.assert_allclose(got, want, **F32_TOL)


def test_fused_custom_state_and_rerun():
    """run(state=) reuses the compiled sweep on fresh data."""
    name, tiles, params = CASES[0]
    spec = SPECS[name]
    g = _graph(name, tiles)
    ex = FusedExecutor(g, params)      # body/tile inferred from the graph
    s1 = default_state(spec, params["N"], np.float32)
    s2 = np.asarray(s1[::-1])
    np.testing.assert_allclose(ex.run(s1).final,
                               reference_solve(spec, s1, params["T"]),
                               **F32_TOL)
    np.testing.assert_allclose(ex.run(s2).final,
                               reference_solve(spec, s2, params["T"]),
                               **F32_TOL)


def test_zero_step_run_returns_initial_state():
    name, tiles, _ = CASES[0]
    spec = SPECS[name]
    state = default_state(spec, 9, np.float32)
    run = FusedExecutor(_graph(name, tiles), {"T": 0, "N": 9},
                        state=state).run()
    assert run.levels == [] and run.counters.depth == 0
    assert np.array_equal(run.final, state)


# ================================================== frontier identity
@pytest.mark.parametrize("name,tiles,params", CASES)
def test_fused_frontiers_byte_identical(name, tiles, params):
    """Both fused modes walk exactly the host schedule's frontiers — the
    compute never perturbs the counter sweep."""
    g = _graph(name, tiles)
    ig, sched = synthesize_indexed(g, params)
    runs = {
        "replay": FusedExecutor(ig, params, body=name, tile=tiles,
                                schedule=sched).run(),
        "discover": FusedExecutor(ig, params, body=name, tile=tiles).run(),
    }
    host_order = simulate_indexed(sched, workers=3).exec_order
    for label, run in runs.items():
        assert len(run.levels) == sched.depth, label
        for dev_lv, host_lv in zip(run.levels, sched.levels):
            assert dev_lv.dtype == host_lv.dtype, label
            assert np.array_equal(dev_lv, host_lv), label
        assert np.array_equal(run.level_of, sched.level_of), label
        assert run.exec_order.tolist() == host_order, label
        c = run.counters
        assert c.tasks_started == c.tasks_finished == ig.n, label
        assert c.depth == sched.depth, label
        assert c.max_in_flight == sched.max_width, label


def test_validate_false_same_answer():
    """Dropping the three violation counters changes nothing numeric."""
    name, tiles, params = CASES[1]
    g = _graph(name, tiles)
    ig, sched = synthesize_indexed(g, params)
    a = FusedExecutor(ig, params, body=name, tile=tiles,
                      schedule=sched).run()
    b = FusedExecutor(ig, params, body=name, tile=tiles, schedule=sched,
                      validate=False).run()
    assert np.array_equal(a.final, b.final)
    assert np.array_equal(a.level_of, b.level_of)


def test_replay_rejects_corrupt_schedule():
    """The fused replay keeps the device executor's validation teeth."""
    from repro.core.edt import ScheduleValidationError
    from repro.core.edt.wavefront import IndexedSchedule, levels_from_array
    name, tiles, params = CASES[0]
    g = _graph(name, tiles)
    ig, sched = synthesize_indexed(g, params)
    lv = sched.level_of.copy()
    lv[sched.levels[1][0]] += 2
    bad = IndexedSchedule(levels=levels_from_array(lv), level_of=lv)
    with pytest.raises(ScheduleValidationError):
        FusedExecutor(ig, params, body=name, tile=tiles, schedule=bad).run()


def test_dropped_decrement_stalls_fused_discover():
    """A dropped decrement (PR-6 fault plan) deadlocks the fused sweep
    loudly, with the structured stall report naming the context."""
    from repro.core.edt import Fault, FaultPlan, StallError
    from repro.core.edt.faults import DROPPED_DECREMENT
    name, tiles, params = CASES[0]
    g = _graph(name, tiles)
    plan = FaultPlan([Fault(DROPPED_DECREMENT, task=3)])
    ex = FusedExecutor(g, params, config=ExecutionConfig(faults=plan))
    with pytest.raises(StallError) as ei:
        ex.run()
    assert ei.value.report.context == "fused-discover"


# ======================================================= construction
def test_packed_layout_and_sentinel():
    name, tiles, params = CASES[1]
    g = _graph(name, tiles)
    ig = g.index_graph(params)
    fo = pack_origins(ig, tiles)
    assert fo.shape == (ig.n + 1, len(tiles)) and fo.dtype == np.int32
    assert (fo[-1] == SENTINEL_ORIGIN).all()
    _, coords = ig.stmt_blocks[0]
    assert np.array_equal(fo[:-1], coords * np.asarray(tiles))
    assert graph_tile(g) == tiles


def test_constructor_rejects_bad_inputs():
    name, tiles, params = CASES[0]
    g = _graph(name, tiles)
    ig, sched = synthesize_indexed(g, params)
    with pytest.raises(TypeError, match="params required"):
        FusedExecutor(g)
    with pytest.raises(TypeError, match="tile="):
        FusedExecutor(ig, params, body=name)
    with pytest.raises(TypeError, match="body="):
        FusedExecutor(ig, params, tile=tiles)
    with pytest.raises(TypeError, match="unknown stencil body"):
        FusedExecutor(ig, params, body="nope", tile=tiles)
    with pytest.raises(ValueError, match="tile dims"):
        FusedExecutor(ig, params, body=name, tile=(2, 2, 2))
    with pytest.raises(TypeError, match="not both"):
        FusedExecutor(ig, params, body=name, tile=tiles, schedule=sched,
                      packed=(None, None, None))
    with pytest.raises(TypeError, match="discover sweep only"):
        FusedExecutor(ig, params, body=name, tile=tiles, schedule=sched,
                      use_pallas=True)
    with pytest.raises(ValueError, match="state shape"):
        FusedExecutor(ig, params, body=name, tile=tiles,
                      state=np.zeros((3, 3), np.float32))
    # multi-statement graphs have no single tile body
    from repro.core.edt import IndexedGraph
    two = IndexedGraph(
        stmt_blocks=[("A", np.zeros((1, 2), np.int64)),
                     ("B", np.zeros((1, 2), np.int64))],
        n=2, edge_src=np.zeros(0, np.int64), edge_tgt=np.zeros(0, np.int64),
        pred_n=np.zeros(2, np.int64))
    with pytest.raises(ValueError, match="single-statement"):
        pack_origins(two, tiles)
    with pytest.raises(ValueError, match="do not match"):
        pack_origins(ig, (2, 2, 2))


def test_f64_without_x64_raises():
    import jax
    if jax.config.jax_enable_x64:          # pragma: no cover - env guard
        pytest.skip("suite running under global x64")
    name, tiles, params = CASES[0]
    ex = FusedExecutor(_graph(name, tiles), params, dtype=np.float64)
    with pytest.raises(RuntimeError, match="enable_x64"):
        ex.run()


def test_fused_discover_pallas_interpret():
    """The pallas decrement composes with the fused compute (interpret
    mode on this CPU container)."""
    name, tiles, params = CASES[0]
    spec = SPECS[name]
    g = _graph(name, tiles)
    state = default_state(spec, params["N"], np.float32)
    run = FusedExecutor(g, params, state=state, use_pallas=True,
                        interpret=True).run()
    np.testing.assert_allclose(
        run.final, reference_solve(spec, state, params["T"]), **F32_TOL)


# ============================================================== cache
def test_cache_fused_product_warm_by_reference():
    g = _graph("jacobi2d", (2, 2, 2))
    params = {"T": 4, "N": 10}
    cache = GraphCache(CachePolicy(incremental=False))
    cold = cache.fused(g, params)
    warm = cache.fused(g, params)
    for a, b in zip(cold, warm):
        assert a is b
    # the ig and tile are under the same fingerprint: bytes accounted
    assert cache.info()["bytes"] >= cold[2].nbytes


def test_cache_fused_respects_byte_budget():
    """The fo product participates in LRU eviction like the others."""
    g = _graph("stencil1d", (2, 2))
    budget = 30_000
    cache = GraphCache(CachePolicy(max_entries=64, max_bytes=budget,
                                   incremental=False))
    for n in range(8, 40, 2):
        cache.fused(g, {"T": 6, "N": n})
        assert cache.info()["bytes"] <= budget
    assert cache.info()["evictions"] > 0


def test_cache_disabled_fused_pass_through():
    g = _graph("stencil1d", (2, 2))
    cache = GraphCache(CachePolicy(enabled=False))
    a = cache.fused(g, {"T": 4, "N": 10})
    b = cache.fused(g, {"T": 4, "N": 10})
    assert a[2] is not b[2]
    assert np.array_equal(a[2], b[2])
    assert cache.info()["entries"] == 0


def test_session_fused_executor_end_to_end():
    """Session.fused_executor: warm products, correct numerics, both
    modes, and the packed arrays come back by reference."""
    name, tiles, params = CASES[1]
    spec = SPECS[name]
    g = _graph(name, tiles)
    with Session() as s:
        run = s.fused_executor(g, params).run()
        state = default_state(spec, params["N"], np.float32)
        np.testing.assert_allclose(
            run.final, reference_solve(spec, state, params["T"]), **F32_TOL)
        d = s.fused_executor(g, params, replay=False).run()
        assert d.mode == "discover"
        assert np.array_equal(d.final, run.final)
        p1 = s.fused_packed(g, params)
        p2 = s.fused_packed(g, params)
        for a, b in zip(p1, p2):
            assert a is b


def _requests(s, g, params, n, **kw):
    """``n`` requests for one executor; each one's graph-cache and
    executor counter deltas and whether it built the sweep."""
    from repro.core.edt import tracing

    out = []
    for i in range(n):
        before = s.cache.info()
        with tracing.recording(), tracing.request(i) as rec:
            ex = s.fused_executor(g, params, **kw)
            ex.run()
        after = s.cache.info()
        out.append((ex, {k: after[k] - before[k] for k in after
                         if isinstance(after[k], int)
                         and not isinstance(after[k], bool)}, rec))
    return out


def test_session_keeps_the_executor_of_a_warm_request():
    """A warm request gets the executor (and its built sweep) of the
    request before it; its graph-cache hits and misses are those of the
    products' own lookups, as without the reuse."""
    name, tiles, params = CASES[1]
    g = _graph(name, tiles)
    with Session() as s:
        (ex0, d0, cold), (ex1, d1, warm), (ex2, d2, _) = _requests(
            s, g, params, 3, validate=True)
        assert ex0 is ex1 is ex2
        assert "build" in [sp.name for sp in cold.spans]
        assert cold.counters["sweep.builds"] == 1
        assert "build" not in [sp.name for sp in warm.spans]
        assert "sweep.builds" not in warm.counters
        assert warm.counters["executor.reused"] == 1
        assert (d0["executor_builds"], d0["executor_hits"]) == (1, 0)
        assert (d1["executor_builds"], d1["executor_hits"]) == (0, 1)
        # the products' lookups alone, as a warm request made them before
        before = s.cache.info()
        s.index_graph(g, params)
        s.fused_packed(g, params)
        after = s.cache.info()
        assert d1["hits"] == d2["hits"] == after["hits"] - before["hits"] > 0
        assert d1["misses"] == d2["misses"] == 0
        info = graph_cache_info()
        assert info["executor_hits"] >= 2 and info["executor_builds"] >= 1


@pytest.mark.parametrize("kw,same", [
    ({"dtype": "float32"}, True),          # the default, spelled out
    ({"dtype": None, "validate": True}, True),
    ({"replay": False}, False),
    ({"validate": False}, False),
    ({"dtype": np.float64}, False),
])
def test_executor_key_separates_what_builds_another_sweep(kw, same):
    name, tiles, params = CASES[1]
    g = _graph(name, tiles)
    with Session() as s:
        base = s.fused_executor(g, params)
        ex = s.fused_executor(g, params, **kw)
        assert (ex is base) is same
        assert s.fused_executor(g, params, **kw) is ex
        assert s.fused_executor(g, params) is base
        assert s.cache.info()["executor_builds"] == 1 + (not same)


@pytest.mark.parametrize("how", ["state", "disabled"])
def test_unkeyed_calls_build_fresh_executors(how):
    name, tiles, params = CASES[1]
    spec = SPECS[name]
    g = _graph(name, tiles)
    state = default_state(spec, params["N"], np.float32)
    policy = CachePolicy(enabled=(how != "disabled"))
    kw = {"state": state} if how == "state" else {}
    with Session(ExecutionConfig(cache=policy)) as s:
        a = s.fused_executor(g, params, **kw)
        b = s.fused_executor(g, params, **kw)
        assert a is not b
        assert np.array_equal(a.run().final, b.run().final)
        info = s.cache.info()
        assert info["executor_hits"] == 0
        # a disabled cache counts its builds; a call with state= never
        # reaches the cache
        assert info["executor_builds"] == (2 if how == "disabled" else 0)


@pytest.mark.parametrize("how", ["clear", "evict"])
def test_executor_goes_with_its_cache_entry(how):
    name, tiles, params = CASES[1]
    g = _graph(name, tiles)
    with Session(ExecutionConfig(cache=CachePolicy(max_entries=1))) as s:
        first = s.fused_executor(g, params)
        if how == "clear":
            s.cache.clear()
        else:
            s.fused_executor(g, dict(params, N=params["N"] + 1))
            assert s.cache.info()["evictions"] >= 1
        again = s.fused_executor(g, params)
        assert again is not first
        assert s.fused_executor(g, params) is again


def test_reused_executor_carries_nothing_between_requests():
    """Two seeded grids through one kept executor: bitwise the results
    of two fresh executors, and the level-major host oracle's."""
    name, tiles, params = CASES[1]
    spec = SPECS[name]
    g = _graph(name, tiles)
    rng = np.random.default_rng(2**33 + 5)
    grids = [rng.random(spec.shape(params["N"]), dtype=np.float32)
             for _ in range(2)]
    ig, sched = synthesize_indexed(g, params)
    with Session() as s:
        kept = [s.fused_executor(g, params).run(a).final for a in grids]
        kept.append(s.fused_executor(g, params).run(grids[0]).final)
    for a, got in zip(grids + grids[:1], kept):
        fresh = FusedExecutor(g, params).run(a).final
        assert np.array_equal(got, fresh)
        want = host_execute(spec, tiles, params["T"], params["N"],
                            pack_origins(ig, tiles), sched.levels, a)
        np.testing.assert_allclose(got, want, **F32_TOL)


def test_zero_step_final_is_the_request_grid():
    """T=0 answers the grid the request gave, a copy: neither the
    executor's default grid nor an array a caller could alter it by."""
    name, tiles, _ = CASES[1]
    spec = SPECS[name]
    with Session() as s:
        ex = s.fused_executor(_graph(name, tiles), {"T": 0, "N": 9})
        grid = np.ones(spec.shape(9), np.float32)
        run = ex.run(grid)
        assert np.array_equal(run.final, grid)
        run.final[...] = 7
        assert np.array_equal(ex.run().final,
                              default_state(spec, 9, np.float32))


def test_concurrent_requests_share_the_first_stored_executor():
    """Threads that miss together may each build; every one of them, and
    every later call, gets the executor stored first."""
    import os
    import sys
    import threading

    name, tiles, params = CASES[1]
    g = _graph(name, tiles)
    n = min(64, 2 * (os.cpu_count() or 4))
    got = [None] * n
    with Session() as s:
        s.fused_packed(g, params)
        start = threading.Barrier(n)

        def request(i):
            start.wait(timeout=30)
            got[i] = s.fused_executor(g, params)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=request, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        kept = s.fused_executor(g, params)
        assert all(ex is kept for ex in got)
        info = s.cache.info()
        assert info["executor_builds"] + info["executor_hits"] == n + 1
        assert info["executor_builds"] >= 1


# ================================================ 2-D Gauss-Seidel
def _seidel_iteration(t, s0, s1):
    """PolyBench seidel-2d's skew: site (i, j) at sweep t is the point
    (t, t + i, 2t + i + j)."""
    return np.stack([t, t + s0, 2 * t + s0 + s1], -1)


@pytest.mark.parametrize("tiles,params", [
    ((2, 3, 4), {"T": 5, "N": 11}), ((3, 4, 5), {"T": 7, "N": 9}),
    ((2, 8, 8), {"T": 4, "N": 13})])
def test_seidel2d_tile_graph_orders_every_point_read(tiles, params):
    """Every one of the nine reads of every point, taken from the taps
    and PolyBench's skew (not from the program's dependences), has its
    source in a tile at a lower level, or in the same tile at an earlier
    in-tile wave."""
    spec = SPECS["seidel2d"]
    ig, sched = synthesize_indexed(_graph("seidel2d", tiles), params)
    coords = ig.stmt_blocks[0][1].astype(np.int64)
    level = {tuple(c): int(lv) for c, lv in zip(coords, sched.level_of)}
    size = np.asarray(tiles)
    steps, n = params["T"], params["N"]
    t, s0, s1 = (a.ravel() for a in np.meshgrid(
        np.arange(steps), np.arange(n), np.arange(n), indexing="ij"))
    tgt = _seidel_iteration(t, s0, s1)
    for dt, off, _ in spec.taps:
        ts, a, b = t - dt, s0 + off[0], s1 + off[1]
        ok = (ts >= 0) & (a >= 0) & (a < n) & (b >= 0) & (b < n)
        src = _seidel_iteration(ts[ok], a[ok], b[ok])
        dst = tgt[ok]
        st, dtile = src // size, dst // size
        lv_src = np.asarray([level[tuple(c)] for c in st])
        lv_dst = np.asarray([level[tuple(c)] for c in dtile])
        same = np.all(st == dtile, axis=1)
        wave_src = (src - st * size).sum(1)
        wave_dst = (dst - dtile * size).sum(1)
        assert np.all(np.where(same, wave_src < wave_dst, lv_src < lv_dst)), \
            (dt, off)
        assert ok.any(), (dt, off)


def test_seidel2d_distances_are_the_programs():
    """The spec's taps, through its site map, are the nine dependence
    distances of ``programs.seidel2d``."""
    want = {(0, 1, 0), (0, 1, 1), (0, 1, 2), (0, 0, 1), (1, 0, 0),
            (1, 0, 1), (1, 0, 2), (1, 1, 1), (1, 1, 2)}
    spec = SPECS["seidel2d"]
    assert {spec.distance(dt, off) for dt, off, _ in spec.taps} == want
    assert spec.wavefront and not SPECS["seidel1d"].wavefront
    assert not SPECS["jacobi2d"].wavefront
    # the default map is x - t: a jacobi tap one step back at offset -1
    assert SPECS["jacobi2d"].distance(1, (-1, 1)) == (1, 2, 0)


@pytest.mark.parametrize("tile", [(2, 3, 4), (2, 64, 64), (1, 1, 1)])
def test_wave_table_covers_each_tile_point_once(tile):
    from repro.core.edt.fused import _wave_table

    loc, valid = _wave_table(SPECS["seidel2d"], tile)
    assert loc.shape[0] == sum(tile) - len(tile) + 1
    pts = loc[valid]
    assert len(pts) == np.prod(tile)
    assert len(np.unique(pts, axis=0)) == len(pts)
    waves = np.repeat(np.arange(loc.shape[0]), valid.sum(1))
    assert np.array_equal(pts.sum(1), waves)
    if tile == (2, 64, 64):
        assert loc.shape[1] == 127       # 63 + 64 points at c = 64


def test_wave_table_refuses_a_tap_that_does_not_cross_forward():
    from repro.core.edt.fused import _wave_table
    from repro.kernels.stencils import StencilSpec

    bad = StencilSpec("bad", 2, ((0, (1, 0), 1.0),), (True, True),
                      site_map=SPECS["seidel2d"].site_map)
    with pytest.raises(ValueError, match="wavefront"):
        _wave_table(bad, (2, 2, 2))


def test_seidel2d_session_replay_matches_the_benchmark_reference():
    """The normal path (``Session.graph`` -> ``fused_executor(replay=True)``
    -> ``run(state)``) against the chip benchmark's float64 reference,
    which imports nothing of the program, on a seeded random grid."""
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.chip.seidel_reference import seidel_solve

    params = {"T": 5, "N": 23}
    state = np.random.default_rng(2**33 + 5).random((23, 23),
                                                    dtype=np.float32)
    with Session() as s:
        g = s.graph(PROGRAMS["seidel2d"](), {"S": Tiling((2, 8, 8))})
        ex = s.fused_executor(g, params, replay=True)
        got = ex.run(state).final
    np.testing.assert_allclose(got, seidel_solve(state, params["T"]),
                               **F32_TOL)
    np.testing.assert_allclose(
        got, reference_solve(SPECS["seidel2d"], state, params["T"]),
        **F32_TOL)


def _golden_lowerings() -> list:
    path = Path(__file__).parent / "golden" / "fused_jacobi2d_lowered.sha256"
    rows = [ln.split() for ln in path.read_text().splitlines()
            if ln and not ln.startswith("#")]
    return [(tuple(map(int, tile.split("x"))),
             {k: int(v) for k, v in (t.split("=") for t in (tp, np_))},
             mode, digest) for tile, tp, np_, mode, digest in rows]


@pytest.mark.parametrize("tile,params,mode,digest", _golden_lowerings())
def test_jacobi2d_sweep_lowers_as_before(tile, params, mode, digest):
    """The site map and the wavefront body leave a Jacobi sweep's program
    as it was: the lowered text of the fine (2,2,2) and coarse (8,64,64)
    jacobi2d sweeps hashes to the digests taken before they came."""
    import hashlib

    ig, sched = synthesize_indexed(_graph("jacobi2d", tile), params)
    ex = FusedExecutor(ig, params, body="jacobi2d", tile=tile,
                       schedule=sched if mode == "replay" else None)
    text = ex.lowered().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


# ========================================================== at scale
def test_million_task_jacobi2d_fused_acceptance(pool):
    """The ISSUE acceptance run: a ≥1M-task jacobi2d solve end to end on
    the fused executor — schedule validated on device, frontiers
    byte-identical to the host schedule, numerics within the documented
    float32 tolerance of the handwritten jax solve of the same problem
    (the full sequential NumPy reference is priced out at this size; the
    handwritten baseline is itself reference-checked at small sizes
    above)."""
    g = _graph("jacobi2d", (2, 2, 2))
    params = {"T": 32, "N": 512}
    ig, sched = synthesize_indexed(
        g, params, config=ExecutionConfig(shards=2, pool=pool))
    assert ig.n >= 1_000_000
    spec = SPECS["jacobi2d"]
    state = default_state(spec, params["N"], np.float32)
    run = FusedExecutor(ig, params, body="jacobi2d", tile=(2, 2, 2),
                        schedule=sched, state=state).run()   # validates
    assert run.counters.tasks_finished == ig.n
    assert run.counters.depth == sched.depth
    for dev_lv, host_lv in zip(run.levels, sched.levels):
        assert np.array_equal(dev_lv, host_lv)
    want = handwritten_solve(spec, state, params["T"])
    np.testing.assert_allclose(run.final, want, rtol=1e-4, atol=1e-5)
