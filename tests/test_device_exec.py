"""Differential suite for the device-resident wavefront executor.

Every execution path that can drive a schedule must agree, bit for bit:

* ``DeviceExecutor`` discover mode (counters-only frontier derivation on
  the jax layer, XLA step and pallas-kernel step),
* ``DeviceExecutor`` replay mode (O(V+E) schedule sweep with on-device
  counted-sync validation),
* the host oracle: ``synthesize_indexed`` levels executed by
  ``simulate_indexed`` on the instrumented Sim.

Graphs come from the same seeded random-program generator as the backend
differential harness (``tests/test_backend_differential.py``) and are
built through the fraction / compiled / numpy backends and the sharded
engine — the device layer must be insensitive to how the index arrays were
produced.  The suite also covers the failure modes (cyclic graphs, sched-
ules that are not the counted execution), the pallas kernel's NumPy oracle
and its graceful absence, and the ≥1M-task jacobi2d acceptance run.
"""
from __future__ import annotations

import random

import numpy as np
import pytest
from test_backend_differential import _build_program

from repro.core.edt import (DeviceExecutor, ExecutionConfig, IndexedGraph,
                            TiledTaskGraph, levels_from_array,
                            simulate_indexed, synthesize_indexed)
from repro.core.edt.device import (decrement_reference, make_pallas_step,
                                   pack_graph, pack_schedule)
from repro.core.edt.shard import process_pool
from repro.core.edt.wavefront import IndexedSchedule
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

BACKENDS = ("fraction", "compiled", "numpy")


@pytest.fixture(scope="module")
def pool():
    p = process_pool(2)
    p.submit(int, 0).result()
    yield p
    p.shutdown()


# ------------------------------------------------------------- comparator
def assert_device_matches_host(graph: TiledTaskGraph, params: dict,
                               shards=None, pool=None) -> None:
    """The differential property: device frontiers == host frontiers."""
    ig, sched = synthesize_indexed(
        graph, params, config=ExecutionConfig(shards=shards, pool=pool))
    runs = {
        "discover": DeviceExecutor(ig).run(),
        "replay": DeviceExecutor(ig, schedule=sched).run(),
    }
    sim = simulate_indexed(sched, workers=3)
    host_order = sim.exec_order
    for label, run in runs.items():
        # every task exactly once
        order = run.exec_order
        assert order.shape[0] == ig.n, label
        if ig.n:
            assert np.array_equal(np.sort(order), np.arange(ig.n)), label
        # topological: every edge crosses levels forward
        if ig.n_edges:
            assert (run.level_of[ig.edge_src]
                    < run.level_of[ig.edge_tgt]).all(), label
        # per-level frontiers byte-identical to the host schedule
        assert len(run.levels) == sched.depth, label
        for dev_lv, host_lv in zip(run.levels, sched.levels):
            assert dev_lv.dtype == host_lv.dtype, label
            assert np.array_equal(dev_lv, host_lv), label
        assert run.level_of.dtype == sched.level_of.dtype, label
        assert np.array_equal(run.level_of, sched.level_of), label
        # and the Sim replays exactly that order
        assert order.tolist() == host_order, label
        # Sim-mirror counters
        c = run.counters
        assert c.tasks_started == c.tasks_finished == ig.n, label
        assert c.depth == sched.depth, label
        assert c.max_in_flight == sched.max_width, label
        assert c.level_widths.tolist() == [lv.size for lv in sched.levels]


# ---------------------------------------------------------- differential
def test_differential_device_random_programs(pool):
    """Seeded sweep: random polyhedral programs, every build path."""
    rng = random.Random(20260731)
    for case in range(8):
        prog, tilings, params = _build_program(rng)
        for backend in BACKENDS:
            g = TiledTaskGraph(prog, tilings, backend=backend)
            assert_device_matches_host(g, params)
        g = TiledTaskGraph(prog, tilings, backend="numpy")
        assert_device_matches_host(g, params, shards=2, pool=pool)


def test_differential_device_named_programs(pool):
    """The paper-suite anchors (triangular, multi-dep, stencil, edgeless)."""
    cases = [
        ("trisolv", (2, 2), {"N": 21}),
        ("seidel1d", (3, 3), {"T": 9, "N": 21}),
        ("diamond", (1, 1), {"K": 9}),
        ("pipeline", (1, 1), {"M": 12, "S": 5}),
        ("embarrassing", (3,), {"N": 17}),
    ]
    for name, tiles, params in cases:
        g = TiledTaskGraph(PROGRAMS[name](), {"S": Tiling(tiles)},
                           backend="numpy")
        assert_device_matches_host(g, params)
        assert_device_matches_host(g, params, shards=2, pool=pool)


def test_device_packing_layout():
    """CSR + transpose-CSR columns agree with the flat edge arrays."""
    g = TiledTaskGraph(PROGRAMS["trisolv"](), {"S": Tiling((2, 2))},
                       backend="numpy")
    ig = g.index_graph({"N": 15})
    dg = pack_graph(ig)
    assert dg.n == ig.n and dg.n_edges == ig.n_edges
    assert dg.indptr[-1] == dg.n_edges == dg.dec_ptr[-1]
    # successors of each task in CSR order == lex-sorted edge targets
    order = np.argsort(ig.edge_src, kind="stable")
    assert np.array_equal(dg.succ, ig.edge_tgt[order])
    # per-target group sizes are exactly the §4.3 counters
    assert np.array_equal(np.diff(dg.dec_ptr), ig.pred_n)
    assert np.array_equal(dg.pred_n, ig.pred_n)


# ------------------------------------------------------------- failures
def _two_task_cycle() -> IndexedGraph:
    blocks = [("S", np.asarray([[0], [1]], dtype=np.int64))]
    return IndexedGraph(
        stmt_blocks=blocks, n=2,
        edge_src=np.asarray([0, 1], dtype=np.int64),
        edge_tgt=np.asarray([1, 0], dtype=np.int64),
        pred_n=np.asarray([1, 1], dtype=np.int64))


def test_discover_detects_cycle():
    """Still a RuntimeError matching "cycle" (back-compat), but now a
    StallError carrying the structured report with the starved counters."""
    from repro.core.edt import StallError
    with pytest.raises(RuntimeError, match="cycle") as ei:
        DeviceExecutor(_two_task_cycle()).run()
    assert isinstance(ei.value, StallError)
    rep = ei.value.report
    assert rep.context == "device-discover"
    assert rep.started == 0 and set(rep.undrained) == {0, 1}


def test_replay_rejects_non_counted_schedule():
    """A schedule that is topologically valid but not the earliest-start
    counted execution (a task delayed past its frontier) must be flagged
    by the on-device validation — with the offending level and task ids
    named in the structured payload."""
    from repro.core.edt import ScheduleValidationError
    g = TiledTaskGraph(PROGRAMS["diamond"](), {"S": Tiling((1, 1))},
                       backend="numpy")
    ig, sched = synthesize_indexed(g, {"K": 6})
    lv = sched.level_of.copy()
    moved = sched.levels[1][0]
    lv[moved] += 2                      # push one task two levels late
    bad = IndexedSchedule(levels=levels_from_array(lv), level_of=lv)
    with pytest.raises(RuntimeError, match="counted-sync") as ei:
        DeviceExecutor(ig, schedule=bad).run()
    e = ei.value
    assert isinstance(e, ScheduleValidationError)
    # the delayed task never decremented its successors, so the schedule
    # runs them not-ready one level after the delay
    assert e.kind == "not-ready"
    assert e.level == 2
    succ = ig.edge_tgt[ig.edge_src == int(moved)]
    assert set(e.task_ids) == set(int(s) for s in succ)
    assert e.counters["tasks"] == ig.n
    assert e.counters["device_not_ready"] == len(succ)


def test_replay_rejects_swapped_levels():
    from repro.core.edt import ScheduleValidationError
    g = TiledTaskGraph(PROGRAMS["diamond"](), {"S": Tiling((1, 1))},
                       backend="numpy")
    ig, sched = synthesize_indexed(g, {"K": 6})
    lv = sched.level_of.copy()
    a, b = sched.levels[1][0], sched.levels[3][0]
    lv[a], lv[b] = lv[b], lv[a]         # order violation across levels
    bad = IndexedSchedule(levels=levels_from_array(lv), level_of=lv)
    with pytest.raises(RuntimeError, match="counted-sync") as ei:
        DeviceExecutor(ig, schedule=bad).run()
    e = ei.value
    # the late-level task scheduled early has an undrained counter there
    assert isinstance(e, ScheduleValidationError)
    assert e.kind == "not-ready"
    assert e.level == 1
    assert int(b) in e.task_ids


def test_pack_schedule_rejects_duplicate_ids():
    g = TiledTaskGraph(PROGRAMS["diamond"](), {"S": Tiling((1, 1))},
                       backend="numpy")
    ig, sched = synthesize_indexed(g, {"K": 4})
    lv = sched.levels[0].copy()
    levels = [np.concatenate([lv, lv[:1]])] + sched.levels[1:]
    with pytest.raises(ValueError, match="exactly-once"):
        pack_schedule(ig, IndexedSchedule(levels=levels,
                                          level_of=sched.level_of))


# -------------------------------------------------------------- pallas
def _small_graph():
    g = TiledTaskGraph(PROGRAMS["seidel1d"](), {"S": Tiling((2, 2))},
                       backend="numpy")
    return synthesize_indexed(g, {"T": 8, "N": 18})


def test_pallas_step_matches_reference_and_xla():
    """One wavefront step: NumPy oracle == XLA step == pallas kernel
    (interpret mode on this CPU-only container), on every frontier of a
    real sweep."""
    import jax.numpy as jnp

    from repro.core.edt.device import _step_xla

    ig, sched = _small_graph()
    dg = pack_graph(ig)
    xla = _step_xla(jnp)
    pal = make_pallas_step(dg.n, dg.n_edges, interpret=True)
    indeg = dg.pred_n.copy()
    frontier = indeg == 0
    for _ in range(sched.depth):
        ref_indeg, ref_newly = decrement_reference(
            indeg, frontier, dg.dec_src, dg.dec_ptr)
        for name, step in (("xla", xla), ("pallas", pal)):
            got_indeg, got_newly = step(
                jnp.asarray(indeg), jnp.asarray(frontier),
                jnp.asarray(dg.dec_src), jnp.asarray(dg.dec_ptr))
            assert np.array_equal(np.asarray(got_indeg), ref_indeg), name
            assert np.array_equal(np.asarray(got_newly), ref_newly), name
        indeg, frontier = ref_indeg, ref_newly
    assert not frontier.any() and (indeg == 0).all()


def test_pallas_discover_run_identical():
    ig, sched = _small_graph()
    run = DeviceExecutor(ig, use_pallas=True).run()
    assert [lv.tolist() for lv in run.levels] == [
        lv.tolist() for lv in sched.levels]
    # the kernel prices the discover sweep only; silently measuring the
    # replay scatter path under a "pallas" label would mislead
    with pytest.raises(TypeError, match="discover sweep only"):
        DeviceExecutor(ig, schedule=sched, use_pallas=True)


# ------------------------------------------------------------- at scale
def test_million_task_jacobi2d_device_matches_host(pool):
    """The acceptance run: a ≥1M-task jacobi2d schedule end-to-end on the
    device executor, frontiers identical to what ``simulate_indexed``
    executes on the host Sim.

    In replay mode the run's level arrays ARE the validated input schedule
    (comparing them back to ``sched`` would be vacuous), so frontier
    identity rests on (1) the on-device violation counters — ``run()``
    raises unless the schedule is exactly the counted execution, a check
    the corrupt-schedule tests above prove has teeth — plus (2) an
    independent host-side check that every edge crosses frontiers forward,
    and (3) the Sim executing the same order."""
    g = TiledTaskGraph(PROGRAMS["jacobi2d"](), {"S": Tiling((2, 2, 2))},
                       backend="numpy")
    params = {"T": 32, "N": 512}
    ig, sched = synthesize_indexed(
        g, params, config=ExecutionConfig(shards=2, pool=pool))
    assert ig.n >= 1_000_000
    run = DeviceExecutor(ig, schedule=sched).run()   # (1) validates on device
    assert run.counters.tasks_finished == ig.n
    assert run.counters.depth == sched.depth
    assert run.counters.max_in_flight == sched.max_width
    # (2) independent of the device path and of synthesize_indexed's own
    # leveling loop: raw edge columns against the executed levels
    assert (run.level_of[ig.edge_src] < run.level_of[ig.edge_tgt]).all()
    order = run.exec_order
    assert np.array_equal(np.sort(order), np.arange(ig.n))
    # (3) the host Sim replays the identical order
    sim = simulate_indexed(sched, workers=8)
    assert order.shape[0] == len(sim.exec_order)
    assert np.array_equal(order, np.asarray(sim.exec_order))
