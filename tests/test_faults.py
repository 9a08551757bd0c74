"""Fault-injection matrix: every failure mode × every execution engine.

The robustness contract (``docs/robustness.md``) is differential: under a
seeded :class:`FaultPlan`, a *recoverable* run must end byte-identical to
the fault-free oracle, and an *unrecoverable* run must end in a structured
report (``ShardRecoveryError`` / ``TaskGroupError`` / ``StallError``) —
never a hang, never a leaked ``/dev/shm`` segment.

The matrix crosses {worker crash round 0/1/2 (soft and hard), hung worker,
shm attach failure, task-body exception, dropped decrement} with {sharded
materialization, threaded autodec, instrumented Sim, device discover}.  A
seeded fuzz loop (hypothesis when available, deterministic otherwise)
drives random plans through random polyhedral programs asserting the same
byte-identical-or-reported property.

When ``FAULT_ARTIFACT_DIR`` is set (the CI fault-injection job), every
structured report produced here is also written out as JSON.
"""
from __future__ import annotations

import gc
import json
import os
import random
from multiprocessing import shared_memory

import numpy as np
import pytest
from test_backend_differential import _build_program

try:
    from hypothesis import HealthCheck, example, given, settings
    from hypothesis import strategies as st
except ImportError:
    from hypo_stub import HealthCheck, example, given, settings, st

from repro.core.edt import (DROPPED_DECREMENT, SHM_ATTACH_FAIL,
                            ExecutionConfig,
                            TASK_BODY_ERROR, WORKER_CRASH, WORKER_HANG,
                            Fault, FaultPlan, RetryPolicy,
                            ShardRecoveryError, Sim, StallError,
                            TaskGroupError, TiledTaskGraph, DeviceExecutor,
                            poisoned_cone, run_graph_threaded,
                            run_graph_threaded_resilient, simulate_indexed,
                            simulate_indexed_resilient, synthesize_indexed)
from repro.core.edt.shard import _Segments, process_pool
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

FAST_RETRY = RetryPolicy(max_retries=2, base_delay=0.001, timeout=5.0)


def _artifact(name: str, payload: str) -> None:
    d = os.environ.get("FAULT_ARTIFACT_DIR")
    if not d:
        return
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, name + ".json"), "w") as f:
        f.write(payload)


def _shm_listing() -> set:
    try:
        return set(os.listdir("/dev/shm"))
    except OSError:          # non-POSIX-shm platform: leak check is vacuous
        return set()


@pytest.fixture()
def shm_guard(monkeypatch):
    """Assert the test leaked no /dev/shm segments (crashes included).

    Only segments this process created during the test are checked:
    ``/dev/shm`` is shared with every other process on the machine, such
    as parallel test workers, whose live segments are not leaks.
    """
    created = []

    class Recording(shared_memory.SharedMemory):
        def __init__(self, name=None, create=False, size=0, **kw):
            super().__init__(name, create, size, **kw)
            if create:
                created.append(self.name)

    monkeypatch.setattr(shared_memory, "SharedMemory", Recording)
    yield
    monkeypatch.undo()
    gc.collect()
    leaked = sorted(set(created) & _shm_listing())
    assert not leaked, f"leaked shm segments: {leaked}"


def _graph_and_oracle():
    g = TiledTaskGraph(PROGRAMS["trisolv"](), {"S": Tiling((2, 2))},
                      backend="numpy")
    params = {"N": 21}
    return g, params, g.index_graph(params)


def _assert_identical(ig, oracle):
    assert ig.n == oracle.n
    assert np.array_equal(ig.edge_src, oracle.edge_src)
    assert np.array_equal(ig.edge_tgt, oracle.edge_tgt)
    assert np.array_equal(ig.pred_n, oracle.pred_n)
    assert len(ig.stmt_blocks) == len(oracle.stmt_blocks)
    for (sa, ba), (sb, bb) in zip(ig.stmt_blocks, oracle.stmt_blocks):
        assert sa == sb and np.array_equal(ba, bb)


# ===================================================== sharded recovery
SHARD_MATRIX = [
    Fault(kind=WORKER_CRASH, round=0, index=0, times=1),
    Fault(kind=WORKER_CRASH, round=1, index=1, times=2),
    Fault(kind=WORKER_CRASH, round=2, index=0, times=1),
    Fault(kind=WORKER_CRASH, round=1, index=0, times=1, hard=True),
    Fault(kind=WORKER_HANG, round=1, index=0, times=1, delay=2.0),
    Fault(kind=SHM_ATTACH_FAIL, round=2, index=1, times=2),
]


@pytest.mark.parametrize("fault", SHARD_MATRIX,
                         ids=lambda f: f"{f.kind}-r{f.round}-x{f.times}"
                         + ("-hard" if f.hard else ""))
def test_sharded_recoverable_is_byte_identical(fault, shm_guard):
    """Faults within the retry budget: re-materialized shards must land
    byte-identical to the fault-free single-process oracle."""
    g, params, oracle = _graph_and_oracle()
    plan = FaultPlan(faults=(fault,))
    policy = FAST_RETRY if fault.kind != WORKER_HANG else RetryPolicy(
        max_retries=3, base_delay=0.001, timeout=0.6)
    ig = g.index_graph(
        params, config=ExecutionConfig(shards=2, faults=plan, recovery=policy))
    _assert_identical(ig, oracle)
    assert plan.fired, "the fault never actually fired"


def test_sharded_unrecoverable_reports(shm_guard):
    """A fault outliving the retry budget must surface a ShardRecoveryError
    carrying the structured report — and still release every segment."""
    g, params, _ = _graph_and_oracle()
    plan = FaultPlan(faults=(Fault(kind=WORKER_CRASH, round=2, index=1,
                                   times=99),))
    with pytest.raises(ShardRecoveryError) as ei:
        g.index_graph(params, config=ExecutionConfig(
            shards=2, faults=plan, recovery=FAST_RETRY))
    rep = ei.value.report
    assert rep.context == "sharded"
    assert rep.failed and rep.failed[0][0] == (2, 1)
    assert rep.attempts[(2, 1)] == FAST_RETRY.max_retries + 1
    _artifact("sharded_unrecoverable", rep.to_json())


def test_sharded_hard_crash_in_caller_pool_is_unrecoverable(shm_guard):
    """A hard crash breaks the pool; scan_sharded must not rebuild a pool
    it does not own — that is the caller's resource."""
    g, params, _ = _graph_and_oracle()
    plan = FaultPlan(faults=(Fault(kind=WORKER_CRASH, round=0, index=0,
                                   times=1, hard=True),))
    pool = process_pool(2)
    try:
        with pytest.raises(ShardRecoveryError):
            g.index_graph(params, config=ExecutionConfig(
                shards=2, pool=pool, faults=plan, recovery=FAST_RETRY))
    finally:
        pool.shutdown(wait=False)


def test_sharded_faults_without_policy_use_default_retry(shm_guard):
    """faults= without recovery= falls back to the default RetryPolicy —
    injection alone never silently disables recovery."""
    g, params, oracle = _graph_and_oracle()
    plan = FaultPlan(faults=(Fault(kind=WORKER_CRASH, round=1, index=0),))
    ig = g.index_graph(params,
                       config=ExecutionConfig(shards=2, faults=plan))
    _assert_identical(ig, oracle)
    assert plan.fired


def test_sharded_zero_retry_budget_fails_fast(shm_guard):
    """max_retries=0 is structured fail-fast: first failure → report."""
    g, params, _ = _graph_and_oracle()
    plan = FaultPlan(faults=(Fault(kind=WORKER_CRASH, round=1, index=0),))
    with pytest.raises(ShardRecoveryError) as ei:
        g.index_graph(params, config=ExecutionConfig(
            shards=2, faults=plan,
            recovery=RetryPolicy(max_retries=0, base_delay=0.001)))
    assert "injected worker crash" in ei.value.report.failed[0][1]


def test_segments_finalizer_sweeps_on_collection():
    """Satellite 1: dropping a _Segments without release() must still
    unlink its /dev/shm files (weakref.finalize, also runs atexit)."""
    segs = _Segments(enabled=True)
    if not segs.allocate(("S", 0), (8,)):
        pytest.skip("shared memory unavailable on this platform")
    name = segs.slot(("S", 0), 0, 8).shm
    assert name in _shm_listing(), "allocation produced no segment"
    del segs
    gc.collect()
    assert name not in _shm_listing(), "finalizer did not unlink"


# ===================================================== threaded autodec
def test_threaded_aggregates_every_failure():
    """Satellite 2: every (task, exception) pair rides one TaskGroupError."""
    g, params, _ = _graph_and_oracle()
    tasks = list(g.tasks(params))
    _, sched = synthesize_indexed(g, params)
    wide = next(lv for lv in sched.levels if len(lv) >= 2)
    victims = [tasks[int(i)] for i in wide[:2]]
    plan = FaultPlan(faults=tuple(
        Fault(kind=TASK_BODY_ERROR, task=t) for t in victims))
    with pytest.raises(TaskGroupError) as ei:
        run_graph_threaded(g, params, workers=4, faults=plan)
    failed_keys = {k for k, _ in ei.value.failures}
    assert failed_keys == set(victims)
    rep = ei.value.report
    assert rep.context == "threaded" and len(rep.failed) == 2
    _artifact("threaded_taskgroup", rep.to_json())


def test_threaded_quarantine_matches_cone_oracle():
    """Resilient mode cancels exactly the dependent cone of the failure."""
    g, params, _ = _graph_and_oracle()
    tasks = list(g.tasks(params))
    victim = tasks[len(tasks) // 3]
    plan = FaultPlan(faults=(Fault(kind=TASK_BODY_ERROR, task=victim),))
    res = run_graph_threaded_resilient(g, params, workers=4, faults=plan)
    assert not res.ok and res.stall is None
    rep = res.failure
    # closure oracle recomputed independently of the runtime
    cone, frontier = set(), [victim]
    while frontier:
        nxt = []
        for t in frontier:
            for s in g.successors(t, params):
                if s != victim and s not in cone:
                    cone.add(s)
                    nxt.append(s)
        frontier = nxt
    assert set(rep.poisoned) == cone
    assert set(res.executed) == set(tasks) - cone - {victim}
    assert all(t in cone for t in rep.undrained)


def test_threaded_hang_becomes_stall_report():
    g, params, _ = _graph_and_oracle()
    victim = list(g.roots(params))[0]
    plan = FaultPlan(faults=(Fault(kind=WORKER_HANG, task=victim,
                                   delay=3.0),))
    with pytest.raises(StallError) as ei:
        run_graph_threaded(g, params, workers=2, faults=plan,
                           stall_timeout=0.4)
    rep = ei.value.report
    assert rep.context == "threaded"
    assert rep.in_flight >= 1           # the hung body never finished
    _artifact("threaded_stall_hang", rep.to_json())


def test_threaded_dropped_decrement_is_diagnosed():
    """A swallowed signal must not look like success: the runtime quiesces
    incomplete and the stall report names the starved counters."""
    g, params, _ = _graph_and_oracle()
    tasks = list(g.tasks(params))
    victim = tasks[len(tasks) // 2]
    plan = FaultPlan(faults=(Fault(kind=DROPPED_DECREMENT, task=victim),))
    res = run_graph_threaded_resilient(g, params, workers=4, faults=plan,
                                       stall_timeout=5.0)
    assert res.stall is not None
    assert "decrement was dropped" in res.stall.note
    assert victim in res.stall.undrained
    assert victim not in res.executed
    _artifact("threaded_stall_dropped", res.stall.to_json())


def test_threaded_clean_run_unchanged_under_fault_machinery():
    g, params, _ = _graph_and_oracle()
    plain = run_graph_threaded(g, params, workers=4)
    res = run_graph_threaded_resilient(g, params, workers=4,
                                       faults=FaultPlan())
    assert res.ok
    assert set(res.executed) == set(plain)


# ============================================================ sim engine
def test_sim_resilient_clean_is_byte_identical():
    g, params, _ = _graph_and_oracle()
    ig, sched = synthesize_indexed(g, params)
    res = simulate_indexed_resilient(ig, sched)
    ref = simulate_indexed(sched)
    assert res.ok
    assert res.sim.exec_order == ref.exec_order
    assert res.sim.now == ref.now


def test_sim_quarantine_matches_vectorized_cone():
    g, params, _ = _graph_and_oracle()
    ig, sched = synthesize_indexed(g, params)
    victim = int(sched.levels[1][0])
    plan = FaultPlan(faults=(Fault(kind=TASK_BODY_ERROR, task=victim),))
    res = simulate_indexed_resilient(ig, sched, faults=plan)
    assert not res.ok
    rep = res.report
    cone = poisoned_cone(ig.n, ig.edge_src, ig.edge_tgt, [victim])
    assert rep.poisoned == sorted(int(t) for t in cone)
    # the victim was dispatched (its body raised), so it is in exec_order;
    # everything outside its cone ran, nothing inside it was dispatched
    executed = set(res.sim.exec_order)
    assert executed == set(range(ig.n)) - set(cone)
    assert rep.executed + len(rep.poisoned) == ig.n
    _artifact("sim_quarantine", rep.to_json())


def test_sim_on_task_error_hook():
    """The raw Sim hook: a failing run_fn is recorded, the slot is freed,
    and the event loop keeps dispatching instead of unwinding."""
    seen = []
    sim = Sim(workers=1, on_task_error=lambda t, e: seen.append((t, e)))

    def boom():
        raise ValueError("body failed")

    sim.make_ready("bad", boom)
    sim.make_ready("good", lambda: None)
    sim.run()
    assert [t for t, _ in seen] == ["bad"]
    assert [t for t, _ in sim.task_errors] == ["bad"]
    assert "good" in sim.exec_order and "bad" in sim.exec_order


def test_sim_without_hook_still_raises():
    sim = Sim(workers=1)

    def boom():
        raise ValueError("body failed")

    sim.make_ready("bad", boom)
    with pytest.raises(ValueError, match="body failed"):
        sim.run()


# ========================================================== device layer
def test_device_discover_dropped_decrement_stalls_with_report():
    g, params, _ = _graph_and_oracle()
    ig, sched = synthesize_indexed(g, params)
    victim = int(sched.levels[1][0])
    plan = FaultPlan(faults=(Fault(kind=DROPPED_DECREMENT, task=victim),))
    with pytest.raises(StallError) as ei:
        DeviceExecutor(ig, config=ExecutionConfig(faults=plan)).run()
    rep = ei.value.report
    assert rep.context == "device-discover"
    assert victim in rep.undrained
    assert plan.fired
    _artifact("device_stall_dropped", rep.to_json())


def test_device_discover_clean_run_ignores_empty_plan():
    g, params, _ = _graph_and_oracle()
    ig, sched = synthesize_indexed(g, params)
    clean = DeviceExecutor(ig).run()
    fp = DeviceExecutor(ig, config=ExecutionConfig(faults=FaultPlan())).run()
    assert [np.asarray(a).tolist() for a in fp.levels] == \
           [np.asarray(a).tolist() for a in clean.levels]


# ============================================================== fuzzing
def _fuzz_one(seed: int) -> None:
    rng = random.Random(seed)
    prog, tilings, params = _build_program(rng)
    g = TiledTaskGraph(prog, tilings, backend="numpy")
    oracle = g.index_graph(params)
    plan = FaultPlan.random(seed, n_jobs=2,
                            kinds=(WORKER_CRASH, SHM_ATTACH_FAIL))
    try:
        ig = g.index_graph(params, config=ExecutionConfig(
            shards=2, faults=plan, recovery=FAST_RETRY))
    except ShardRecoveryError as e:
        assert not _reached(plan).recoverable(FAST_RETRY.max_retries)
        assert e.report.failed
        _artifact(f"fuzz_seed{seed}", e.report.to_json())
    else:
        assert _reached(plan).recoverable(FAST_RETRY.max_retries)
        _assert_identical(ig, oracle)


def _reached(plan: FaultPlan) -> FaultPlan:
    """The faults of ``plan`` whose target job the run scheduled.

    A scheduled target fails its first attempt, so its ``(round, job)``
    site is in the plan's fired log.  A fault aimed at a job the run never
    schedules (a round-2 job index past that round's jobs) never fires and
    cannot fail the run, whatever its ``times``.
    """
    sites = {where for _, where, *_ in plan.fired}
    return FaultPlan(faults=[f for f in plan.faults
                             if (f.round, f.index) in sites])


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_random_fault_plans(seed, shm_guard):
    """Byte-identical-or-reported over random plans × random programs."""
    _fuzz_one(seed)


@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(st.integers(min_value=0, max_value=10_000))
@example(seed=5487)   # an unrecoverable budget aimed at an unscheduled job
def test_fuzz_random_fault_plans_hypothesis(seed):
    _fuzz_one(seed)


def test_fault_plan_random_is_deterministic():
    a = FaultPlan.random(1234, n_jobs=4)
    b = FaultPlan.random(1234, n_jobs=4)
    assert a.faults == b.faults


def test_fault_plan_report_roundtrip():
    """Report JSON must be loadable — the CI artifact contract."""
    g, params, _ = _graph_and_oracle()
    victim = list(g.tasks(params))[5]
    plan = FaultPlan(faults=(Fault(kind=TASK_BODY_ERROR, task=victim),))
    res = run_graph_threaded_resilient(g, params, workers=2, faults=plan)
    doc = json.loads(res.failure.to_json())
    assert doc["context"] == "threaded"
    assert doc["n_failed"] == 1
