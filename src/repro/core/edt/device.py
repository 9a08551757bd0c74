"""Device-resident wavefront execution: index graphs on the jax/pallas layer.

The host executors (:class:`~repro.core.edt.executor.Sim`, the dict-based
sync models) re-serialize every schedule through Python — fine for counter
semantics, hopeless for driving a million tasks from a device.  This module
is the step ROADMAP calls "feed ``index_graph()`` / wavefront index arrays
into the jax/pallas execution layer directly": the flat arrays the numpy
backend and the sharded engine already produce are packed **once** into
device-resident jax arrays, and the §2 *counted* synchronization model —
predecessor counters decremented by completions, a task ready exactly when
its counter drains — runs as an XLA loop that never returns to host between
wavefronts.

Two sweeps share the packed graph:

* **discover** (no schedule input) — the device derives the frontiers
  itself.  State is ``(indeg, frontier)``; each :func:`jax.lax.while_loop`
  iteration decrements every frontier task's successors and emits the next
  ready frontier from the counters alone.  The decrement is a segment-sum
  over the transpose-CSR edge columns (gather + cumsum + boundary
  difference — XLA's scatter-add is ~10x slower on CPU for million-edge
  graphs), available either as fused jnp ops or as a pallas kernel
  (``use_pallas=True``), which runs in interpret mode only: Mosaic refuses
  it for the TPU (:func:`make_pallas_step`).  Work is
  ``O(depth * (V + E))`` — the dense-frontier tradeoff every fixed-shape
  runtime makes.
* **replay** (schedule packed too) — the million-task path.  Edges are
  pre-sorted by source wavefront, so one :func:`jax.lax.fori_loop` over
  levels touches each edge exactly once (``O(V + E)`` total): a level's
  out-edges are a contiguous slice, sliced at fixed padded width and
  scatter-decremented.  The counters are *checked*, not merely trusted: a
  violation counter accumulates (a) any task whose counter is nonzero when
  its level starts, (b) any task whose counter drained before the level
  preceding its own (it would have been ready earlier — a frontier
  mismatch), and (c) any counter left undrained at the end.  All three at
  zero proves the packed schedule is exactly the counted-model execution —
  the same per-level frontiers ``simulate_indexed`` feeds the host Sim.

:class:`DeviceExecutor` wraps both behind one ``run()``, mirroring the
Sim's observable counters (tasks started/finished, max in-flight, per-level
widths) so ``benchmarks/bench_executor.py`` can price host vs device
dispatch per task.  See ``docs/device_exec.md`` for the array layout and
measured numbers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Union

import numpy as np

from .config import UNSET, resolve_execution
from .faults import DROPPED_DECREMENT
from .recovery import ScheduleValidationError, StallError, StallReport
from .taskgraph import IndexedGraph, TiledTaskGraph
from .wavefront import IndexedSchedule, levels_from_array

_I32_MAX = np.iinfo(np.int32).max


def _interpret_default() -> bool:
    import jax

    return jax.default_backend() == "cpu"


# ------------------------------------------------------------------ packing
@dataclass
class DeviceGraph:
    """An :class:`IndexedGraph` as device-resident int32 arrays.

    Successors are CSR by source (the put-loop order: ``succ[indptr[t] :
    indptr[t+1]]`` are task ``t``'s out-edges, lexicographic); the
    transpose columns (``dec_src`` grouped by target via ``dec_ptr``) drive
    the counter decrement as a segment sum.  ``pred_n`` is the §4.3 counter
    init vector.  Everything is int32 — a graph near 2^31 tasks or edges
    does not fit a single device anyway.
    """

    n: int
    n_edges: int
    indptr: "np.ndarray"     # i32[n+1]  CSR row starts, source-major
    succ: "np.ndarray"       # i32[E]    edge targets, source-major lex order
    dec_src: "np.ndarray"    # i32[E]    edge sources, target-major order
    dec_ptr: "np.ndarray"    # i32[n+1]  per-target boundaries into dec_src
    pred_n: "np.ndarray"     # i32[n]    §4.3 predecessor counts


@dataclass
class DeviceSchedule:
    """An :class:`IndexedSchedule` packed for the replay sweep.

    ``order`` concatenates the levels (each level's ids ascend) and is
    padded with the sentinel id ``n`` so every level can be read as one
    fixed-size ``dynamic_slice`` of ``w_pad`` ids; ``task_ptr`` holds the
    level boundaries (two trailing entries pin the one-past-end reads).
    ``lvl_tgt`` holds every edge's *target*, sorted stably by the source's
    level, ``e_pad``-padded likewise — a level's out-edges are the slice
    ``[edge_ptr[l], edge_ptr[l+1])``, so the whole sweep touches each edge
    once.

    ``origin`` (optional, set by the fused executor's packing) carries the
    per-task tile-origin columns — row ``t`` is task ``t``'s iteration-space
    origin (tile coords × tile sizes), with a sentinel row at index ``n``
    whose negative time coordinate masks padded lanes; see
    :func:`~repro.core.edt.fused.pack_origins`.
    """

    depth: int
    w_pad: int               # max level width (slice size for task ids)
    e_pad: int               # max out-edges of any level (slice size)
    order: "np.ndarray"      # i32[n + w_pad], sentinel-padded level concat
    task_ptr: "np.ndarray"   # i32[depth+2]
    lvl_tgt: "np.ndarray"    # i32[E + e_pad], sentinel-padded
    edge_ptr: "np.ndarray"   # i32[depth+1]
    levels: list             # the source IndexedSchedule levels (int64 ids)
    level_of: "np.ndarray"   # int64[n]
    origin: Optional["np.ndarray"] = None   # i32[n+1, ndim] tile origins


def pack_graph(ig: IndexedGraph) -> DeviceGraph:
    """CSR + transpose-CSR + counter-init columns, int32, host-side."""
    n, e = ig.n, ig.n_edges
    if max(n, e) >= _I32_MAX:
        raise ValueError(f"graph too large for int32 device ids: {n=} {e=}")
    order = np.argsort(ig.edge_src, kind="stable")
    succ = ig.edge_tgt[order].astype(np.int32)
    indptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(np.bincount(ig.edge_src, minlength=n), out=indptr[1:])
    torder = np.argsort(ig.edge_tgt, kind="stable")
    dec_src = ig.edge_src[torder].astype(np.int32)
    dec_ptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(ig.pred_n, out=dec_ptr[1:])
    return DeviceGraph(n=n, n_edges=e, indptr=indptr, succ=succ,
                       dec_src=dec_src, dec_ptr=dec_ptr,
                       pred_n=ig.pred_n.astype(np.int32))


def pack_schedule(ig: IndexedGraph, schedule: IndexedSchedule,
                  origins: Optional["np.ndarray"] = None) -> DeviceSchedule:
    """Level-major task and edge columns for the O(V+E) replay sweep.

    ``origins`` (from :func:`~repro.core.edt.fused.pack_origins`) attaches
    the fused executor's tile-origin columns so one packed object carries
    everything the fused replay sweep reads.
    """
    n = ig.n
    if max(n, ig.n_edges) >= _I32_MAX:
        raise ValueError(
            f"graph too large for int32 device ids: n={n} e={ig.n_edges}")
    depth = schedule.depth
    widths = np.asarray([lv.size for lv in schedule.levels], dtype=np.int64)
    order = (np.concatenate(schedule.levels).astype(np.int32) if depth
             else np.zeros(0, dtype=np.int32))
    counts = np.bincount(order, minlength=n) if n else np.zeros(0, np.int64)
    if order.shape[0] != n or (n and (counts != 1).any()):
        raise ValueError("schedule is not an exactly-once permutation of "
                         "the graph's task ids")
    w_pad = int(widths.max()) if depth else 1
    task_ptr = np.zeros(depth + 2, dtype=np.int32)
    task_ptr[1:depth + 1] = np.cumsum(widths)
    task_ptr[depth + 1] = n
    lv_src = schedule.level_of[ig.edge_src]
    eorder = np.argsort(lv_src, kind="stable")
    ecounts = np.bincount(lv_src, minlength=max(depth, 1))
    e_pad = max(int(ecounts.max()), 1)
    edge_ptr = np.zeros(depth + 1, dtype=np.int32)
    edge_ptr[1:] = np.cumsum(ecounts[:depth])
    sent = np.int32(n)
    return DeviceSchedule(
        depth=depth, w_pad=w_pad, e_pad=e_pad,
        order=np.concatenate([order, np.full(w_pad, sent, np.int32)]),
        task_ptr=task_ptr,
        lvl_tgt=np.concatenate([ig.edge_tgt[eorder].astype(np.int32),
                                np.full(e_pad, sent, np.int32)]),
        edge_ptr=edge_ptr,
        levels=schedule.levels, level_of=schedule.level_of, origin=origins)


# ----------------------------------------------------------- decrement step
def decrement_reference(indeg, frontier, dec_src, dec_ptr):
    """Pure-NumPy oracle for one counted-sync wavefront step.

    Given the current counters, the frontier mask, and the transpose-CSR
    edge columns: decrement each task's counter by its in-edges from the
    frontier and report which tasks just became ready.  Returns
    ``(new_indeg, newly_ready_mask)``.
    """
    active = frontier[dec_src].astype(np.int32)
    c = np.zeros(active.shape[0] + 1, dtype=np.int32)
    np.cumsum(active, out=c[1:])
    dec = c[dec_ptr[1:]] - c[dec_ptr[:-1]]
    new_indeg = indeg - dec
    return new_indeg, (new_indeg == 0) & (dec > 0)


def make_xla_step():
    """The wavefront step as fused XLA ops, ready to jit.

    Public spelling of the discover sweep's default decrement
    (:func:`_step_xla`): the distributed runtime's device rank engine
    steps each rank's *local* counters through this exact function, so a
    per-rank sweep is observably the single-host sweep restricted to the
    rank's task range (``core/edt/distributed.py``).
    """
    import jax.numpy as jnp

    return _step_xla(jnp)


def _step_xla(jnp):
    """The reference step as fused XLA ops (the default device path)."""

    def step(indeg, frontier, dec_src, dec_ptr):
        active = frontier[dec_src].astype(jnp.int32)
        c = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(active, dtype=jnp.int32)])
        dec = c[dec_ptr[1:]] - c[dec_ptr[:-1]]
        new_indeg = indeg - dec
        return new_indeg, (new_indeg == 0) & (dec > 0)

    return step


def make_pallas_step(n: int, n_edges: int, interpret: Optional[bool] = None):
    """The wavefront step as one pallas kernel (decrement + frontier emit).

    The kernel reads the counters, the frontier, and the transpose-CSR edge
    columns as whole-array blocks and writes the decremented counters plus
    the newly-ready mask.  It exists in interpret mode only: Mosaic, the
    TPU kernel compiler, cannot lower its vector gather
    (``tests/test_tpu_compile.py`` pins the refusal).  ``interpret``
    defaults to True on the CPU backend and False elsewhere, and asking
    for the compiled kernel off the CPU raises ``NotImplementedError``
    here, at construction, instead of a compiler error inside the first
    sweep.  The XLA step computes the same decrement
    (tests/test_device_exec.py asserts both against
    :func:`decrement_reference`).
    """
    # deferred so that importing this module (and therefore
    # repro.core.edt, incl. in every ProcessPool worker of the sharded
    # engine) stays jax-free on the host-only paths
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = _interpret_default()
    if not interpret and jax.default_backend() != "cpu":
        raise NotImplementedError(
            f"use_pallas=True has no compiled kernel on "
            f"{jax.default_backend()}: Mosaic (the TPU kernel compiler) "
            "refuses the pallas decrement kernel with 'Only 2D gather is "
            "supported' on its vector gather frontier[dec_src].  Use the "
            "XLA step (use_pallas=False), which computes the same "
            "decrement, or interpret=True to run the kernel interpreted")
    if n_edges == 0:
        # zero-length blocks break the pallas interpreter, and an edgeless
        # graph has a trivial step: nothing decrements, nothing becomes ready
        def step(indeg, frontier, dec_src, dec_ptr):
            return indeg, jnp.zeros(n, jnp.bool_)

        return step

    def kernel(indeg_ref, frontier_ref, dec_src_ref, dec_ptr_ref,
               out_indeg_ref, newly_ref):
        indeg = indeg_ref[...]
        active = frontier_ref[...][dec_src_ref[...]].astype(jnp.int32)
        c = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(active, dtype=jnp.int32)])
        ptr = dec_ptr_ref[...]
        dec = c[ptr[1:]] - c[ptr[:-1]]
        new_indeg = indeg - dec
        out_indeg_ref[...] = new_indeg
        newly_ref[...] = (new_indeg == 0) & (dec > 0)

    call = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.bool_)),
        interpret=interpret,
    )

    def step(indeg, frontier, dec_src, dec_ptr):
        return call(indeg, frontier, dec_src, dec_ptr)

    return step


# ---------------------------------------------------------------- diagnosis
def _diagnose_replay(dg: DeviceGraph, ds: DeviceSchedule):
    """Host-side replay of the on-device validation, naming the offenders.

    The device sweep accumulates violation *counts* (cheap scalars inside
    the XLA loop); when any is nonzero this NumPy twin re-walks the levels
    with the identical check order — (a) level tasks not ready, (b) next
    level ready early, (c) end-of-sweep undrained counters — and returns
    ``(kind, level, offending task ids, counter state)`` for the first
    violation, so the raised error carries evidence, not just totals.
    """
    indeg = dg.pred_n.astype(np.int64).copy()
    indptr = dg.indptr.astype(np.int64)
    succ = dg.succ.astype(np.int64)
    for level, ids in enumerate(ds.levels):
        bad = ids[indeg[ids] != 0]
        if bad.size:
            return "not-ready", level, bad, indeg
        if level + 1 < ds.depth:
            nxt = ds.levels[level + 1]
            early = nxt[indeg[nxt] == 0]
            if early.size:
                return "early-ready", level + 1, early, indeg
        starts = indptr[ids]
        counts = indptr[ids + 1] - starts
        tot = int(counts.sum())
        if tot:
            csum = np.cumsum(counts)
            eidx = (np.repeat(starts - (csum - counts), counts)
                    + np.arange(tot, dtype=np.int64))
            np.subtract.at(indeg, succ[eidx], 1)
    und = np.flatnonzero(indeg != 0)
    return "undrained", ds.depth, und, indeg


def _counter_summary(indeg: "np.ndarray") -> dict:
    und = np.flatnonzero(indeg != 0)
    return {
        "tasks": int(indeg.shape[0]),
        "undrained": int(und.size),
        "undrained_ids": und[:32].tolist(),
        "max_residual": int(indeg[und].max()) if und.size else 0,
    }


# ----------------------------------------------------------------- counters
@dataclass
class DeviceCounters:
    """The Sim-observable counters, measured on device.

    ``tasks_started``/``tasks_finished`` mirror the Sim's dispatch counts
    (on the device every started wavefront task finishes within its level);
    ``max_in_flight`` is the widest wavefront — what the Sim's
    ``inflight_tasks`` gauge peaks at once workers outnumber the frontier;
    ``level_widths`` are the per-level batch sizes ``make_ready_ids`` would
    see on the host.
    """

    tasks_started: int
    tasks_finished: int
    max_in_flight: int
    depth: int
    level_widths: "np.ndarray"

    def summary(self) -> dict:
        n = self.tasks_started
        return {"tasks_started": n,
                "tasks_finished": self.tasks_finished,
                "max_in_flight": self.max_in_flight,
                "depth": self.depth,
                "avg_width": n / max(1, self.depth)}


@dataclass
class DeviceRun:
    """Result of one device sweep: frontiers + counters, host-side.

    In discover mode ``levels``/``level_of`` are *computed* by the sweep;
    in replay mode they are the input schedule's own arrays, returned only
    after the on-device violation counters proved the schedule is exactly
    the counted-model execution — so "the frontiers match" is established
    by that validation, not by comparing these arrays back to their
    source.
    """

    mode: str                  # "discover" | "replay"
    levels: list               # int64 id arrays per level — the frontiers
    level_of: "np.ndarray"     # int64[n]
    counters: DeviceCounters

    @property
    def exec_order(self) -> "np.ndarray":
        """Global task ids in execution order (level-major, ids ascending
        within a level) — the Sim's ``exec_order`` for the same schedule."""
        if not self.levels:
            return np.zeros(0, dtype=np.int64)
        return np.concatenate(self.levels)


# ---------------------------------------------------------------- executor
class DeviceExecutor:
    """Counted-sync execution of an index graph on the jax layer.

    Construct from a :class:`TiledTaskGraph` (``params`` required;
    ``config=``/``session=`` drive the generation scans — shard fan-out,
    pool, recovery; a session serves the graph from its cache) or directly
    from an :class:`IndexedGraph`.  The per-call
    ``shards=``/``parallel=``/``pool=``/``faults=`` kwargs are the
    deprecated spelling of the same config; ``config.faults`` also arms
    execution-side injection (dropped decrements) exactly as the old
    ``faults=`` did.  With ``schedule=`` (an :class:`IndexedSchedule`,
    e.g. from ``synthesize_indexed``) the O(V+E) replay sweep runs and
    *validates* the schedule against the counters; without it the discover
    sweep derives the frontiers on device.  ``packed=(DeviceGraph,
    DeviceSchedule | None)`` skips the host-side packing entirely — the
    graph cache hands its stored device columns through here, so a warm
    executor build is allocation-free.  ``use_pallas=True`` routes the
    discover decrement through the pallas kernel (``interpret=`` overrides
    the CPU auto-fallback).

    ``run()`` returns a :class:`DeviceRun` whose ``levels`` are
    byte-identical to ``synthesize_indexed``'s for the same graph and whose
    ``exec_order`` matches what ``simulate_indexed`` records on the host
    Sim — asserted across backends and shard counts by
    ``tests/test_device_exec.py``.
    """

    def __init__(self, graph: Union[TiledTaskGraph, IndexedGraph],
                 params: Optional[dict] = None, *,
                 schedule: Optional[IndexedSchedule] = None,
                 shards=UNSET, parallel=UNSET, pool=UNSET, faults=UNSET,
                 use_pallas: bool = False,
                 interpret: Optional[bool] = None,
                 config=None, session=None, packed=None):
        cfg, sess = resolve_execution(
            config, session, stacklevel=3,
            legacy=dict(shards=shards, parallel=parallel, pool=pool,
                        faults=faults))
        if isinstance(graph, TiledTaskGraph):
            if params is None:
                raise TypeError("params required with a TiledTaskGraph")
            ig = (sess.index_graph(graph, params) if sess is not None
                  else graph._index_graph_cfg(params, cfg))
        else:
            ig = graph
        self.faults = cfg.faults
        if packed is not None and schedule is not None:
            raise TypeError("pass schedule= or packed=, not both")
        if use_pallas and (schedule is not None
                           or (packed is not None and packed[1] is not None)):
            raise TypeError(
                "use_pallas applies to the discover sweep only; the replay "
                "sweep's decrement is a per-level scatter, not the pallas "
                "wavefront kernel — drop schedule= to price the kernel")
        self.ig = ig
        if packed is not None:
            self.dg, self.ds = packed
        else:
            self.dg = pack_graph(ig)
            self.ds = (pack_schedule(ig, schedule)
                       if schedule is not None else None)
        self.use_pallas = use_pallas
        self.interpret = interpret
        # compiled sweeps + uploaded arrays, built lazily on the first run()
        # and reused after — repeat runs pay dispatch, not jit, cost
        self._discover_fn = None
        self._replay_fn = None
        if use_pallas:  # resolve (and fail) eagerly, not mid-sweep
            self._pallas_step = make_pallas_step(
                self.dg.n, self.dg.n_edges, interpret)

    # ------------------------------------------------------------- sweeps
    def run(self) -> DeviceRun:
        if self.dg.n == 0:
            counters = DeviceCounters(0, 0, 0, 0, np.zeros(0, np.int64))
            return DeviceRun("replay" if self.ds is not None else "discover",
                             [], np.zeros(0, np.int64), counters)
        if self.ds is not None:
            return self._run_replay()
        return self._run_discover()

    def _build_discover(self):
        """The jitted discover sweep over the state tuple ``(indeg,
        frontier, level, level_of, started, max_width)``."""
        import jax
        import jax.numpy as jnp

        dg = self.dg
        step = (self._pallas_step if self.use_pallas else _step_xla(jnp))
        dec_src = jnp.asarray(dg.dec_src)
        dec_ptr = jnp.asarray(dg.dec_ptr)

        def cond(state):
            return state[1].any()

        def body(state):
            indeg, frontier, level, level_of, started, maxw = state
            w = frontier.sum().astype(jnp.int32)
            level_of = jnp.where(frontier, level, level_of)
            indeg, newly = step(indeg, frontier, dec_src, dec_ptr)
            return (indeg, newly, level + 1, level_of, started + w,
                    jnp.maximum(maxw, w))

        return jax.jit(lambda s: jax.lax.while_loop(cond, body, s))

    def _run_discover(self) -> DeviceRun:
        import jax.numpy as jnp

        dg = self.dg
        n = dg.n
        if self._discover_fn is None:
            self._discover_fn = self._build_discover()
        pred_host = dg.pred_n
        if self.faults is not None:
            # DROPPED_DECREMENT: the counter is initialized one too high,
            # so the matching signal "never arrives" — the exact state a
            # lost decrement leaves behind in the counted model
            dropped = [int(t) for t in self.faults.dropped_tasks()]
            if dropped:
                pred_host = pred_host.copy()
                for t in dropped:
                    pred_host[t] += 1
                    self.faults.record(DROPPED_DECREMENT, t, 0)
        pred = jnp.asarray(pred_host)
        init = (pred, pred == 0, jnp.int32(0),
                jnp.full(n, -1, jnp.int32), jnp.int32(0), jnp.int32(0))
        out = self._discover_fn(init)
        indeg, _, depth, level_of, started, maxw = (np.asarray(x) for x in out)
        started = int(started)
        if started != n:
            # the frontier emptied with counters undrained: a cycle or a
            # dropped decrement.  Not an infinite hang — the sweep reached
            # a fixpoint — so diagnose it: the undrained counters name
            # exactly the tasks whose signals never arrived.
            und = np.flatnonzero(indeg != 0)
            report = StallReport(
                context="device-discover", elapsed=0.0,
                started=started, finished=started,
                in_flight=0,
                undrained={int(t): int(indeg[t]) for t in und[:1024]},
                note=("counted-sync sweep reached a fixpoint with "
                      f"{und.size} counter(s) undrained — the task graph "
                      "has a cycle or a decrement was dropped"))
            raise StallError(report, msg=(
                f"counted-sync sweep deadlocked: {started}/{n} tasks became "
                f"ready — the task graph has a cycle or a decrement was "
                f"dropped; undrained: {und[:8].tolist()}"
                + (f" (+{und.size - 8} more)" if und.size > 8 else "")))
        level_of = level_of.astype(np.int64)
        levels = levels_from_array(level_of)
        widths = np.asarray([lv.size for lv in levels], dtype=np.int64)
        counters = DeviceCounters(started, started, int(maxw), int(depth),
                                  widths)
        return DeviceRun("discover", levels, level_of, counters)

    def _build_replay(self):
        """The jitted, validating replay sweep over the counters
        ``indeg`` (``i32[n+1]``, slot ``n`` absorbs padded lanes)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        dg, ds = self.dg, self.ds
        n, depth, w_pad, e_pad = dg.n, ds.depth, ds.w_pad, ds.e_pad
        op = jnp.asarray(ds.order)
        tp = jnp.asarray(ds.task_ptr)
        ep = jnp.asarray(ds.edge_ptr)
        tg = jnp.asarray(ds.lvl_tgt)

        @jax.jit
        def sweep(indeg):
            aw = jnp.arange(w_pad, dtype=jnp.int32)
            ae = jnp.arange(e_pad, dtype=jnp.int32)

            def body(level, carry):
                indeg, not_ready, early, maxw = carry
                w = tp[level + 1] - tp[level]
                ids = lax.dynamic_slice(op, (tp[level],), (w_pad,))
                # (a) every task of this level must have a drained
                # counter when it starts
                not_ready += jnp.sum(
                    jnp.where(aw < w, indeg[ids] != 0, False))
                # (b) no task of the NEXT level may be ready before this
                # level's decrements run — it would have been in an
                # earlier frontier.  Checked level by level, this pins
                # every task's drain to exactly the level before its own.
                nw = tp[level + 2] - tp[level + 1]
                nids = lax.dynamic_slice(op, (tp[level + 1],), (w_pad,))
                early += jnp.sum(
                    jnp.where(aw < nw, indeg[nids] == 0, False))
                # decrement this wavefront's out-edges (contiguous slice)
                ec = ep[level + 1] - ep[level]
                tgts = lax.dynamic_slice(tg, (ep[level],), (e_pad,))
                tgts = jnp.where(ae < ec, tgts, n)
                indeg = indeg.at[tgts].add(-1)
                return indeg, not_ready, early, jnp.maximum(maxw, w)

            z = jnp.int32(0)
            indeg, not_ready, early, maxw = lax.fori_loop(
                0, depth, body, (indeg, z, z, z))
            # (c) every counter fully consumed: each edge signaled once
            undrained = jnp.sum(indeg[:n] != 0)
            return not_ready, early, undrained, maxw

        return sweep

    def _run_replay(self) -> DeviceRun:
        import jax.numpy as jnp

        dg, ds = self.dg, self.ds
        n, depth = dg.n, ds.depth
        if self._replay_fn is None:
            self._replay_fn = self._build_replay()
        # slot n swallows sentinel/padded decrements and gathers
        indeg0 = jnp.concatenate([jnp.asarray(dg.pred_n),
                                  jnp.zeros(1, jnp.int32)])
        not_ready, early, undrained, maxw = (
            int(x) for x in self._replay_fn(indeg0))
        if not_ready or early or undrained:
            # the device counted the violations; re-derive the offenders
            # host-side so the error carries evidence, not just totals
            kind, level, ids, indeg = _diagnose_replay(dg, ds)
            counters = _counter_summary(indeg)
            counters.update(device_not_ready=not_ready, device_early=early,
                            device_undrained=undrained)
            raise ScheduleValidationError(kind, level, ids, counters)
        widths = np.asarray([lv.size for lv in ds.levels], dtype=np.int64)
        counters = DeviceCounters(n, n, int(maxw), depth, widths)
        return DeviceRun("replay", ds.levels, ds.level_of, counters)
