"""Task-generation throughput across scanning backends and shard counts.

The paper's premise (§4, §5.1) is that task-graph *generation* — the
get/put/count loops the compiler emits — must cost like generated C loop
bounds, not like a polyhedral library call.  This benchmark measures exactly
that layer for every backend:

* ``fraction`` — the retained rational reference path,
* ``compiled`` — PR 1's generated integer loop nests (scalar points),
* ``numpy``    — PR 2's vectorized batch enumeration (whole wavefronts as
  index arrays),
* ``numpy`` with ``shards=n`` — the sharded materialization engine
  (:mod:`repro.core.edt.shard`): scans fan out across a process pool and
  stream into shared-memory index arrays.

Per backend we time producing the graph in its **native representation**:
``materialize()`` (dict-of-tuples adjacency) for the scalar backends and for
the numpy compatibility view, plus ``index_graph()`` (flat index arrays —
what the batched wavefront/executor layers consume) for numpy and the
sharded rows.  The §4.3 counter sweep and root scan are timed per backend
as well (per-task calls vs array blocks vs merged-array bincount).

Graph equality is asserted, not assumed: task lists, edge lists, pred
counts, root sets, and the index-graph's labels/degrees must be identical
across all backends *and all shard counts* or the run fails.

``run(scale=True)`` (the default outside smoke mode) additionally
materializes ≥1M-task graphs end-to-end and reports the speedup curve
across shard counts, with byte-identical results verified against the
single-process arrays.

Output: one CSV row per (program, backend, shards) with a stable
machine-readable schema — ``rows`` / ``shard_scale`` (lists of dicts) and
geomean summaries are also returned for the JSON artifact emitted by
``benchmarks/run.py``.
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.core.edt import ExecutionConfig, TiledTaskGraph
from repro.core.edt.shard import process_pool
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

# (program, tile sizes, params) — sized so the Fraction path takes ~0.1-5 s.
SUITE = [
    ("stencil1d", (4, 4), {"T": 64, "N": 256}),
    ("seidel1d", (4, 4), {"T": 48, "N": 192}),
    ("jacobi2d", (2, 2, 2), {"T": 12, "N": 24}),
    ("heat3d", (2, 2, 2, 2), {"T": 6, "N": 10}),
    ("matmul", (2, 2, 2), {"N": 24}),
    ("trisolv", (2, 2), {"N": 96}),
    ("lu_like", (2, 2, 2), {"N": 20}),
    ("diamond", (1, 1), {"K": 48}),
    ("pipeline", (1, 1), {"M": 64, "S": 24}),
]

SMOKE_SUITE = [
    ("jacobi2d", (2, 2, 2), {"T": 6, "N": 10}),
    ("trisolv", (2, 2), {"N": 32}),
]

BACKENDS = ("fraction", "compiled", "numpy")
SHARD_COUNTS = (2, 4)

# ≥1M-task graphs for the end-to-end scale curve.  jacobi2d's ragged
# 6-dim joint scans are compute-bound (sharding wins); diamond's dense box
# is bandwidth-bound (an honest overhead floor on few-core hosts).
SCALE_SUITE = [
    ("jacobi2d", (2, 2, 2), {"T": 32, "N": 512}),
    ("diamond", (1, 1), {"K": 1024}),
]
SMOKE_SCALE_SUITE = [
    ("jacobi2d", (2, 2, 2), {"T": 8, "N": 64}),
]
SCALE_SHARDS = (1, 2, 4)

CSV_FIELDS = ("program", "backend", "shards", "n_tasks", "n_edges",
              "materialize_ms", "enum_ms", "predcount_ms", "roots_ms",
              "tasks_per_s", "edges_per_s")


def _time(fn, reps: int = 1):
    """Best-of-``reps`` wall time and the last result.

    Every backend is timed with the same rep count so warm-up or scheduler
    noise cannot bias the reported speedups either way."""
    best = float("inf")
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        best = min(best, dt)
    return best, out


def _check_identical(ma, mb) -> None:
    assert ma.tasks == mb.tasks, "task sets differ between backends"
    assert ma.succ == mb.succ, "edge lists differ between backends"
    assert ma.pred_n == mb.pred_n, "pred counts differ between backends"


def _check_ig_identical(a, b) -> None:
    """Byte-identical flat graphs: blocks, edge columns, in-degrees."""
    assert a.n == b.n, "task counts differ"
    assert np.array_equal(a.edge_src, b.edge_src), "edge sources differ"
    assert np.array_equal(a.edge_tgt, b.edge_tgt), "edge targets differ"
    assert np.array_equal(a.pred_n, b.pred_n), "in-degrees differ"
    for (na, xa), (nb, xb) in zip(a.stmt_blocks, b.stmt_blocks):
        assert na == nb and np.array_equal(xa, xb), "stmt blocks differ"


def _geomean(xs):
    g = 1.0
    for x in xs:
        g *= x
    return g ** (1.0 / len(xs)) if xs else 0.0


def _row(name, backend, shards, n, e, t_mat, t_enum, t_pc, t_roots):
    return {
        "program": name,
        "backend": backend,
        "shards": shards,
        "n_tasks": n,
        "n_edges": e,
        "materialize_ms": round(t_mat * 1e3, 3),
        "enum_ms": round(t_enum * 1e3, 3),
        "predcount_ms": round(t_pc * 1e3, 3),
        "roots_ms": round(t_roots * 1e3, 3),
        "tasks_per_s": round(n / max(t_enum, 1e-9)),
        "edges_per_s": round(e / max(t_enum, 1e-9)),
    }


def _bench_one(name, tiles, params, reps, pool):
    """Rows for one program (one per backend + shard count), verified."""
    tilings = {"S": Tiling(tiles)}
    graphs = {b: TiledTaskGraph(PROGRAMS[name](), tilings, backend=b)
              for b in BACKENDS}
    rows = []
    mats = {}
    counts = {}
    roots = {}
    igs = {}
    for b, g in graphs.items():
        t_mat, m = _time(lambda: g.materialize(params), reps)
        mats[b] = m
        tasks = m.tasks
        if b == "numpy":
            # native product: the flat index-array graph
            t_enum, ig = _time(lambda: g.index_graph(params), reps)
            igs[1] = ig
            assert ig.n == len(tasks) and ig.n_edges == m.n_edges
            assert ig.tasks == tasks, "index-graph labels differ"
            assert ig.pred_n.tolist() == [m.pred_n[t] for t in tasks], "index-graph degrees differ"
            stmts = list(g.program.statements)
            arrs = g.tasks_arrays(params)
            t_pc, pc = _time(
                lambda: [c for s in stmts
                         for c in g.pred_count_block(s, arrs[s], params)],
                reps)
            counts[b] = [int(c) for c in pc]
        else:
            t_enum = t_mat
            t_pc, pc = _time(
                lambda: [g.pred_count(t, params) for t in tasks], reps)
            counts[b] = pc
        t_roots, rt = _time(lambda: list(g.roots(params)), reps)
        roots[b] = rt
        rows.append(_row(name, b, 1, len(tasks), m.n_edges,
                         t_mat, t_enum, t_pc, t_roots))
    for b in ("compiled", "numpy"):
        _check_identical(mats["fraction"], mats[b])
        assert counts["fraction"] == counts[b], f"pred counts differ (fraction vs {b})"
        assert roots["fraction"] == roots[b], f"root sets differ (fraction vs {b})"
    # sharded rows: the same graph through the process-pool engine,
    # byte-identical to the single-process arrays (asserted).
    g = graphs["numpy"]
    n, e = len(mats["numpy"].tasks), mats["numpy"].n_edges
    for s in SHARD_COUNTS:
        cfg = ExecutionConfig(shards=s, pool=pool)
        t_mat, m_s = _time(
            lambda: g.materialize(params, config=cfg), reps)
        _check_identical(mats["fraction"], m_s)
        t_enum, ig_s = _time(
            lambda: g.index_graph(params, config=cfg), reps)
        _check_ig_identical(igs[1], ig_s)
        # §4.3 counters / roots from the merged arrays
        t_pc, pn = _time(
            lambda: np.bincount(ig_s.edge_tgt, minlength=ig_s.n), reps)
        assert np.array_equal(pn, igs[1].pred_n)
        t_roots, rt = _time(
            lambda: list(g.roots(params, config=cfg)), reps)
        assert rt == roots["fraction"], f"sharded roots differ (shards={s})"
        rows.append(_row(name, "numpy", s, n, e, t_mat, t_enum, t_pc,
                         t_roots))
    return rows


def shard_scale(emit=print, smoke: bool = False, pool=None, reps: int = 2):
    """≥1M-task end-to-end materialization: the shard-count speedup curve.

    Each graph is generated as flat index arrays (``index_graph``) at every
    shard count and verified byte-identical to the single-process result.
    """
    suite = SMOKE_SCALE_SUITE if smoke else SCALE_SUITE
    rows = []
    own = pool is None
    if own:
        pool = process_pool(os.cpu_count() or 1)
        pool.submit(int, 0).result()
    try:
        for name, tiles, params in suite:
            g = TiledTaskGraph(PROGRAMS[name](), {"S": Tiling(tiles)},
                               backend="numpy")
            base = None
            base_ms = None
            for s in SCALE_SHARDS:
                if s == 1:
                    t, ig = _time(lambda: g.index_graph(params), reps)
                else:
                    cfg = ExecutionConfig(shards=s, pool=pool)
                    g.index_graph(params, config=cfg)  # warm pool
                    t, ig = _time(
                        lambda: g.index_graph(params, config=cfg), reps)
                if base is None:
                    base, base_ms = ig, t * 1e3
                else:
                    _check_ig_identical(base, ig)
                rows.append({
                    "program": name, "shards": s,
                    "n_tasks": ig.n, "n_edges": ig.n_edges,
                    "index_graph_ms": round(t * 1e3, 1),
                    "speedup_vs_1": round(base_ms / (t * 1e3), 2),
                })
                emit(f"# scale {name}: shards={s} tasks={ig.n} "
                     f"edges={ig.n_edges} index_graph={t * 1e3:.0f}ms "
                     f"speedup={rows[-1]['speedup_vs_1']:.2f}x "
                     f"(byte-identical verified)", flush=True)
    finally:
        if own:
            pool.shutdown()
    return rows


def run(emit=print, smoke: bool = False, scale: bool = None):
    suite = SMOKE_SUITE if smoke else SUITE
    reps = 1 if smoke else 3
    if scale is None:
        scale = True
    emit(",".join(CSV_FIELDS))
    rows = []
    pool = process_pool(max(1, min(max(SHARD_COUNTS), os.cpu_count() or 1)))
    pool.submit(int, 0).result()   # absorb spawn cost before timing
    try:
        for name, tiles, params in suite:
            prog_rows = _bench_one(name, tiles, params, reps, pool)
            rows.extend(prog_rows)
            for r in prog_rows:
                emit(",".join(str(r[f]) for f in CSV_FIELDS), flush=True)
        scale_rows = shard_scale(emit, smoke=smoke, pool=pool) if scale else []
    finally:
        pool.shutdown()
    by = {(r["program"], r["backend"], r["shards"]): r for r in rows}
    progs = [s[0] for s in suite]
    enum_sp = [by[p, "compiled", 1]["materialize_ms"]
               / max(by[p, "numpy", 1]["enum_ms"], 1e-6) for p in progs]
    mat_sp = [by[p, "compiled", 1]["materialize_ms"]
              / max(by[p, "numpy", 1]["materialize_ms"], 1e-6) for p in progs]
    frac_sp = [by[p, "fraction", 1]["materialize_ms"]
               / max(by[p, "compiled", 1]["materialize_ms"], 1e-6)
               for p in progs]
    pc_sp = [by[p, "compiled", 1]["predcount_ms"]
             / max(by[p, "numpy", 1]["predcount_ms"], 1e-6) for p in progs]
    roots_sp = [by[p, "compiled", 1]["roots_ms"]
                / max(by[p, "numpy", 1]["roots_ms"], 1e-6) for p in progs]
    geo = {
        "numpy_enum_over_compiled": round(_geomean(enum_sp), 2),
        "numpy_materialize_over_compiled": round(_geomean(mat_sp), 2),
        "compiled_over_fraction": round(_geomean(frac_sp), 2),
        "numpy_predcount_over_compiled": round(_geomean(pc_sp), 2),
        "numpy_roots_over_compiled": round(_geomean(roots_sp), 2),
    }
    for s in SHARD_COUNTS:
        sp = [by[p, "numpy", 1]["enum_ms"]
              / max(by[p, "numpy", s]["enum_ms"], 1e-6) for p in progs]
        geo[f"shard{s}_enum_over_numpy"] = round(_geomean(sp), 2)
    emit(f"# geomean enumeration speedup (numpy index arrays over compiled "
         f"materialize): {geo['numpy_enum_over_compiled']:.1f}x over "
         f"{len(progs)} programs (graphs verified identical)")
    emit(f"# geomean dict-view materialize speedup (numpy over compiled): "
         f"{geo['numpy_materialize_over_compiled']:.1f}x; compiled over "
         f"fraction: {geo['compiled_over_fraction']:.1f}x")
    emit(f"# geomean pred_count block speedup: "
         f"{geo['numpy_predcount_over_compiled']:.1f}x; roots: "
         f"{geo['numpy_roots_over_compiled']:.1f}x")
    emit(f"# sharded enumeration vs single-process numpy (small suite — "
         f"pool overhead dominates; see the scale rows for the real curve): "
         + ", ".join(f"{s} shards {geo[f'shard{s}_enum_over_numpy']:.2f}x"
                     for s in SHARD_COUNTS))
    return {"schema_version": 2, "rows": rows, "geomean": geo,
            "shard_scale": scale_rows,
            "host_cpus": os.cpu_count()}


if __name__ == "__main__":
    run()
