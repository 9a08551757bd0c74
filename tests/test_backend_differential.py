"""Cross-backend differential harness: every generation path, one graph.

Randomly generated small polyhedral programs (seeded — deterministic in CI)
are materialized through every path the repo has:

* the ``fraction`` reference backend (exact rational oracle),
* the ``compiled`` integer-codegen backend,
* the ``numpy`` vectorized batch backend (dict view and ``index_graph``),
* the sharded process-pool engine (``shards=n``, shm and pickle
  transports),

and every product — task list, adjacency, §4.3 predecessor counts, root
set, flat edge columns — must be identical.  The same property is exposed
through hypothesis when it is installed (via the ``hypo_stub`` shim it
skips cleanly otherwise); the seeded loop below keeps the differential
coverage running either way.
"""
from __future__ import annotations

import random

import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
except ImportError:  # property tests skip; deterministic tests still run
    from hypo_stub import HealthCheck, given, settings, st

from repro.core.edt import ExecutionConfig, PolyhedralProgram, TiledTaskGraph
from repro.core.edt.shard import plan_shards, process_pool, scan_sharded
from repro.core.poly import Polyhedron, Tiling
from repro.core.programs import PROGRAMS, dep

BACKENDS = ("fraction", "compiled", "numpy")


@pytest.fixture(scope="module")
def pool():
    p = process_pool(2)
    p.submit(int, 0).result()   # absorb spawn cost
    yield p
    p.shutdown()


# ------------------------------------------------------------- generator
def _random_domain(rng: random.Random, nd: int):
    """Box 0 <= x_i < E_i (E_0 may be the parameter N), optionally made
    triangular with x_1 <= x_0 — the §4.3 counting-loop shape."""
    param_extent = rng.random() < 0.5
    extents = [rng.randint(2, 5) for _ in range(nd)]
    rows = []
    for i in range(nd):
        lo = [0] * (nd + 2)
        lo[i] = 1
        hi = [0] * (nd + 2)
        hi[i] = -1
        if i == 0 and param_extent:
            hi[nd] = 1      # x_0 <= N - 1
            hi[-1] = -1
        else:
            hi[-1] = extents[i] - 1
        rows += [lo, hi]
    triangular = nd >= 2 and rng.random() < 0.4
    if triangular:
        r = [0] * (nd + 2)
        r[0], r[1] = 1, -1          # x_1 <= x_0
        rows.append(r)
    return Polyhedron.from_ineqs(
        tuple(f"x{i}" for i in range(nd)), ("N",), rows)


def _random_dep_rows(rng: random.Random, nd: int):
    """(eqs, ineqs) over [src, tgt, N, 1] — lex-positive, so the graph is
    acyclic and the root set is nontrivial."""
    if rng.random() < 0.7:
        # uniform shift with lex-positive distance
        while True:
            off = [rng.choice([-1, 0, 0, 1, 1, 2]) for _ in range(nd)]
            nz = [o for o in off if o]
            if nz and next(o for o in off if o) > 0:
                break
        eqs = []
        for i in range(nd):
            e = [0] * (2 * nd + 2)
            e[i], e[nd + i], e[-1] = 1, -1, off[i]   # x_t_i = x_s_i + off_i
            eqs.append(e)
        return eqs, []
    # non-uniform: advance dim 0, fan out over dim 1 (x_t_1 >= x_s_1)
    eqs = []
    e = [0] * (2 * nd + 2)
    e[0], e[nd], e[-1] = 1, -1, 1
    eqs.append(e)
    for i in range(2, nd):
        e = [0] * (2 * nd + 2)
        e[i], e[nd + i] = 1, -1
        eqs.append(e)
    ineqs = []
    if nd >= 2:
        r = [0] * (2 * nd + 2)
        r[1], r[nd + 1] = -1, 1                      # x_t_1 >= x_s_1
        ineqs.append(r)
        r = [0] * (2 * nd + 2)
        r[1], r[nd + 1], r[-1] = 1, -1, 2            # x_t_1 <= x_s_1 + 2
        ineqs.append(r)
    return eqs, ineqs


def _build_program(rng: random.Random):
    nd = rng.choice([1, 2, 2, 3])
    P = PolyhedralProgram()
    D = _random_domain(rng, nd)
    P.add_statement("S", D)
    for j in range(rng.randint(1, 2)):
        eqs, ineqs = _random_dep_rows(rng, nd)
        P.add_dependence("S", "S", dep(D, D, eqs=eqs, ineqs=ineqs),
                         f"d{j}")
    tiling = Tiling(tuple(rng.randint(1, 3) for _ in range(nd)))
    params = {"N": rng.randint(4, 9)}
    return P, {"S": tiling}, params


# ------------------------------------------------------------ comparator
def assert_paths_identical(prog, tilings, params, pool=None,
                           shard_counts=(3,), use_shm=True):
    """The differential property: every generation path, identical graph."""
    graphs = {b: TiledTaskGraph(prog, tilings, backend=b) for b in BACKENDS}
    ref = graphs["fraction"].materialize(params)
    ref_roots = list(graphs["fraction"].roots(params))
    ref_counts = [graphs["fraction"].pred_count(t, params) for t in ref.tasks]
    for b in ("compiled", "numpy"):
        m = graphs[b].materialize(params)
        assert m.tasks == ref.tasks, b
        assert m.succ == ref.succ, b
        assert m.pred_n == ref.pred_n, b
        assert list(graphs[b].roots(params)) == ref_roots, b
        assert [graphs[b].pred_count(t, params) for t in ref.tasks] == ref_counts, b
    g = graphs["numpy"]
    ig = g.index_graph(params)
    assert ig.tasks == ref.tasks
    assert ig.pred_n.tolist() == [ref.pred_n[t] for t in ref.tasks]
    edges = sorted((ig.tasks[s], ig.tasks[t])
                   for s, t in zip(ig.edge_src.tolist(),
                                   ig.edge_tgt.tolist()))
    assert edges == sorted((u, v) for u, ss in ref.succ.items() for v in ss)
    for s in shard_counts:
        cfg = ExecutionConfig(shards=s, pool=pool)
        for gb in (g, graphs["compiled"]):
            m = gb.materialize(params, config=cfg)
            assert m.tasks == ref.tasks, f"sharded tasks differ (x{s})"
            assert m.succ == ref.succ, f"sharded adjacency differs (x{s})"
            assert m.pred_n == ref.pred_n, f"sharded counts differ (x{s})"
        igs = g.index_graph(params, config=cfg)
        assert np.array_equal(igs.edge_src, ig.edge_src)
        assert np.array_equal(igs.edge_tgt, ig.edge_tgt)
        assert np.array_equal(igs.pred_n, ig.pred_n)
        for (na, xa), (nb, xb) in zip(igs.stmt_blocks, ig.stmt_blocks):
            assert na == nb and np.array_equal(xa, xb)
        assert list(g.roots(params, config=cfg)) == ref_roots
        if not use_shm:
            scans = scan_sharded(g, params, s, pool=pool, use_shm=False)
            m = g._materialize_numpy(g._pv(params), scans=scans)
            assert m.succ == ref.succ and m.pred_n == ref.pred_n


# ------------------------------------------------------- deterministic
def test_differential_random_programs(pool):
    """Seeded sweep: 12 random programs through every path."""
    rng = random.Random(20260731)
    for case in range(12):
        prog, tilings, params = _build_program(rng)
        assert_paths_identical(prog, tilings, params, pool=pool)


def test_differential_pickle_transport(pool):
    """The no-shared-memory fallback produces the same graphs."""
    rng = random.Random(7)
    for case in range(3):
        prog, tilings, params = _build_program(rng)
        assert_paths_identical(prog, tilings, params, pool=pool,
                               shard_counts=(2,), use_shm=False)


def test_differential_named_programs(pool):
    """The paper-suite shapes (triangular, multi-dep, stencil) as anchors."""
    cases = [
        ("trisolv", (2, 2), {"N": 21}),
        ("seidel1d", (3, 3), {"T": 9, "N": 21}),
        ("diamond", (1, 1), {"K": 9}),
    ]
    for name, tiles, params in cases:
        assert_paths_identical(PROGRAMS[name](), {"S": Tiling(tiles)},
                               params, pool=pool, shard_counts=(2, 5))


def test_plan_is_deterministic_and_partitions():
    """Shard plans depend only on (graph, params, shards): stable block
    boundaries that exactly partition each unit's outer extent."""
    g = TiledTaskGraph(PROGRAMS["trisolv"](), {"S": Tiling((2, 2))})
    params = {"N": 33}
    p1 = plan_shards(g, params, 4)
    p2 = plan_shards(g, params, 4)
    assert p1.tile_specs == p2.tile_specs
    assert p1.edge_specs == p2.edge_specs
    by_unit = {}
    for s in p1.tile_specs + p1.edge_specs:
        by_unit.setdefault((s.kind, s.key), []).append(s)
    for specs in by_unit.values():
        specs.sort(key=lambda s: s.seq)
        for a, b in zip(specs, specs[1:]):
            assert b.lo == a.hi + 1, "blocks must tile the outer range"
        assert all(s.lo <= s.hi for s in specs)


def test_sharded_restricted_scan_is_slice():
    """A __slo/__shi-restricted scan equals the matching slice of the full
    scan — the invariant the whole merge rests on."""
    from repro.core.poly import LoopNest, shard_polyhedron
    g = TiledTaskGraph(PROGRAMS["lu_like"](), {"S": Tiling((2, 2, 2))})
    params = {"N": 11}
    pv = g._pv(params)
    for nest in list(g.tile_nests.values()) + [g._joint_nest(td) for td in g.tiled_deps]:
        full = nest.iterate_array(pv)
        lb, ub = nest.outer_bounds(pv)
        if full.shape[0]:
            assert lb == int(full[:, 0].min())
            assert ub == int(full[:, 0].max())
        snest = LoopNest(shard_polyhedron(nest.poly))
        mid = (lb + ub) // 2
        for lo, hi in ((lb, mid), (mid + 1, ub), (lb, ub), (ub + 1, ub + 3)):
            block = snest.iterate_array(pv + [lo, hi])
            mask = (full[:, 0] >= lo) & (full[:, 0] <= hi)
            assert np.array_equal(block, full[mask])


def test_sharded_counts_match_scans():
    """The counting round's exact pre-counts equal what the scans produce —
    asserted in-process here (workers re-assert it on every deposit)."""
    from repro.core.edt.shard import (_block_scan, _count_shard, _CountJob,
                                      _diag_shard_poly)
    g = TiledTaskGraph(PROGRAMS["seidel1d"](), {"S": Tiling((3, 3))})
    params = {"T": 12, "N": 30}
    plan = plan_shards(g, params, 3)
    for spec in plan.tile_specs:
        n = _count_shard(_CountJob(spec, None))
        assert _block_scan(spec).shape[0] == n
    for spec in plan.edge_specs:
        td = g.tiled_deps[spec.key]
        diag = (_diag_shard_poly(g, spec.key)
                if td.dep.src == td.dep.tgt else None)
        n = _count_shard(_CountJob(spec, diag))
        arr = _block_scan(spec)
        if td.dep.src == td.dep.tgt and arr.shape[0]:
            ns = g.tilings[td.dep.src].ndim
            arr = arr[(arr[:, :ns] != arr[:, ns:]).any(axis=1)]
        assert arr.shape[0] == n


# --------------------------------------------------------- hypothesis
@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(st.integers(min_value=0, max_value=10_000))
def test_differential_property(seed):
    """Hypothesis twin of the seeded sweep (skips without hypothesis)."""
    rng = random.Random(seed)
    prog, tilings, params = _build_program(rng)
    assert_paths_identical(prog, tilings, params, pool=None,
                           shard_counts=(2,))
