"""Scanning-backend equivalence (regression gate).

The compiled backend (integer codegen) and the numpy backend (vectorized
batch codegen, ``iterate_array``/``count_vectorized``) must be *observably
identical* to the retained Fraction reference path: same iterated point
sets and orders, same counts, same enumerator-vs-loop strategy split, same
task/edge/root sets, same pred counts, same wavefront schedules, and same
Sim counter summaries and execution orders.  Any divergence here means the
integer normalization of a bound row — or its array translation — is wrong.

Also covered: the compiled-scan cache (identical canonical polyhedra across
graphs must share one generated function object).
"""
import numpy as np
import pytest

from repro.core.edt import TiledTaskGraph, run_model, synthesize, validate_order
from repro.core.poly import LoopNest, Tiling, clear_scan_cache, scan_cache_info
from repro.core.programs import PROGRAMS

# Small-but-nontrivial shapes: odd params so tiles are ragged at the borders.
CASES = {
    "stencil1d": ((2, 3), {"T": 5, "N": 9}),
    "seidel1d": ((2, 2), {"T": 4, "N": 7}),
    "seidel2d": ((2, 2, 3), {"T": 3, "N": 4}),
    "jacobi2d": ((2, 2, 2), {"T": 3, "N": 5}),
    "heat3d": ((2, 2, 2, 2), {"T": 3, "N": 4}),
    "matmul": ((2, 2, 2), {"N": 5}),
    "trisolv": ((3, 2), {"N": 9}),
    "cholesky_like": ((2, 2, 2), {"N": 5}),
    "lu_like": ((2, 2, 2), {"N": 5}),
    "fanout2": ((2, 3), {"L": 4, "W": 7}),
    "fanout8": ((2, 3), {"L": 3, "W": 9}),
    "diamond": ((2, 2), {"K": 7}),
    "pipeline": ((2, 1), {"M": 5, "S": 3}),
    "embarrassing": ((4,), {"N": 13}),
    "synthetic5d": ((2,) * 5, {"N": 4}),
    "synthetic6d": ((2,) * 6, {"N": 4}),
}

assert set(CASES) == set(PROGRAMS), "every program must be covered"


def _graphs(name, backends=("compiled", "fraction")):
    tiles, params = CASES[name]
    tilings = {"S": Tiling(tiles)}
    gs = [TiledTaskGraph(PROGRAMS[name](), tilings, backend=b)
          for b in backends]
    return (*gs, params)


@pytest.mark.parametrize("name", sorted(CASES))
def test_backend_equivalence(name):
    gc, gf, params = _graphs(name)

    # tile-domain scanning: same points in the same (lexicographic) order
    for st in gc.program.statements:
        pc = list(gc.tile_nests[st].iterate(params))
        pf = list(gf.tile_nests[st].iterate(params))
        assert pc == pf
        assert gc.tile_nests[st].count(params) == len(pc)
        assert gf.tile_nests[st].count(params) == len(pf)

    # §4.3 strategy split (enumerator vs counting loop) must match
    assert gc.pred_count_strategies() == gf.pred_count_strategies()

    # materialized graph: identical task lists, edge lists, pred counts
    mc, mf = gc.materialize(params), gf.materialize(params)
    assert mc.tasks == mf.tasks
    assert mc.succ == mf.succ
    assert mc.pred_n == mf.pred_n

    # generated loops: per-task get/put loops and counter agree
    for t in mc.tasks:
        assert gc.pred_count(t, params) == gf.pred_count(t, params)
        assert list(gc.predecessors(t, params)) == list(gf.predecessors(t, params))

    # root sets (including the self-pair special case) agree
    assert list(gc.roots(params)) == list(gf.roots(params))


@pytest.mark.parametrize("name", sorted(CASES))
def test_numpy_backend_equivalence(name):
    """The vectorized backend's batch products equal the scalar graph,
    byte for byte: point arrays, counts, graphs, roots, counters, levels."""
    gc, gn, params = _graphs(name, backends=("compiled", "numpy"))

    # array enumeration: same points, same lexicographic order, same counts
    for st in gc.program.statements:
        pts = list(gc.tile_nests[st].iterate(params))
        arr = gn.tile_nests[st].iterate_array(params)
        assert arr.dtype == np.int64 and arr.shape == (len(pts), len(pts[0]))
        assert [tuple(r) for r in arr.tolist()] == pts
        assert gn.tile_nests[st].count_vectorized(params) == len(pts)
        # scalar APIs on the numpy backend share the compiled path
        assert list(gn.tile_nests[st].iterate(params)) == pts

    # materialized graph (dict view) is identical
    mc, mn = gc.materialize(params), gn.materialize(params)
    assert mc.tasks == mn.tasks
    assert mc.succ == mn.succ
    assert mc.pred_n == mn.pred_n

    # index-graph (native array view) carries the same graph
    ig = gn.index_graph(params)
    assert ig.n == len(mc.tasks) and ig.n_edges == mc.n_edges
    assert ig.tasks == mc.tasks
    assert ig.pred_n.tolist() == [mc.pred_n[t] for t in mc.tasks]

    # batched pred counts equal the §4.3 per-task counter
    for st, arr in gn.tasks_arrays(params).items():
        blk = gn.pred_count_block(st, arr, params)
        ref = [gc.pred_count((st, tuple(r)), params) for r in arr.tolist()]
        assert blk.tolist() == ref

    # root sets and wavefront schedules agree
    assert list(gc.roots(params)) == list(gn.roots(params))
    wc, wn = synthesize(gc, params), synthesize(gn, params)
    assert wc.levels == wn.levels
    assert wc.level_of == wn.level_of


@pytest.mark.parametrize("name", ["jacobi2d", "trisolv", "diamond"])
@pytest.mark.parametrize("backend", ["fraction", "numpy"])
def test_backend_identical_execution(name, backend):
    """Table-2 counters and exec order are bit-identical across backends."""
    gc, go, params = _graphs(name, backends=("compiled", backend))
    for model in ("prescribed", "counted", "autodec"):
        rc = run_model(model, gc, params, workers=3)
        ro = run_model(model, go, params, workers=3)
        assert rc.order == ro.order, model
        assert rc.counters.summary() == ro.counters.summary(), model
        validate_order(gc, params, rc)


def test_counting_function_backend_split():
    """All strategies of §4.3 give equal values under every backend."""
    from repro.core.poly import Polyhedron, make_counting_function

    tri = Polyhedron.from_ineqs(("i", "j"), ("N",), [
        (1, 0, 0, 0), (-1, 1, 0, 0), (0, -1, 1, -1)])
    for count_dims, fixed_dims, coords_list in [
            ([0], [1], [((j,),) for j in range(6)]),
            ([0, 1], [], [((),)]),
    ]:
        fc = make_counting_function(tri, count_dims, fixed_dims)
        ff = make_counting_function(tri, count_dims, fixed_dims,
                                    backend="fraction")
        fn = make_counting_function(tri, count_dims, fixed_dims,
                                    backend="numpy")
        assert fc.strategy == ff.strategy == fn.strategy
        for (coords,) in coords_list:
            assert fc(coords, (6,)) == ff(coords, (6,)) == fn(coords, (6,))
            assert list(fc.points(coords, (6,))) == list(ff.points(coords, (6,)))
        if coords_list[0][0]:
            block = np.asarray([c for (c,) in coords_list], dtype=np.int64)
            ref = [fc(tuple(r), (6,)) for r in block.tolist()]
            assert fn.count_block(block, (6,)).tolist() == ref
            # empty blocks are fine, including non-2-D inputs
            assert fn.count_block(np.zeros((0, 1), np.int64), (6,)).shape == (0,)
            assert fn.count_block([], (6,)).shape == (0,)


def test_scan_cache_shares_compiled_nests():
    """Two graphs over the same program share one compiled scan function
    per canonical polyhedron (ROADMAP cache item)."""
    clear_scan_cache()
    tiles, params = CASES["jacobi2d"]
    tilings = {"S": Tiling(tiles)}
    g1 = TiledTaskGraph(PROGRAMS["jacobi2d"](), tilings)
    g2 = TiledTaskGraph(PROGRAMS["jacobi2d"](), tilings)
    m1 = g1.materialize(params)
    g1.pred_count(m1.tasks[0], params)  # force the counter codegen too
    before = scan_cache_info()
    m2 = g2.materialize(params)
    g2.pred_count(m2.tasks[0], params)
    after = scan_cache_info()
    # the second graph compiled nothing new: only cache hits were added
    assert after["size"] == before["size"]
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]
    # the generated function objects are literally shared
    for st in g1.program.statements:
        assert g1.tile_nests[st]._scan_fn is g2.tile_nests[st]._scan_fn
    for t1, t2 in zip(g1.tiled_deps, g2.tiled_deps):
        assert t1.succ_fn.nest._scan_fn is t2.succ_fn.nest._scan_fn
        for fn in (t1.pred_fn, t2.pred_fn):
            fn.nest.count([0] * fn.nest.nparam)  # force counter codegen
        assert t1.pred_fn.nest._count_fn is not None
        assert t1.pred_fn.nest._count_fn is t2.pred_fn.nest._count_fn
    # the numpy flavor shares through the same key
    n1 = TiledTaskGraph(PROGRAMS["jacobi2d"](), tilings, backend="numpy")
    n2 = TiledTaskGraph(PROGRAMS["jacobi2d"](), tilings, backend="numpy")
    n1.materialize(params)
    n2.materialize(params)
    for t1, t2 in zip(n1.tiled_deps, n2.tiled_deps):
        assert t1.joint_nest._scan_np_fn is t2.joint_nest._scan_np_fn


def test_unbounded_dim_raises_in_both_backends():
    from repro.core.poly import Polyhedron

    half = Polyhedron.from_ineqs(("x",), (), [(1, 0)])  # x >= 0, unbounded
    for backend in ("compiled", "fraction"):
        nest = LoopNest(half, backend=backend)
        with pytest.raises(ValueError):
            list(nest.iterate(()))
        with pytest.raises(ValueError):
            nest.count(())
    nest = LoopNest(half, backend="numpy")
    with pytest.raises(ValueError):
        nest.iterate_array(())
    with pytest.raises(ValueError):
        nest.count_vectorized(())


def test_unbounded_inner_dim_with_empty_outer_range():
    """An empty outer loop must hide an unbounded inner dim identically.

    {0 <= i <= N, j >= i}: dim j is unbounded, but for N < 0 the i-range is
    empty, so iterate() yields nothing (and never reaches the raise) in all
    backends; for N >= 0 all raise on first consumption."""
    from repro.core.poly import Polyhedron

    P = Polyhedron.from_ineqs(("i", "j"), ("N",), [
        (1, 0, 0, 0), (-1, 0, 1, 0), (-1, 1, 0, 0)])
    for backend in ("compiled", "fraction"):
        nest = LoopNest(P, backend=backend)
        assert list(nest.iterate((-1,))) == [], backend
        with pytest.raises(ValueError):
            list(nest.iterate((2,)))
    nest = LoopNest(P, backend="numpy")
    assert nest.iterate_array((-1,)).shape == (0, 2)
    assert nest.count_vectorized((-1,)) == 0
    with pytest.raises(ValueError):
        nest.iterate_array((2,))
