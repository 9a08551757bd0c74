"""Table-2 overhead asymptotics, asserted on growing graph sizes.

The paper's §2 comparison is qualitative ("scales with the number of
tasks/edges", "O(1) start-up"); these tests pin the measured counters of
each synchronization model to those shapes on the diamond DAG (the paper's
worst case for prescribed synchronization, Fig 1) at increasing sizes:

* ``prescribed`` start-up is exactly tasks + edges (the master declares
  everything); ``counted`` start-up is exactly tasks — both grow linearly.
* ``autodec`` start-up stays O(1) and its master does only the
  statically-computed root set (preschedule).
* ``tags1`` spatial peak tracks the edge count (one-use tags); ``tags2``
  tags are disposable only at completion, so its garbage gauge holds
  every producer's tag at the end while every other model drains to zero.
* ``autodec`` live counters peak at the frontier, not the graph.
"""
from __future__ import annotations

import json

import pytest

from repro.core.edt import (MODELS, PolyhedralProgram, TiledTaskGraph, atlas,
                            validate_order)
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

SIZES = (4, 8, 12)


def _measurements():
    out = []
    for k in SIZES:
        g = TiledTaskGraph(PROGRAMS["diamond"](), {"S": Tiling((1, 1))})
        params = {"K": k}
        m = g.materialize(params)
        runs = {}
        for name, fn in MODELS.items():
            r = fn(g, params, workers=4)
            validate_order(g, params, r)
            runs[name] = r.counters
        out.append((k, len(m.tasks), m.n_edges,
                    len(list(g.roots(params))), runs))
    return out


MEASURED = None


def _runs():
    global MEASURED
    if MEASURED is None:
        MEASURED = _measurements()
    return MEASURED


def test_prescribed_and_counted_startup_grow_with_tasks():
    for k, n, e, _, runs in _runs():
        assert runs["prescribed"].startup_ops == n + e
        assert runs["counted"].startup_ops == n
    startups = [runs["prescribed"].startup_ops for *_, runs in _runs()]
    assert startups == sorted(startups) and startups[0] < startups[-1]


def test_autodec_startup_is_o1_plus_roots():
    for k, n, e, roots, runs in _runs():
        assert runs["autodec"].startup_ops == 1      # O(1): gate never closes
        assert runs["autodec"].master_ops == roots   # preschedule = root set
        assert runs["autodec_nosrc"].startup_ops == 1
        assert runs["autodec_nosrc"].master_ops == n  # w/o src: all tasks
    # the root set, not the graph, sizes the master's work: on the
    # embarrassing program every task is a root and the master does N ops
    g = TiledTaskGraph(PROGRAMS["embarrassing"](), {"S": Tiling((1,))})
    r = MODELS["autodec"](g, {"N": 23}, workers=4)
    assert r.counters.master_ops == 23
    assert r.counters.startup_ops == 1


def test_tags1_spatial_peak_tracks_edges():
    peaks = []
    for k, n, e, _, runs in _runs():
        peak = runs["tags1"].spatial.peak
        # every edge becomes one one-use tag (+1 transient pending get)
        assert e - 1 <= peak <= e + 1
        assert runs["tags1"].spatial.total == 2 * e  # tag + pending get
        peaks.append(peak)
    assert peaks == sorted(peaks) and peaks[0] < peaks[-1]


def test_counted_spatial_is_tasks_autodec_is_frontier():
    for k, n, e, _, runs in _runs():
        assert runs["counted"].spatial.peak == n
        # autodec keeps only live frontier counters — far below n, and its
        # lifetime total still covers every task exactly once
        assert runs["autodec"].spatial.peak < n // 2
        assert runs["autodec"].spatial.total == n


def test_garbage_drains_to_zero_except_tags2():
    for k, n, e, _, runs in _runs():
        for name in ("prescribed", "tags1", "counted", "autodec",
                     "autodec_nosrc"):
            assert runs[name].garbage.cur == 0, name
            assert runs[name].inflight_deps.cur == 0, name
            assert runs[name].inflight_tasks.cur == 0, name
        # tags2 tags are only disposable at graph completion: every task
        # that produced a tag still holds it as garbage at the end
        assert runs["tags2"].garbage.cur == n - 1


def test_every_model_covered_and_validated():
    """``validate_order`` ran for every model at every size inside
    ``_measurements`` (exactly-once + dependence-respecting order); this
    pins that the registry was fully covered."""
    for *_, runs in _runs():
        assert set(runs) == set(MODELS)


def test_tags_models_survive_multigraph_edges():
    """Two dependences relating the same task pair (a multigraph) must not
    break any model — regression for the tags1 tag table, which assumed
    one tag per (src, dst) key and crashed deleting the key twice."""
    from repro.core.poly import Polyhedron
    from repro.core.programs import dep

    P = PolyhedralProgram()
    D = Polyhedron.from_ineqs(("i",), ("N",), [(1, 0, 0), (-1, 1, -1)])
    P.add_statement("S", D)
    step = dep(D, D, eqs=[(1, -1, 0, 1)])        # i_t = i_s + 1, twice
    P.add_dependence("S", "S", step, "a")
    P.add_dependence("S", "S", step, "b")
    g = TiledTaskGraph(P, {"S": Tiling((1,))})
    params = {"N": 6}
    m = g.materialize(params)
    assert m.n_edges == 2 * 5                    # both edges materialized
    for name, fn in MODELS.items():
        r = fn(g, params, workers=2)
        validate_order(g, params, r)
        if name == "tags1":
            # one one-use tag + one pending get per dependence INSTANCE
            assert r.counters.spatial.total == 2 * m.n_edges


# ------------------------------------------------------------------- atlas
#
# The Table-2 atlas (core/edt/atlas.py): the smoke sweep must reproduce the
# paper's asymptotic classes on every (model, program, counter) its ladders
# can measure — this is the CI gate behind the sync-atlas artifact.

_ATLAS = None


def _atlas():
    global _ATLAS
    if _ATLAS is None:
        _ATLAS = atlas.sweep(smoke=True)
    return _ATLAS


def test_atlas_smoke_matches_table2():
    res = _atlas()
    assert res["fit_failures"] == [], res["fit_failures"]
    rows = res["rows"]
    # acceptance floor: >= 5 sync models x >= 3 program classes
    assert len({r["model"] for r in rows}) >= 5
    assert len({r["family"] for r in rows}) >= 3
    assert len({r["program"] for r in rows}) >= 3
    for f in res["fits"]:
        assert f["relation"] in ("match", "below")
        assert set(f["expected"]) <= set(atlas.CLASSES)


def test_atlas_rows_json_round_trip_with_string_keys():
    """The whole sweep payload is structured JSON — the (model, K)
    tuple-key bug class (shipped as ``repr`` from schema v2 to v7) can
    never reappear."""
    res = _atlas()
    assert json.loads(json.dumps(res))
    for r in res["rows"]:
        assert all(isinstance(k, str) for k in r)


def test_atlas_fit_class_picks_the_generating_class():
    refs = {"1": [1.0] * 3, "r": [4.0, 8.0, 16.0], "n": [16.0, 64.0, 256.0],
            "e": [40.0, 320.0, 2560.0], "n2": [256.0, 4096.0, 65536.0]}
    for cls in ("r", "n", "e", "n2"):
        assert atlas.fit_class([2 * v for v in refs[cls]], refs)["cls"] == cls
    # an exact match fits with scale 1 and no residual
    fit = atlas.fit_class([16, 64, 256], refs)
    assert fit["cls"] == "n" and fit["scale"] == 1.0 and fit["resid"] == 0.0
    # an all-zero counter is class 1, not a log-domain error
    assert atlas.fit_class([0, 0, 0], refs)["cls"] == "1"


def test_atlas_indistinguishability_is_data_driven():
    insts = atlas.build_instances(atlas.WORKLOADS[0], smoke=True)  # diamond
    refs = atlas.reference_curves(insts)
    assert atlas._indistinct(refs, "n", "e")       # e ~ 2n on the grid
    assert not atlas._indistinct(refs, "r", "n")   # frontier vs area


def test_atlas_growth_factors_honest_about_zero():
    """0 -> 0 is flat (1.0) and 0 -> b is born-at-scale (None); neither is
    masked by a max(1, ...) floor, and the task factor is measured."""
    base = {"program": "p", "model": "m", "grain": 1.0,
            "inflight_tasks_peak": 2, "garbage_peak": 1}
    rows = [
        dict(base, size="a", n_tasks=10, n_edges=18, width=4,
             startup_ops=0, spatial_peak=5, inflight_deps_peak=0),
        dict(base, size="b", n_tasks=40, n_edges=76, width=8,
             startup_ops=0, spatial_peak=20, inflight_deps_peak=3,
             inflight_tasks_peak=8, garbage_peak=0),
    ]
    (g,) = atlas.growth_rows(rows)
    assert g["task_factor"] == 4.0          # measured, not a K^2 closed form
    assert g["startup_ops"] == 1.0          # 0 -> 0 stays flat
    assert g["inflight_deps_peak"] is None  # born at scale, not x3
    assert g["spatial_peak"] == 4.0
    assert g["garbage_peak"] == 0.0         # a drop is a drop, not x1


def test_atlas_grain_axis_prices_startup_not_counters():
    """Lifetime object counts are grain-invariant; only makespan moves."""
    insts = atlas.build_instances(atlas.WORKLOADS[0], smoke=True)
    fine = atlas.measure(insts[0], "counted", grain=0.2)
    coarse = atlas.measure(insts[0], "counted", grain=5.0)
    for c in atlas.ATLAS_COUNTERS:
        assert fine[c] == coarse[c], c
    assert coarse["makespan"] > fine["makespan"]


def test_atlas_expected_covers_every_model_and_counter():
    assert set(atlas.EXPECTED) == set(MODELS)
    for spec in atlas.EXPECTED.values():
        assert set(spec) == set(atlas.ATLAS_COUNTERS)
        for classes in spec.values():
            assert classes and set(classes) <= set(atlas.CLASSES)
    # the table's headline start-up rows
    assert atlas.EXPECTED["prescribed"]["startup_ops"] == ("e",)
    assert atlas.EXPECTED["counted"]["startup_ops"] == ("n",)
    for m in ("tags1", "tags2", "autodec", "autodec_nosrc"):
        assert atlas.EXPECTED[m]["startup_ops"] == ("1",)


def test_atlas_crossover_smoke_verified():
    res = atlas.crossover(smoke=True)
    paths = {r["path"] for r in res["rows"]}
    assert paths == {"host_sim", "device_replay", "distributed_inline_2"}
    for r in res["rows"]:
        assert r["verified"], r
        assert r["per_task_us"] > 0
    assert set(res["points"]) == {"device_replay", "distributed_inline_2"}


def test_atlas_crossover_device_failure_fails_the_atlas(monkeypatch):
    """A device engine that fails raises out of the crossover: no row
    records it as skipped while the section reports success."""
    from repro.core.edt import device

    def broken(self):
        raise RuntimeError("device sweep failed")

    monkeypatch.setattr(device.DeviceExecutor, "run", broken)
    with pytest.raises(RuntimeError, match="device sweep failed"):
        atlas.crossover(smoke=True)
