"""Graph-cache suite: warm identity, byte budget, incremental stitching,
and service-level coalescing.

The contract under test (``docs/service.md``):

* a warm hit returns the *same* arrays a cold materialization produced —
  byte-identical across every generation backend including the sharded
  engine;
* the LRU never holds more than ``CachePolicy.max_bytes`` of arrays;
* incremental re-materialization (outer-param stitch from a cached donor)
  is byte-identical to a cold full scan;
* N concurrent :class:`ScheduleService` requests for one cold key run
  exactly one materialization.
"""
from __future__ import annotations

import asyncio
import threading
import time

import numpy as np
import pytest

from repro.core.edt import (CachePolicy, ExecutionConfig, GraphCache,
                            ScheduleService, Session, graph_cache_info)
from repro.core.edt.shard import process_pool
from repro.core.edt.taskgraph import TiledTaskGraph
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

BACKENDS = ("fraction", "compiled", "numpy")


@pytest.fixture(scope="module")
def pool():
    p = process_pool(2)
    p.submit(int, 0).result()
    yield p
    p.shutdown()


def _graph(name="jacobi2d", tiles=(2, 2, 2), backend="numpy"):
    return TiledTaskGraph(PROGRAMS[name](), {"S": Tiling(tiles)},
                          backend=backend)


def _assert_ig_identical(a, b):
    assert a.n == b.n and a.n_edges == b.n_edges
    assert np.array_equal(a.edge_src, b.edge_src)
    assert np.array_equal(a.edge_tgt, b.edge_tgt)
    assert np.array_equal(a.pred_n, b.pred_n)
    for (na, xa), (nb, xb) in zip(a.stmt_blocks, b.stmt_blocks):
        assert na == nb and np.array_equal(xa, xb)


# ======================================================== warm identity
@pytest.mark.parametrize("backend", BACKENDS)
def test_warm_hit_identical_to_cold(backend):
    """Warm products are the cold products — same objects, same bytes —
    for every scanning backend."""
    g = _graph(backend=backend)
    params = {"T": 4, "N": 16}
    cache = GraphCache(CachePolicy(incremental=False))
    cold = cache.graph(g, params)
    oracle = g.index_graph(params)     # uncached reference
    _assert_ig_identical(cold, oracle)
    warm = cache.graph(g, params)
    assert warm is cold                # by-reference warm hit
    ig, sched = cache.schedule(g, params)
    assert ig is cold
    ig2, sched2 = cache.schedule(g, params)
    assert sched2 is sched
    dg, ds = cache.packed(g, params)
    dg2, ds2 = cache.packed(g, params)
    assert dg2 is dg and ds2 is ds
    assert cache.info()["hits"] >= 4


def test_warm_hit_identical_to_cold_sharded(pool):
    """The sharded engine fills the cache with the same arrays the
    in-process scan produces; the warm hit returns them by reference."""
    g = _graph()
    params = {"T": 4, "N": 16}
    cfg = ExecutionConfig(shards=2, pool=pool)
    cache = GraphCache()
    cold = cache.graph(g, params, cfg)
    _assert_ig_identical(cold, g.index_graph(params))
    assert cache.graph(g, params, cfg) is cold


def test_fingerprint_distinguishes_programs_not_backends():
    """Identical programs share a fingerprint across backends (the cache
    key is the *parametric program*); different programs never collide."""
    fps = {b: _graph(backend=b).fingerprint() for b in BACKENDS}
    assert len(set(fps.values())) == 1
    assert _graph("trisolv", (4, 4)).fingerprint() != fps["numpy"]
    assert _graph(tiles=(2, 2, 4)).fingerprint() != fps["numpy"]


# ========================================================= byte budget
def test_eviction_respects_byte_budget():
    """The cache never exceeds max_bytes; LRU entries evict whole."""
    g = _graph("trisolv", (4, 4))
    budget = 20_000
    cache = GraphCache(CachePolicy(max_entries=64, max_bytes=budget,
                                   incremental=False))
    for n in range(8, 32, 2):
        cache.packed(g, {"N": n})
        assert cache.info()["bytes"] <= budget
    info = cache.info()
    assert info["evictions"] > 0
    assert info["entries"] < 12        # the budget actually bit


def test_max_entries_bounds_lru():
    g = _graph("trisolv", (4, 4))
    cache = GraphCache(CachePolicy(max_entries=3, incremental=False))
    for n in range(8, 20, 2):
        cache.graph(g, {"N": n})
    assert cache.info()["entries"] <= 3
    # most-recent key is still warm
    hits0 = cache.info()["hits"]
    cache.graph(g, {"N": 18})
    assert cache.info()["hits"] == hits0 + 1


def test_disabled_cache_is_pass_through():
    g = _graph("trisolv", (4, 4))
    cache = GraphCache(CachePolicy(enabled=False))
    a = cache.graph(g, {"N": 10})
    b = cache.graph(g, {"N": 10})
    assert a is not b
    _assert_ig_identical(a, b)
    assert cache.info()["entries"] == 0


# ======================================================== incremental
@pytest.mark.parametrize("name,tiles,old,new", [
    ("jacobi2d", (2, 2, 2), {"T": 6, "N": 12}, {"T": 9, "N": 12}),   # grow T
    ("jacobi2d", (2, 2, 2), {"T": 9, "N": 12}, {"T": 5, "N": 12}),   # shrink T
    ("stencil1d", (2, 2), {"T": 8, "N": 14}, {"T": 12, "N": 14}),
    ("trisolv", (4, 4), {"N": 20}, {"N": 28}),
])
def test_incremental_matches_full_rescan(name, tiles, old, new):
    """Outer-param change: the stitched graph equals a cold scan, and the
    stitch actually ran (incremental_hits advanced)."""
    g = _graph(name, tiles)
    cache = GraphCache()
    cache.graph(g, old)                       # donor
    inc = cache.graph(g, new)                 # stitched
    assert cache.info()["incremental_hits"] == 1
    assert cache.info()["units_reused"] >= 1
    _assert_ig_identical(inc, _graph(name, tiles).index_graph(new))


def test_incremental_falls_back_when_param_bounds_inner_dims():
    """diamond's K bounds both loop dims — nothing is outer-only, so the
    cache must fall back to a full re-scan (and still be correct)."""
    g = _graph("diamond", (2, 2))
    cache = GraphCache()
    cache.graph(g, {"K": 8})
    ig = cache.graph(g, {"K": 12})
    assert cache.info()["incremental_hits"] == 0
    _assert_ig_identical(ig, _graph("diamond", (2, 2)).index_graph({"K": 12}))


def test_incremental_schedule_and_packed_still_correct():
    """Products derived from a stitched graph (levels, device columns)
    equal those derived from a cold graph."""
    g = _graph()
    old, new = {"T": 6, "N": 12}, {"T": 8, "N": 12}
    cache = GraphCache()
    cache.packed(g, old)
    dg, ds = cache.packed(g, new)
    assert cache.info()["incremental_hits"] == 1
    ig_cold, sched_cold = _graph().index_graph(new), None
    from repro.core.edt import schedule_from_graph
    sched_cold = schedule_from_graph(ig_cold)
    assert np.array_equal(ds.level_of, sched_cold.level_of)
    assert np.array_equal(np.sort(dg.succ), np.sort(ig_cold.edge_tgt))


# ========================================================== coalescing
def test_concurrent_service_requests_materialize_once():
    """N clients, one cold key: exactly one materialization runs; every
    client gets the same object."""
    g = _graph("trisolv", (4, 4))
    calls = []
    inner = g._index_graph_cfg

    def counting(params, cfg, scans=None):
        calls.append(dict(params))
        # hold the fill until the other clients have found it in flight:
        # on a loaded host it could otherwise finish before they probe,
        # and they would count as warm hits instead of coalescing
        deadline = time.monotonic() + 30
        while service.coalesced < 7 and time.monotonic() < deadline:
            time.sleep(0.001)
        return inner(params, cfg, scans=scans)

    g._index_graph_cfg = counting

    async def burst(service, n):
        return await asyncio.gather(
            *(service.schedule(g, {"N": 24}) for _ in range(n)))

    with Session() as session:
        service = ScheduleService(session)
        try:
            results = asyncio.run(burst(service, 8))
        finally:
            service.close()
        assert len(calls) == 1
        igs = {id(ig) for ig, _ in results}
        assert len(igs) == 1
        stats = service.stats()
        assert stats["cold"] == 1
        assert stats["coalesced"] == 7
        # warm pass: no new materialization, no executor hop
        service2 = ScheduleService(session)
        try:
            asyncio.run(burst(service2, 4))
        finally:
            service2.close()
        assert len(calls) == 1
        assert service2.stats()["warm"] == 4


def test_service_distinct_keys_fill_independently():
    g = _graph("trisolv", (4, 4))

    async def go(service):
        return await service.batch(g, [{"N": 16}, {"N": 20}, {"N": 16}])

    service = ScheduleService(config=ExecutionConfig())
    try:
        a, b, a2 = asyncio.run(go(service))
        assert a[0] is a2[0]
        assert a[0] is not b[0]
        stats = service.stats()
        assert stats["cold"] == 2
        assert stats["warm"] + stats["coalesced"] == 1
    finally:
        service.close()


def test_service_frontiers_stream_matches_schedule():
    g = _graph("trisolv", (4, 4))

    async def go(service):
        levels = [lv async for lv in service.frontiers(g, {"N": 16})]
        _, sched = await service.schedule(g, {"N": 16})
        return levels, sched

    service = ScheduleService(config=ExecutionConfig())
    try:
        levels, sched = asyncio.run(go(service))
        assert len(levels) == len(sched.levels)
        for got, want in zip(levels, sched.levels):
            assert np.array_equal(got, want)
    finally:
        service.close()


# ===================================================== param-key hygiene
def test_params_key_normalizes_scalar_types():
    """``np.int64(24)`` (a sharded merge), ``24`` (a direct call), and
    ``24.0`` (JSON) key one entry — the key holds plain Python ints."""
    from repro.core.edt.cache import _params_key
    a = _params_key({"N": 24, "T": 4})
    b = _params_key({"T": np.int64(4), "N": np.float64(24.0)})
    assert a == b
    assert all(type(v) is int for _, v in b)
    assert type(_params_key({"flag": np.bool_(True)})[0][1]) is bool
    assert _params_key({"x": 2.5}) == (("x", 2.5),)   # non-integral floats


def test_params_key_rejects_unhashable_with_named_param():
    from repro.core.edt.cache import _params_key
    with pytest.raises(TypeError, match="'N'.*unhashable"):
        _params_key({"N": [24]})
    with pytest.raises(TypeError, match="'tiles'"):
        _params_key({"N": 24, "tiles": {"S": 2}})


def test_cache_mixed_scalar_types_share_one_entry():
    """Regression: numpy-scalar params used to be able to shadow the
    Python-int entry; now they are one warm key."""
    g = _graph("trisolv", (4, 4))
    cache = GraphCache(CachePolicy(incremental=False))
    cold = cache.graph(g, {"N": 24})
    assert cache.graph(g, {"N": np.int64(24)}) is cold
    assert cache.graph(g, {"N": np.float64(24.0)}) is cold
    assert cache.info()["entries"] == 1
    assert cache.info()["hits"] == 2


# =============================================== service warm-path race
def test_lookup_product_is_atomic_under_eviction():
    """Regression: the service's warm path used to peek one field and then
    re-fetch the product, racing eviction between the two probes.  One
    ``lookup_product`` call returns the *whole* product by reference under
    the cache lock — an eviction landing right after it cannot claw the
    arrays back."""
    g = _graph("trisolv", (4, 4))
    cache = GraphCache(CachePolicy(incremental=False))
    ig, sched = cache.schedule(g, {"N": 16})
    got = cache.lookup_product(g, {"N": 16}, "schedule")
    cache.clear()                     # the eviction lands after the probe
    assert got is not None
    got_ig, got_sched = got
    assert got_ig is ig and got_sched is sched
    # a partially-filled entry is never a warm product
    cache.graph(g, {"N": 20})         # ig cached, schedule not
    assert cache.lookup_product(g, {"N": 20}, "schedule") is None
    assert cache.lookup_product(g, {"N": 20}, "graph") is not None


def test_service_warm_path_never_fills_on_the_loop_under_eviction():
    """Eviction storm (budget admits one entry, two keys alternate): every
    materialization must run on the service executor — the loop thread
    never blocks on a scan, no matter how the warm probe races."""
    g = _graph("trisolv", (4, 4))
    fill_threads = []
    inner = g._index_graph_cfg

    def counting(params, cfg, scans=None):
        fill_threads.append(threading.current_thread().name)
        return inner(params, cfg, scans=scans)

    g._index_graph_cfg = counting
    try:
        session = Session(ExecutionConfig(
            cache=CachePolicy(max_entries=1, incremental=False)))
        service = ScheduleService(session)

        async def storm():
            for _ in range(4):
                await service.schedule(g, {"N": 16})
                await service.schedule(g, {"N": 20})   # evicts N=16

        asyncio.run(storm())
        assert len(fill_threads) == 8            # every request re-fills
        assert all(t.startswith("edt-serve") for t in fill_threads)
        stats = service.stats()
        assert stats["cold"] == 8 and stats["warm"] == 0
        service.close()
        session.close()
    finally:
        g._index_graph_cfg = inner


# ================================================== service close() drain
def test_close_drains_inflight_then_tears_down():
    """Regression: ``close()`` used to shut the executor down under live
    fills.  Now it refuses new requests, waits for every in-flight fill,
    and resolves already-awaiting clients normally — and it is idempotent."""
    g = _graph("trisolv", (4, 4))
    started, release = threading.Event(), threading.Event()
    inner = g._index_graph_cfg

    def slow(params, cfg, scans=None):
        started.set()
        release.wait(10)
        return inner(params, cfg, scans=scans)

    g._index_graph_cfg = slow
    service = ScheduleService(config=ExecutionConfig())
    results = {}
    try:
        client = threading.Thread(
            target=lambda: results.update(
                r=asyncio.run(service.schedule(g, {"N": 24}))))
        client.start()
        assert started.wait(10)               # the fill is in flight
        closer = threading.Thread(target=service.close)
        closer.start()
        time.sleep(0.1)
        assert closer.is_alive()              # close is draining, not axing
        release.set()
        closer.join(10)
        client.join(10)
        assert not closer.is_alive()
        assert "r" in results                 # awaiting client resolved
        assert results["r"][1].depth > 0
    finally:
        release.set()
        g._index_graph_cfg = inner
    service.close()                           # idempotent second close
    with pytest.raises(RuntimeError, match="closed"):
        asyncio.run(service.schedule(g, {"N": 30}))


def test_close_with_no_inflight_is_clean():
    service = ScheduleService(config=ExecutionConfig())
    service.close()
    service.close()
    with pytest.raises(RuntimeError, match="closed"):
        asyncio.run(service.index_graph(_graph("trisolv", (4, 4)),
                                        {"N": 12}))


# ====================================================== introspection
def test_graph_cache_info_aggregates():
    g = _graph("trisolv", (4, 4))
    cache = GraphCache()
    before = graph_cache_info()
    cache.graph(g, {"N": 12})
    cache.graph(g, {"N": 12})
    after = graph_cache_info()
    assert after["hits"] >= before["hits"] + 1
    assert after["entries"] >= 1
