"""Fast smoke checks of the benchmark entry points (< 1 minute total).

These do not assert absolute timings (CI noise); they assert that every
benchmark section runs end-to-end in smoke mode, emits its CSV rows and
machine-readable payload, and that the taskgen benchmark's built-in
backend-equality checks pass — plus two sanity bounds: compiled must not be
slower than Fraction, and the numpy index-array enumeration must not be
slower than compiled, on a real materialize.
"""
import json
import time

import pytest

from repro.core.edt import TiledTaskGraph
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS


def _collect(run_fn, **kw):
    lines = []
    out = run_fn(emit=lambda *a, **k: lines.append(str(a[0]) if a else ""), **kw)
    return lines, out


def test_bench_taskgen_smoke():
    from benchmarks import bench_taskgen
    lines, out = _collect(bench_taskgen.run, smoke=True)
    rows = [ln for ln in lines if ln and not ln.startswith("#")]
    # header + one row per (smoke program, backend) + sharded numpy rows
    assert rows[0].startswith("program,backend,shards,")
    per_prog = len(bench_taskgen.BACKENDS) + len(bench_taskgen.SHARD_COUNTS)
    n_expect = len(bench_taskgen.SMOKE_SUITE) * per_prog
    assert len(rows) == 1 + n_expect
    assert any("geomean" in ln for ln in lines)
    # stable machine-readable schema: (name, backend, shards, tasks/sec)
    assert out["schema_version"] == 2
    assert len(out["rows"]) == n_expect
    for r in out["rows"]:
        assert {"program", "backend", "shards", "tasks_per_s"} <= set(r)
        assert r["backend"] in bench_taskgen.BACKENDS
        assert r["shards"] == 1 or r["backend"] == "numpy"
    assert json.dumps(out)  # artifact must be JSON-serializable
    assert out["geomean"]["numpy_enum_over_compiled"] > 0
    # the smoke-scale curve ran, verified byte-identical per shard count
    scale = out["shard_scale"]
    assert [r["shards"] for r in scale] == list(
        bench_taskgen.SCALE_SHARDS) * len(bench_taskgen.SMOKE_SCALE_SUITE)
    assert all(r["n_tasks"] == scale[0]["n_tasks"] for r in scale[:3])


def test_bench_compile_smoke():
    from benchmarks import bench_compile
    lines, _ = _collect(bench_compile.run, smoke=True)
    assert len(lines) == 2 + len(bench_compile.SMOKE_SUITE)
    assert "TIMEOUT" not in "\n".join(lines)


def test_bench_sync_and_executor_smoke():
    from benchmarks import bench_executor, bench_sync_overheads
    sync = bench_sync_overheads.run(emit=lambda *a, **k: None, smoke=True)
    # schema v8: the Table-2 atlas — structured rows with string keys,
    # fitted classes all within the paper's bounds, crossover present
    assert json.dumps(sync)
    assert sync["rows"] and sync["fits"] and sync["growth"]
    assert sync["fit_failures"] == []
    assert len({r["model"] for r in sync["rows"]}) >= 5
    assert len({r["program"] for r in sync["rows"]}) >= 3
    assert {r["path"] for r in sync["crossover"]["rows"]} == {
        "host_sim", "device_replay", "distributed_inline_2"}
    out = bench_executor.run(emit=lambda *a, **k: None, smoke=True)
    assert json.dumps(out)  # v3: executor data must be JSON-serializable
    assert len(out["models"]) == (len(bench_executor.SMOKE_CASES)
                                  * len(bench_executor.MODELS_))
    assert all(r["makespan"] > 0 for r in out["models"])
    # host-vs-device dispatch rows: every path priced and cross-verified
    paths = {r["path"] for r in out["dispatch"]}
    assert {"host", "device_replay", "device_discover"} <= paths
    for r in out["dispatch"]:
        assert {"program", "path", "shards", "tasks", "edges", "depth",
                "seconds", "per_task_us", "verified"} <= set(r)
        assert r["verified"] is True
        assert r["per_task_us"] > 0


def test_run_harness_smoke_mode(tmp_path, monkeypatch):
    """`python -m benchmarks.run --smoke --only taskgen --json F` exits
    cleanly and writes the stable artifact schema."""
    from benchmarks import run as harness
    # the harness places JAX's compile cache; keep it off this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    path = tmp_path / "perf.json"
    assert harness.main(["--smoke", "--only", "taskgen",
                         "--json", str(path)]) == 0
    report = json.loads(path.read_text())
    assert report["schema_version"] == 8
    assert report["smoke"] is True
    assert report["host"]["cpus"] >= 1
    sec = report["sections"]["taskgen"]
    assert sec["ok"] is True
    assert sec["data"]["rows"], "taskgen rows missing from artifact"
    assert sec["data"]["shard_scale"], "shard-scale rows missing"
    assert {r["shards"] for r in sec["data"]["rows"]} >= {1, 2}


def test_every_section_round_trips_json_in_smoke():
    """Every section's smoke return value must survive ``json.dumps`` — the
    regression gate for the v2..v7 bug where the ``sync`` section returned
    tuple-keyed dicts and shipped in every artifact as ``repr(...)``."""
    import inspect

    from benchmarks import run as harness
    for name, fn in harness.section_registry().items():
        params = inspect.signature(fn).parameters
        kw = {}
        if "smoke" in params:
            kw["smoke"] = True
        if "emit" in params:
            kw["emit"] = lambda *a, **k: None
        ok, data = harness.encode_section_data(fn(**kw))
        assert ok, f"section {name} returned unserializable data: {data}"


def test_encode_section_data_fails_loudly():
    """Unserializable section data is an error record, never a repr."""
    from benchmarks.run import encode_section_data
    ok, data = encode_section_data({("model", 4): 1})   # the old sync shape
    assert ok is False
    assert "unserializable" in data and data["type"] == "dict"
    ok, data = encode_section_data({"rows": [1, 2]})
    assert ok is True and data == {"rows": [1, 2]}


def test_service_section_smoke():
    """The schema-v5 graph-cache section: warm hits verified against the
    cold products, flagship row present, service stats coalesce
    (docs/service.md)."""
    from benchmarks import bench_service
    lines, out = _collect(bench_service.run, smoke=True)
    assert any(ln.startswith("case,kind,") for ln in lines)
    assert out["rows"], "service rows missing"
    for r in out["rows"]:
        assert {"case", "kind", "n_tasks", "cold_ms", "warm_ms", "speedup",
                "sub_ms_warm", "verified"} <= set(r)
        assert r["verified"] is True
        assert r["sub_ms_warm"] is True
        assert r["speedup"] > 1
    flag = out["flagship"]
    assert flag["kind"] == "packed" and flag["verified"] is True
    svc = out["service"]
    assert svc["cold_fills"] == svc["keys"]      # exactly-once per key
    assert svc["hit_rate"] > 0.5                 # everything else was warm
    assert json.dumps(out)


def test_fused_section_smoke():
    """The schema-v6 fused-execution section: every path priced per task
    and per point, numerics verified against the handwritten solve
    (docs/device_exec.md, "Fused execution")."""
    from benchmarks import bench_fused
    lines, out = _collect(bench_fused.run, smoke=True)
    assert any(ln.startswith("program,path,") for ln in lines)
    assert out["rows"], "fused rows missing"
    paths = {r["path"] for r in out["rows"]}
    assert {"handwritten", "device_replay", "fused", "fused_novalidate",
            "host_dispatch"} <= paths
    for r in out["rows"]:
        assert {"program", "path", "tasks", "points", "seconds",
                "per_task_us", "per_point_ns", "vs_handwritten",
                "verified"} <= set(r)
        assert r["verified"] is True
    # the acceptance record only exists on the full flagship run
    assert out["acceptance"] is None
    assert json.dumps(out)


def test_distributed_section_smoke():
    """The schema-v7 distributed section: every (ranks, transport) row
    byte-verified against the single-host oracle, message volume equal to
    the cross-partition edge count (docs/distributed.md)."""
    from benchmarks import bench_distributed
    lines, out = _collect(bench_distributed.run, smoke=True)
    assert any(ln.startswith("ranks,transport,") for ln in lines)
    assert out["rows"], "distributed rows missing"
    for r in out["rows"]:
        assert {"program", "tasks", "ranks", "engine", "transport",
                "seconds", "per_task_us", "msgs", "batches", "cross_frac",
                "attempts", "per_rank", "verified"} <= set(r)
        assert r["verified"] is True
        assert len(r["per_rank"]) == r["ranks"]
        assert sum(s["n_local"] for s in r["per_rank"]) == r["tasks"]
    one = next(r for r in out["rows"] if r["ranks"] == 1)
    assert one["msgs"] == 0                      # no cross edges at 1 rank
    assert json.dumps(out)


def test_faults_section_smoke():
    """The recovery-overhead section: rows verified, faults
    actually fired, artifact JSON-serializable (docs/robustness.md)."""
    from benchmarks import bench_faults
    lines, out = _collect(bench_faults.run, smoke=True)
    assert any(ln.startswith("shards,fault,") for ln in lines)
    assert out["rows"], "faults rows missing"
    for r in out["rows"]:
        assert {"shards", "fault", "clean_s", "faulty_s",
                "overhead_ratio", "verified"} <= set(r)
        assert r["verified"] is True
    assert json.dumps(out)


def test_compiled_not_slower_than_fraction():
    """Loose perf floor: the whole point of the backend, cheaply verified."""
    tilings = {"S": Tiling((2, 2, 2))}
    params = {"T": 6, "N": 10}
    gc = TiledTaskGraph(PROGRAMS["jacobi2d"](), tilings)
    gf = TiledTaskGraph(PROGRAMS["jacobi2d"](), tilings, backend="fraction")
    t0 = time.perf_counter()
    mc = gc.materialize(params)
    t_c = time.perf_counter() - t0
    t0 = time.perf_counter()
    mf = gf.materialize(params)
    t_f = time.perf_counter() - t0
    assert mc.succ == mf.succ
    assert t_c < t_f  # compiled wins by ~50x; < is a generous CI-safe bound


def test_numpy_enum_not_slower_than_compiled():
    """The vectorized index-array enumeration must beat the scalar compiled
    materialize (it wins by ~5-10x; < is a generous CI-safe bound)."""
    tilings = {"S": Tiling((1, 1))}
    params = {"K": 40}
    gc = TiledTaskGraph(PROGRAMS["diamond"](), tilings)
    gn = TiledTaskGraph(PROGRAMS["diamond"](), tilings, backend="numpy")
    gc.materialize(params)          # warm both codegens outside the timing
    gn.index_graph(params)
    t0 = time.perf_counter()
    mc = gc.materialize(params)
    t_c = time.perf_counter() - t0
    t0 = time.perf_counter()
    ig = gn.index_graph(params)
    t_n = time.perf_counter() - t0
    assert ig.n == len(mc.tasks) and ig.n_edges == mc.n_edges
    assert t_n < t_c


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_placement(env_set, tmp_path, monkeypatch):
    """Entry points keep JAX's compile cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads it; the code sets
    nothing), else at the fixed ``<checkout>/.jax_cache``."""
    import pathlib

    import jax

    from repro.launch.compile_cache import CHECKOUT_CACHE, use_compile_cache

    old = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == old
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            checkout = pathlib.Path(__file__).resolve().parents[1]
            assert CHECKOUT_CACHE == checkout / ".jax_cache"
            assert use_compile_cache() == str(CHECKOUT_CACHE)
            assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
