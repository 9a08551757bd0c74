"""CPU tests of the chip benchmark's harness: discovery by name, traffic,
least bytes, the peak table, the refusal without a TPU, and the check that
decides ``correct`` (sound runs pass; the bfloat16 control and planted
faults fail).  No test starts a process pool."""
from __future__ import annotations

import itertools
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness, reference, roofline, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
#: Small instances of each cell that the CPU holds: config keys, and for
#: a cold mix its drawn set.
SMALL = {"N": 24, "TSTEPS": 3}          # 6 sweeps
SMALL_COLD = {"params": {"T": 6, "N": {"values": [21, 24, 27]}},
              "setup_params": {"T": 6, "N": 20}}


def small_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config.update(SMALL, shards=0)
    if cell.config["tile"][1] > 8:       # the coarse tile: keep a few tiles
        cell.config.update(tile=[4, 8, 8])
    if cell.mix["cache"] == "cold":
        cell.mix.update(SMALL_COLD)
    if "warmup_seconds" in cell.mix:      # one warm-up request
        cell.mix["warmup_seconds"] = 1e-3
    return cell


# ------------------------------------------------------------ discovery
@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files(name):
    cell = harness.load_cell(name)
    assert cell.chips == 1
    assert cell.mix["reports"] in {m["name"] for m in cell.end_to_end}
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))
    assert cell.limits["max_rel_err"] > 0


def test_discovery_by_file_name_in_a_new_directory(tmp_path):
    """A later cell is data and files of its own: nothing here is edited."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "limits"):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(
        {"N": 9, "TSTEPS": 3, "sweeps_per_tstep": 2, "program": "jacobi2d",
         "tile": [1, 3, 3], "reference": {"solve": "tiny_ref:solve"}}))
    (bench_dir / "tiny_ref.py").write_text(
        "def solve(state, steps):\n    return state\n")
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(
        {"cache": "warm", "reports": "tiny_s"}))
    (bench_dir / "metrics" / "tiny_share.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    (bench_dir / "limits" / "tiny.burst.json").write_text(
        json.dumps({"max_rel_err": 1e-3}))
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.burst", "config": "tiny",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "tiny_s", "unit": "s"},
                       {"name": "other_s", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "tiny_share.x", "unit": "%",
                       "moves": "tiny_s"},
                      {"name": "not_here", "unit": "%", "moves": "tiny_s",
                       "workloads": ["elsewhere"]}]}))
    before = {p: p.read_bytes() for p in Path(harness.BENCH_DIR).rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    cell = harness.load_cell("tiny.burst", bench_file, bench_dir)
    assert cell.params == {"T": 6, "N": 9}
    assert cell.solve("grid", 6) == "grid" and cell.graph_check is None
    assert [m["name"] for m in cell.end_to_end] == ["tiny_s"]
    assert [m["name"] for m in cell.per_layer] == ["tiny_share.x"]
    assert harness.load_reader("tiny_share.x", bench_dir)(None) == 42.0
    with pytest.raises(KeyError):
        harness.load_cell("absent", bench_file, bench_dir)
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_cold_cell_without_a_graph_reference_is_refused(tmp_path):
    """A cold cell's `correct` covers the graph it scans: a configuration
    that names no graph reference cannot serve one."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(
        {"N": 9, "TSTEPS": 3, "sweeps_per_tstep": 1, "tile": [1, 3, 3],
         "reference": {"solve": "reference:box_solve"}}))
    (bench_dir / "traffic" / "fresh.json").write_text(json.dumps(
        {"cache": "cold", "reports": "tiny_s"}))
    (bench_dir / "limits" / "tiny.fresh.json").write_text(
        json.dumps({"max_rel_err": 1e-3}))
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.fresh", "config": "tiny",
                       "traffic": "fresh", "chips": 1}],
        "end_to_end": [{"name": "tiny_s", "unit": "s"}], "per_layer": []}))
    with pytest.raises(ValueError, match="graph reference"):
        harness.load_cell("tiny.fresh", bench_file, bench_dir)


# --------------------------------------------------------------- traffic
def test_cold_draws_are_distinct_and_fixed_by_the_seed():
    mix = traffic.load("cold_sweep")
    base = {"T": 100, "N": 250}
    big = 2**33 + 12345
    runs = {s: [r.params for r in traffic.requests(mix, base, s)]
            for s in (0, 1, big, big + 1)}
    values = sorted(mix["params"]["N"]["values"])
    for params in runs.values():
        ns = [p["N"] for p in params]
        assert sorted(ns) == values          # every seed, the same set
        assert all(p["T"] == 24 for p in params)
    assert runs[big] == [r.params for r in traffic.requests(mix, base, big)]
    orders = {tuple(p["N"] for p in ps) for ps in runs.values()}
    assert len(orders) == len(runs)          # the seed draws the order
    assert traffic.setup_params(mix, base) == {"T": 24, "N": 229}
    assert 229 not in values


def test_warm_requests_repeat_the_configured_instance():
    mix = traffic.load("warm_repeat")
    reqs = list(itertools.islice(traffic.requests(mix, {"T": 16, "N": 9}, 3),
                                 50))
    assert [r.index for r in reqs] == list(range(50))
    assert all(r.params == {"T": 16, "N": 9} for r in reqs)


def test_initial_grids_follow_seed_and_index():
    a = traffic.initial_grid(2**32 + 1, 0, (5, 5))
    assert a.dtype == np.float32 and a.shape == (5, 5)
    assert np.array_equal(a, traffic.initial_grid(2**32 + 1, 0, (5, 5)))
    assert not np.array_equal(a, traffic.initial_grid(2**32 + 1, 1, (5, 5)))
    assert not np.array_equal(a, traffic.initial_grid(2**32 + 2, 0, (5, 5)))


# ------------------------------------------------------ bytes and peaks
@pytest.mark.parametrize("config,want", [
    ("jacobi2d-coarse-large", 2 * 1300 * 1300 * 4 * 16),    # 216,320,000
    ("jacobi2d-fine-medium", 2 * 250 * 250 * 4 * 200),      # 100,000,000
])
def test_least_bytes_by_hand(config, want):
    cell = next(harness.load_cell(w["name"]) for w in BENCH["workloads"]
                if w["config"] == config and w["traffic"] == "warm_repeat")
    p = cell.params
    assert roofline.least_bytes(p["N"], p["T"]) == want


def test_roofline_share_by_hand():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    # 819 MB in 0.1 s at 819 GB/s: the bound is 1 ms, 1% of 0.1 s
    assert roofline.hbm_roofline_pct(819e6, 0.1, peak) == pytest.approx(1.0)


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v99 imaginary")


# ----------------------------------------------------- the entry point
def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    out = _run_py(ROOT)
    assert out.returncode == 1, out.stderr
    assert "TPU" in out.stderr
    assert not out.stdout.strip()


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    out = _run_py(tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()


# ------------------------------------------------------------ reference
def test_reference_matches_the_programs_numpy_solve():
    from repro.kernels.stencils import SPECS, reference_solve

    state = traffic.initial_grid(7, 0, (17, 17))
    want = reference.box_solve(state, 5)
    got = reference_solve(SPECS["jacobi2d"], state, 5)
    assert reference.rel_err(got, want) < 1e-6


@pytest.mark.parametrize("tile,steps,n", [((2, 2, 2), 6, 21),
                                          ((4, 8, 8), 6, 24)])
def test_reference_tile_graph_against_the_scan(tile, steps, n):
    from repro.core.edt import ExecutionConfig, Session
    from repro.core.poly import Tiling
    from repro.core.programs import PROGRAMS

    s = Session(ExecutionConfig(backend="numpy"))
    g = s.graph(PROGRAMS["jacobi2d"](), {"S": Tiling(tile)})
    ig, sched = s.schedule(g, {"T": steps, "N": n})
    coords = ig.stmt_blocks[0][1]
    assert reference.graph_check({"T": steps, "N": n}, tile, coords,
                               ig.edge_src, ig.edge_tgt,
                               sched.level_of) == (0, 0, 0)
    # a dropped edge, a missing tile and a level swap are each seen
    keep = np.ones(len(ig.edge_src), bool)
    keep[len(keep) // 2] = False
    assert reference.graph_check({"T": steps, "N": n}, tile, coords,
                               ig.edge_src[keep], ig.edge_tgt[keep],
                               sched.level_of)[1] == 1
    moved = coords.copy()
    moved[0] = coords[-1]
    assert reference.graph_check({"T": steps, "N": n}, tile, moved,
                               ig.edge_src, ig.edge_tgt,
                               sched.level_of)[0] >= 1
    flat = np.zeros_like(sched.level_of)
    assert reference.graph_check({"T": steps, "N": n}, tile, coords,
                               ig.edge_src, ig.edge_tgt, flat)[2] > 0


# ------------------------------------------ correct: sound, control, faults
def _fault(kind, monkeypatch):
    """Break the timed path underneath the harness."""
    from repro.core.edt import fused

    if kind == "state_unchanged":
        monkeypatch.setattr(fused.FusedExecutor, "_make_compute",
                            lambda self, jnp: (lambda u, org, active=None: u))
    elif kind == "half_left_out":
        make = fused.FusedExecutor._make_compute

        def half(self, jnp):
            compute = make(self, jnp)

            def run(u, org, active=None):
                lanes = jnp.arange(org.shape[0]) % 2 == 0
                return compute(u, org, lanes if active is None
                               else active & lanes)
            return run
        monkeypatch.setattr(fused.FusedExecutor, "_make_compute", half)
    elif kind == "answer_altered":
        finish = fused.FusedExecutor._finish

        def altered(self, mode, levels, level_of, counters, u):
            u = np.array(u)
            u[((self.steps - 1) & 1) * self.size + self.size // 2] += 1e-2
            return finish(self, mode, levels, level_of, counters, u)
        monkeypatch.setattr(fused.FusedExecutor, "_finish", altered)


def _run(cell, seed=2**33 + 3):
    import jax

    return harness.run_cell(cell, seed, 0.3, device=jax.devices()[0],
                            log=lambda m: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(small_cell(name))
    assert out["correct"], out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", small_cell(name).mix["reports"]}


@pytest.mark.parametrize("kind", ["state_unchanged", "half_left_out",
                                  "answer_altered"])
@pytest.mark.parametrize("name", [CELLS[0], CELLS[-1]])
def test_planted_fault_is_not_correct(name, kind, monkeypatch):
    _fault(kind, monkeypatch)
    out = _run(small_cell(name))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_fails_the_limit(name):
    from benchmarks.chip import control

    lines = []
    control.readings(small_cell(name), [5], [5, 2**33 + 9], 1,
                     log=lines.append)
    limit = harness.load_cell(name).limits["max_rel_err"]
    program, *controls = map(json.loads, lines)
    assert program["program"] < limit, program
    for line in controls:
        assert line["control"] > limit, line


# ---------------------------------------------------------------- readers
def _observed(traced=True):
    from benchmarks.chip.trace import TraceSummary

    cell = harness.load_cell(CELLS[0])
    reqs = []
    for i, (scan, sched, pack) in enumerate([(0.3, 0.05, 0.05),
                                             (0.5, 0.1, 0.1)]):
        reqs.append(harness.RequestRecord(
            i, {"T": 10, "N": 100},
            spans={"Session.index_graph": scan, "Session.schedule": sched,
                   "Session.fused_packed": pack},
            jax={"compile_s": 1.0 + i, "trace_s": 0.1, "lower_s": 0.4}))
    trace = TraceSummary(busy_s=0.5, window_s=2.0, devices=1)
    return harness.Observed(cell, reqs, trace if traced else None,
                            roofline.peaks("TPU v5 lite"))


@pytest.mark.parametrize("metric,want", [
    ("scan_s.cold", 0.4),
    ("level_pack_s.cold", 0.15),
    ("compile_s.cold", 1.5),
    ("rebuild_s.warm", 2.0),
    ("device_idle_share.warm", 75.0),
    ("sweep_device_s.warm", 0.25),
    # two solves of 2 * 100**2 * 4 * 10 B = 800 kB each, at 819 GB/s,
    # in 0.5 s of device time
    ("sweep_hbm_roofline", 100 * 1.6e6 / 819e9 / 0.5),
])
def test_reader_arithmetic_by_hand(metric, want):
    assert harness.load_reader(metric)(_observed()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["device_idle_share.warm",
                                    "sweep_device_s.warm",
                                    "sweep_hbm_roofline"])
def test_device_readers_return_nothing_without_a_trace(metric):
    assert harness.load_reader(metric)(_observed(traced=False)) is None
