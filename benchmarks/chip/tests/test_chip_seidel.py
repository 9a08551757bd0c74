"""The seidel-2d cell's plain reference and the readers of the in-tile
wavefront's metrics, on the CPU."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness, roofline, scopes  # noqa: E402
from benchmarks.chip.seidel_reference import seidel_solve  # noqa: E402

CELL = "seidel2d-large.warm"


def _polybench_loop(state: np.ndarray, steps: int) -> np.ndarray:
    """PolyBench's loop nest as written, one point at a time, on the
    zero-padded grid (every site of ``[0, N)^2`` updated)."""
    n = state.shape[0]
    a = np.zeros((n + 2, n + 2))
    a[1:-1, 1:-1] = state
    for _ in range(steps):
        for i in range(1, n + 1):
            for j in range(1, n + 1):
                a[i, j] = a[i - 1:i + 2, j - 1:j + 2].sum() / 9.0
    return a[1:-1, 1:-1]


@pytest.mark.parametrize("n,steps", [(1, 2), (2, 3), (7, 3), (12, 2)])
def test_reference_is_polybenchs_loop(n, steps):
    state = np.random.default_rng(2**33 + n).random((n, n))
    np.testing.assert_allclose(seidel_solve(state, steps),
                               _polybench_loop(state, steps),
                               rtol=1e-13, atol=1e-15)


def test_reference_leaves_its_input_alone():
    state = np.random.default_rng(3).random((6, 6), dtype=np.float32)
    before = state.copy()
    out = seidel_solve(state, 2)
    assert np.array_equal(state, before) and out.dtype == np.float64


def test_reference_imports_nothing_of_the_program():
    import ast

    tree = ast.parse((ROOT / "benchmarks" / "chip"
                      / "seidel_reference.py").read_text())
    names = {a.name for node in ast.walk(tree)
             if isinstance(node, (ast.Import, ast.ImportFrom))
             for a in node.names}
    names |= {node.module for node in ast.walk(tree)
              if isinstance(node, ast.ImportFrom)}
    assert names <= {"__future__", "annotations", "numpy"}, names


def test_the_cell_names_the_reference():
    cell = harness.load_cell(CELL)
    state = np.random.default_rng(4).random((9, 9))
    assert np.array_equal(cell.solve(state, 3), seidel_solve(state, 3))
    assert cell.params == {"T": 4, "N": 2000}
    assert cell.grid(cell.params) == (2000, 2000)


# -------------------------------------------------------------- readers
def _observed(monkeypatch, summary):
    from benchmarks.chip.trace import TraceSummary

    cell = harness.load_cell(CELL)
    reqs = [harness.RequestRecord(i, {"T": 4, "N": 100}, n_tasks=30,
                                  depth=4, max_width=12) for i in range(2)]
    obs = harness.Observed(cell, reqs,
                           TraceSummary(busy_s=0.5, window_s=2.0, devices=1),
                           roofline.peaks("TPU v5 lite"))
    monkeypatch.setattr(scopes, "of_run", lambda run: summary)
    return obs


SUMMARY = scopes.ScopeSummary(busy_s=0.5, window_s=2.0, devices=1, scopes={
    "edt.body": 0.3, "edt.wave": 0.1, "edt.decrement": 0.04,
    "edt.frontier": 0.02, "edt.validate": 0.02, "unattributed": 0.02})


@pytest.mark.parametrize("metric,want", [
    ("wave_device_s.warm", 0.05),
    # two solves of 2 * 100**2 * 4 * 4 B = 320 kB each, at 819 GB/s, in
    # 0.3 + 0.1 s of body and wave time
    ("wave_body_hbm_roofline", 100 * 6.4e5 / 819e9 / 0.4),
    ("body_device_s.warm", 0.15),
])
def test_wave_reader_arithmetic_by_hand(monkeypatch, metric, want):
    obs = _observed(monkeypatch, SUMMARY)
    assert harness.load_reader(metric)(obs) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["wave_device_s.warm",
                                    "wave_body_hbm_roofline"])
@pytest.mark.parametrize("summary", [
    None, scopes.ScopeSummary(0.5, 2.0, 1, {"unattributed": 0.5}),
    scopes.ScopeSummary(0.5, 2.0, 1, {"edt.body": 0.4,
                                      "edt.decrement": 0.1})])
def test_wave_readers_return_nothing_without_the_wave_scope(
        monkeypatch, metric, summary):
    """A trace of a program whose tile body runs no waves (a Jacobi body,
    or the parent program) reads nothing, and does not raise."""
    assert harness.load_reader(metric)(_observed(monkeypatch,
                                                 summary)) is None


def test_wave_metrics_belong_to_the_seidel_cell_alone():
    bench = harness.load_cell(CELL)
    names = {m["name"] for m in bench.per_layer}
    assert {"wave_device_s.warm", "wave_body_hbm_roofline",
            "body_device_s.warm", "sync_device_s.warm", "body_hbm_roofline",
            "sweep_hbm_roofline", "lane_occupancy.warm",
            "device_idle_share.warm", "rebuild_s.warm"} <= names
    for other in ("jacobi2d-coarse-large.warm", "jacobi2d-fine-medium.warm"):
        assert not {"wave_device_s.warm", "wave_body_hbm_roofline"} & {
            m["name"] for m in harness.load_cell(other).per_layer}
