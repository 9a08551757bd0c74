"""The solve path's spans, counters and device scopes
(``repro.core.edt.tracing``, ``fused.py``), on the CPU at a tiny size."""
from __future__ import annotations

import re
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core.edt import Session, TiledTaskGraph, tracing
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

ROOT = Path(__file__).resolve().parents[1]
PARAMS = {"T": 5, "N": 11}
SWEEP_SPANS = ["build", "upload", "dispatch", "wait", "readback"]


def _graph():
    return TiledTaskGraph(PROGRAMS["jacobi2d"](), {"S": Tiling((2, 2, 2))},
                          backend="numpy")


# ----------------------------------------------------------- the module
def test_off_records_nothing_and_imports_no_jax():
    code = ("import sys\n"
            "from repro.core.edt import tracing\n"
            "with tracing.request(1) as rec:\n"
            "    with tracing.span('scan'):\n"
            "        tracing.count('sweep.tasks', 5)\n"
            "assert rec.spans == [] and rec.counters == {}, rec\n"
            "assert 'jax' not in sys.modules\n")
    out = subprocess.run([sys.executable, "-c", code],
                         env={"PYTHONPATH": str(ROOT / "src"),
                              "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_off_span_is_one_shared_noop():
    assert tracing.span("a") is tracing.span("b")
    assert tracing.count("a", 1) is None


def test_nested_spans_carry_parent_and_request():
    with tracing.recording() as outside:
        with tracing.span("setup"):
            tracing.count("sweep.builds")
        with tracing.request(3) as rec:
            with tracing.span("dispatch"):
                with tracing.span("build"):
                    tracing.count("sweep.tasks", 4)
                tracing.count("sweep.tasks", 6)
            with tracing.span("wait"):
                pass
        assert [s.name for s in rec.spans] == ["dispatch", "build", "wait"]
    dispatch, build, wait = rec.spans
    assert (dispatch.parent, build.parent, wait.parent) == (-1, 0, -1)
    assert {s.request for s in rec.spans} == {3}
    assert dispatch.start <= build.start <= build.end <= dispatch.end
    assert dispatch.end <= wait.start
    assert rec.counters == {"sweep.tasks": 10}
    assert 0 < build.seconds <= dispatch.seconds
    # outside any request: the recording's own records
    assert [(s.name, s.request) for s in outside.spans] == [("setup", None)]
    assert outside.counters == {"sweep.builds": 1}
    # off again once the recording closes
    assert tracing.span("x") is tracing.span("y")


def test_recording_keeps_to_its_own_thread():
    def elsewhere():
        with tracing.span("scan"):
            tracing.count("sweep.tasks", 1)

    with tracing.recording(), tracing.request(0) as rec:
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert rec.spans == [] and rec.counters == {}


# ----------------------------------------------------- the fused sweep
def test_fused_replay_records_where_the_work_happens():
    g = _graph()
    with Session() as s, tracing.recording():
        with tracing.request(0) as cold:
            ex = s.fused_executor(g, PARAMS)
            first = ex.run()
        with tracing.request(1) as again:
            ex.run()
        with tracing.request(2) as warm:
            s.fused_executor(g, PARAMS).run()
    names = [sp.name for sp in cold.spans]
    assert names[:1] == ["scan"] and "level" in names and "pack" in names
    assert names[-5:] == SWEEP_SPANS
    # the executor keeps its sweep, and the session keeps the executor
    assert [sp.name for sp in again.spans] == SWEEP_SPANS[1:]
    assert [sp.name for sp in warm.spans] == SWEEP_SPANS[1:]
    ds, dg = ex.ds, ex.dg
    assert cold.counters == {
        "sweep.builds": 1, "sweep.tasks": dg.n,
        "sweep.task_lanes": ds.depth * ds.w_pad,
        "sweep.edges": dg.n_edges, "sweep.edge_lanes": ds.depth * ds.e_pad}
    assert "sweep.builds" not in again.counters
    assert "sweep.builds" not in warm.counters
    assert warm.counters["executor.reused"] == 1
    assert "executor.reused" not in cold.counters
    c = warm.counters
    assert c["sweep.tasks"] / c["sweep.task_lanes"] == pytest.approx(
        dg.n / (first.counters.depth * first.counters.max_in_flight))
    assert 0 < c["sweep.tasks"] < c["sweep.task_lanes"]


def test_recording_leaves_the_answer_alone():
    g = _graph()
    with Session() as s:
        off = s.fused_executor(g, PARAMS).run().final
    with Session() as s, tracing.recording():   # built while recording
        on = s.fused_executor(g, PARAMS).run().final
        disc = s.fused_executor(g, PARAMS, replay=False).run().final
    assert np.array_equal(off, on)
    assert np.array_equal(off, disc)


def _scoped_ops(hlo: str, innermost: bool = False) -> dict:
    """``edt.*`` scope -> opcodes of the HLO instructions under it: the
    outermost ``edt.*`` scope of each op, or with ``innermost`` the
    innermost, as the benchmark's trace reduction attributes it."""
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        scopes = re.findall(r"edt\.\w+", name.group(1)) if name else []
        if m and scopes:
            scope = scopes[-1] if innermost else scopes[0]
            out.setdefault(scope, set()).add(m.group(1))
    return out


@pytest.mark.parametrize("replay", [True, False])
def test_compiled_sweep_names_its_scopes(replay):
    with Session() as s:
        ex = s.fused_executor(_graph(), PARAMS, replay=replay)
        with tracing.recording() as rec:
            compiled = ex.compiled()
        assert rec.counters == {"sweep.builds": 1}
        ops = _scoped_ops(compiled.as_text())
        assert compiled.memory_analysis() is not None
        # the tile body's gathers and grid scatter, the counters' decrement
        assert {"gather", "scatter"} <= ops["edt.body"]
        assert ops["edt.decrement"]
        assert ops["edt.frontier"]
        if replay:
            assert "scatter" in ops["edt.decrement"]
            assert ops["edt.validate"]
        else:
            assert "edt.validate" not in ops
        ex.run()                  # the compiled sweep is the one run calls
        assert ex._sweep() is ex._sweep()


def _seidel_graph(tile=(2, 3, 4)):
    return TiledTaskGraph(PROGRAMS["seidel2d"](), {"S": Tiling(tile)},
                          backend="numpy")


def test_wavefront_body_counts_its_waves():
    """A 2-D Gauss-Seidel solve counts its in-tile waves, the domain
    points they computed and the point lanes they sliced; the numbers
    follow from the tile and the graph's depth and widest level."""
    import itertools

    tile = (2, 3, 4)
    pts = list(itertools.product(*map(range, tile)))
    width = max(sum(1 for p in pts if sum(p) == c)
                for c in range(sum(tile)))
    waves = sum(tile) - len(tile) + 1
    with Session() as s, tracing.recording():
        with tracing.request(0) as rec:
            ex = s.fused_executor(_seidel_graph(tile), PARAMS)
            run = ex.run()
    depth, w_pad = run.counters.depth, run.counters.max_in_flight
    assert (waves, width) == (7, 6)
    c = rec.counters
    assert c["sweep.body_waves"] == depth * waves
    assert c["sweep.wave_points"] == PARAMS["T"] * PARAMS["N"] ** 2
    assert c["sweep.wave_lanes"] == depth * w_pad * width * waves
    assert 0 < c["sweep.wave_points"] < c["sweep.wave_lanes"]
    with Session() as s, tracing.recording():
        with tracing.request(0) as jac:
            s.fused_executor(_graph(), PARAMS).run()
    assert not any(k.startswith("sweep.wave") or k == "sweep.body_waves"
                   for k in jac.counters)


def test_compiled_wavefront_body_names_its_wave_scope():
    """The ops that pick a wave's points sit in ``edt.wave``; the wave's
    gathers and scatter stay in ``edt.body``; a Jacobi body has no
    ``edt.wave``."""
    with Session() as s:
        ops = _scoped_ops(s.fused_executor(_seidel_graph(), PARAMS)
                          .compiled().as_text(), innermost=True)
        jacobi = _scoped_ops(s.fused_executor(_graph(), PARAMS)
                             .compiled().as_text(), innermost=True)
    # the wave table's row, and the sites' and flat indices' arithmetic
    assert {"dynamic-slice", "add", "compare"} <= ops["edt.wave"]
    assert {"gather", "scatter"} <= ops["edt.body"]
    assert not {"gather", "scatter"} & ops["edt.wave"]
    assert "edt.wave" not in jacobi


def test_compiled_refuses_an_empty_graph():
    with Session() as s:
        ex = s.fused_executor(_graph(), {"T": 0, "N": 4})
        with pytest.raises(ValueError):
            ex.compiled()


# ------------------------------------------------- the benchmark's recorder
def test_record_cell_pairs_requests_on_and_off():
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.chip import harness
    from benchmarks.record_cell import record

    cell = harness.load_cell("jacobi2d-fine-medium.warm")
    cell.config.update(N=12, TSTEPS=2, shards=0)
    cell.mix["warmup_seconds"] = 1e-3
    out = record(cell, 2**33 + 1, 0.5, log=lambda m: None)
    assert out["correct"], out["checks"]
    assert out["requests"]["on"] >= 1
    assert out["requests"]["off"] in (out["requests"]["on"],
                                      out["requests"]["on"] - 1)
    # the set-up request built the sweep; the window's requests reuse it
    assert set(SWEEP_SPANS[1:]) <= set(out["spans_s"])
    assert "build" not in out["spans_s"]
    c = out["counters"]
    assert out["lane_occupancy_pct"] == pytest.approx(
        100 * c["sweep.tasks"] / c["sweep.task_lanes"])
    assert out["sweep_code_bytes"] is not None


def test_record_cell_reports_wave_occupancy():
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.chip import harness
    from benchmarks.record_cell import record

    cell = harness.load_cell("seidel2d-large.warm")
    cell.config.update(N=20, TSTEPS=3, shards=0, tile=[2, 8, 8])
    cell.mix["warmup_seconds"] = 1e-3
    out = record(cell, 2**33 + 2, 0.3, log=lambda m: None)
    assert out["correct"], out["checks"]
    c = out["counters"]
    assert out["requests"]["on"] >= 1
    assert c["sweep.wave_points"] == 3 * 20 ** 2     # T · N², per request
    assert out["wave_occupancy_pct"] == pytest.approx(
        100 * c["sweep.wave_points"] / c["sweep.wave_lanes"])
