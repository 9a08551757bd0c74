"""Golden snapshots of the §4 generated-code emitters.

``core/edt/codegen.py`` renders the paper's Figures 3/4/5 as pseudo-C;
until now nothing covered it, so a refactor of ``LoopNest.pretty_loops``
(or of the counting-strategy heuristic the autodec emitter reports) could
silently change every emitted form.  These tests pin the full output for
two shapes — the dense diamond grid (enumerator-strategy counters) and the
skewed Jacobi-1D stencil with a non-unit tiling (loop-strategy counters,
``ceild``/``floord`` bounds with real divisors).

The snapshots live in ``tests/golden/codegen_<program>.txt``.  On an
*intentional* emitter change, regenerate them with

    REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest tests/test_codegen_golden.py

and review the diff like any other code change.
"""
from __future__ import annotations

import os
import pathlib

import pytest

from repro.core.edt import TiledTaskGraph
from repro.core.edt.codegen import (emit_autodec, emit_fused,
                                    emit_prescribed, emit_tags)
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS
from repro.kernels.stencils import SPECS

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
CASES = {"diamond": (1, 1), "stencil1d": (2, 4)}


def _render(name: str) -> str:
    g = TiledTaskGraph(PROGRAMS[name](), {"S": Tiling(CASES[name])})
    parts = [
        emit_prescribed(g), "",
        emit_tags(g, method=2), "",
        emit_tags(g, method=1), "",
        emit_autodec(g), "",
    ]
    if name in SPECS:   # fused form exists only for programs with a body
        parts += [emit_fused(g), ""]
    return "\n".join(parts)


@pytest.mark.parametrize("name", sorted(CASES))
def test_codegen_matches_golden(name):
    path = GOLDEN_DIR / f"codegen_{name}.txt"
    text = _render(name)
    if os.environ.get("REGEN_GOLDEN"):
        path.write_text(text)
    golden = path.read_text()
    assert text == golden, (
        f"emitted pseudo-C for {name!r} drifted from {path}; if the change "
        f"is intentional, regenerate with REGEN_GOLDEN=1 and review the diff")


@pytest.mark.parametrize("name", sorted(CASES))
def test_codegen_is_deterministic(name):
    """Two independent graph builds emit byte-identical code (no dict-order
    or cache-state leakage into the rendered loops)."""
    assert _render(name) == _render(name)


def test_autodec_reports_both_strategies():
    """The golden pair intentionally spans both §4.3 counting strategies."""
    d = TiledTaskGraph(PROGRAMS["diamond"](), {"S": Tiling(CASES["diamond"])})
    s = TiledTaskGraph(PROGRAMS["stencil1d"](),
                       {"S": Tiling(CASES["stencil1d"])})
    assert set(d.pred_count_strategies().values()) == {"enumerator"}
    assert set(s.pred_count_strategies().values()) == {"loop"}
    assert "closed_form" in emit_autodec(d)
    assert "n++;" in emit_autodec(s)


def test_fused_emitter_requires_a_body():
    """Programs with no registered stencil body have no fused form."""
    d = TiledTaskGraph(PROGRAMS["diamond"](), {"S": Tiling(CASES["diamond"])})
    with pytest.raises(ValueError, match="no stencil body"):
        emit_fused(d)


def test_fused_emitter_reflects_the_spec():
    """Sequential dims render as loops, parallel dims as vmap, and every
    tap of the body appears with its parity buffer."""
    s = TiledTaskGraph(PROGRAMS["seidel1d"](),
                       {"S": Tiling(CASES["stencil1d"])})
    text = emit_fused(s)
    assert "Gauss-Seidel dim: sequential" in text
    assert text.count("acc +=") == len(SPECS["seidel1d"].taps)
    assert "u[p, s + (-1,)]" in text      # dt=0 tap reads the same parity
    assert "u[1-p, s + (1,)]" in text     # dt=1 tap reads the other parity


def test_fused_emitter_renders_the_wave_loop():
    """A wavefront body renders its in-tile wave loop and its own site
    map, and no per-dimension Gauss-Seidel loops."""
    s = TiledTaskGraph(PROGRAMS["seidel2d"](), {"S": Tiling((2, 3, 4))})
    text = emit_fused(s)
    assert "for c in range(7):" in text          # 2 + 3 + 4 - 2 waves
    assert "tt + l0 + l1 = c" in text
    assert "s0 = x0 - t " in text and "s1 = x1 - t - x0 " in text
    assert "Gauss-Seidel dim" not in text
    assert text.count("acc +=") == len(SPECS["seidel2d"].taps)
