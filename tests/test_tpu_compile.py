"""The device sweeps compile for a TPU v5e chip — without the chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  Each test
builds a sweep's jitted program and compiles it for one v5e chip, so a
program the chip's compiler refuses fails here, at no chip time.  Nothing
runs: results and times need the chip (``chip_smoke.py``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.  Keep every chip compile in this one file.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.edt import (DeviceExecutor, FusedExecutor, TiledTaskGraph,
                            synthesize_indexed)
from repro.core.edt.device import make_pallas_step
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

TILE = (2, 2, 2)
#: Sizes that compile for v5e in a few seconds per sweep: the replay
#: sweeps at 33,800 tasks (depth 150), the discover sweeps, whose compile
#: time grows faster with the graph, at 4,356 tasks (depth 74).
PARAMS = {"replay": {"T": 16, "N": 128}, "discover": {"T": 8, "N": 64}}
#: The 2-D Gauss-Seidel replay sweep at its benchmark tile: 128 in-tile
#: waves of up to 127 points, in a loop whatever the grid.
SEIDEL_TILE, SEIDEL_PARAMS = (2, 64, 64), {"T": 4, "N": 256}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def jacobi():
    g = TiledTaskGraph(PROGRAMS["jacobi2d"](), {"S": Tiling(TILE)},
                       backend="numpy")
    return {mode: synthesize_indexed(g, params)
            for mode, params in PARAMS.items()}


@pytest.fixture(scope="module")
def seidel():
    g = TiledTaskGraph(PROGRAMS["seidel2d"](), {"S": Tiling(SEIDEL_TILE)},
                       backend="numpy")
    return synthesize_indexed(g, SEIDEL_PARAMS)


def _spec(one_chip, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=one_chip)


def _discover_state(one_chip, n, extra=()):
    """``(indeg, frontier, level, level_of, started, max_width, *extra)``."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    return ((s((n,), "int32"), s((n,), "bool"), s((), "int32"),
             s((n,), "int32"), s((), "int32"), s((), "int32"))
            + tuple(s(shape, dt) for shape, dt in extra))


def _lowered(kind, ig, sched, one_chip):
    n = ig.n
    replay = kind.endswith("replay")
    if kind == "seidel2d-replay":
        ex = FusedExecutor(ig, SEIDEL_PARAMS, body="seidel2d",
                           tile=SEIDEL_TILE, schedule=sched)
        return ex._build_replay().lower(
            _spec(one_chip, (n + 1,), "int32"),
            _spec(one_chip, (2 * ex.size + 1,), "float32"))
    if kind.startswith("fused"):
        params = PARAMS["replay" if replay else "discover"]
        ex = FusedExecutor(ig, params, body="jacobi2d", tile=TILE,
                           schedule=sched if replay else None)
        u = ((2 * ex.size + 1,), "float32")
        if replay:
            return ex._build_replay().lower(
                _spec(one_chip, (n + 1,), "int32"), _spec(one_chip, *u))
        return ex._build_discover().lower(
            _discover_state(one_chip, n, extra=(u,)))
    if kind == "device-replay":
        ex = DeviceExecutor(ig, schedule=sched)
        return ex._build_replay().lower(_spec(one_chip, (n + 1,), "int32"))
    ex = DeviceExecutor(ig)
    return ex._build_discover().lower(_discover_state(one_chip, n))


@pytest.mark.parametrize("kind", ["fused-replay", "fused-discover",
                                  "device-replay", "device-discover",
                                  "seidel2d-replay"])
def test_sweep_compiles_for_v5e(kind, request, one_chip, no_cache):
    if kind.startswith("seidel2d"):
        ig, sched = request.getfixturevalue("seidel")
    else:
        ig, sched = request.getfixturevalue("jacobi")[
            "replay" if kind.endswith("replay") else "discover"]
    compiled = _lowered(kind, ig, sched, one_chip).compile()
    mem = compiled.memory_analysis()
    # one chip holds 16 GB; these sweeps need a small fraction of it
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < 2**30


def test_pallas_kernel_is_refused_by_mosaic(one_chip, no_cache):
    """The compiled pallas decrement does not lower for the chip: Mosaic
    rejects its vector gather.  This is what ``make_pallas_step`` reports
    at construction off the CPU; if a later JAX lowers the kernel, this
    test fails and the refusal can go."""
    import jax

    n, e = 4096, 16384
    step = make_pallas_step(n, e, interpret=False)
    args = (_spec(one_chip, (n,), "int32"), _spec(one_chip, (n,), "bool"),
            _spec(one_chip, (e,), "int32"), _spec(one_chip, (n + 1,), "int32"))
    with pytest.raises(Exception, match="Only 2D gather is supported"):
        jax.jit(step).lower(*args).compile()


def test_pallas_step_fails_at_construction_off_cpu(monkeypatch):
    """On an accelerator backend the compiled kernel is refused when the
    step is built, naming Mosaic's refusal — never a mid-run compiler
    error and never a silent fall back to interpret mode."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        make_pallas_step(16, 32)
    ig, _ = synthesize_indexed(
        TiledTaskGraph(PROGRAMS["jacobi2d"](), {"S": Tiling(TILE)},
                       backend="numpy"), {"T": 4, "N": 8})
    with pytest.raises(NotImplementedError, match="Mosaic"):
        DeviceExecutor(ig, use_pallas=True)
    with pytest.raises(NotImplementedError, match="Mosaic"):
        FusedExecutor(ig, {"T": 4, "N": 8}, body="jacobi2d", tile=TILE,
                      use_pallas=True)
    # an explicit interpret=True is the caller saying so: allowed
    make_pallas_step(16, 32, interpret=True)
