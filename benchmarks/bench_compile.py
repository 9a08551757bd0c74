"""Paper §5.1 / Figure 6: compile-time of compression vs projection.

For every program in the suite, computes each dependence's inter-tile
relation twice — with the paper's compression+inflation method and with the
prior-art lifted Fourier-Motzkin projection — and reports the speedup.
A per-dependence timeout marks projection blowups (the paper's two
timed-out benchmarks).
"""
from __future__ import annotations

import time

from repro.core.edt.shard import process_context
from repro.core.poly import (Tiling, tile_dependence,
                             tile_dependence_projection)
from repro.core.programs import PROGRAMS

TIMEOUT_S = 120.0

# smoke mode: small, projection-friendly programs run in-process with no
# subprocess or 120 s timeout — a sub-second sanity pass over the section.
SMOKE_SUITE = [
    ("stencil1d", (32, 32)),
    ("diamond", (8, 8)),
]

SUITE = [
    # (program, tile sizes per statement-dim)
    ("stencil1d", (32, 32)),
    ("seidel1d", (16, 16)),
    ("jacobi2d", (8, 8, 8)),
    ("heat3d", (4, 4, 4, 4)),
    ("matmul", (16, 16, 16)),
    ("trisolv", (16, 16)),
    ("lu_like", (8, 8, 8)),
    ("diamond", (8, 8)),
    ("pipeline", (4, 1)),
    ("synthetic5d", (4,) * 5),
    ("synthetic6d", (4,) * 6),
]


def _proj_worker(q, name, dep_idx, tiles):
    prog = PROGRAMS[name]()
    dep = prog.dependences[dep_idx]
    g = Tiling(tuple(tiles))
    t0 = time.perf_counter()
    tile_dependence_projection(dep.delta, dep.src_ndim, g, g)
    q.put(time.perf_counter() - t0)


def _timed_projection(name, dep_idx, tiles) -> tuple[float, bool]:
    """FM projection in a subprocess with a hard kill at TIMEOUT_S.

    Exact Fourier-Motzkin can blow up doubly-exponentially — the paper's own
    experiments had two such timeouts; a hard kill is the honest metric."""
    ctx = process_context()
    q = ctx.Queue()
    p = ctx.Process(target=_proj_worker, args=(q, name, dep_idx, tiles))
    p.start()
    p.join(TIMEOUT_S)
    if p.is_alive():
        p.terminate()
        p.join()
        return TIMEOUT_S, True
    return q.get(), False


def run(emit=print, smoke: bool = False):
    suite = SMOKE_SUITE if smoke else SUITE
    emit("name,deps,t_compression_ms,t_projection_ms,speedup,note")
    speedups = []
    for name, tiles in suite:
        prog = PROGRAMS[name]()
        g = Tiling(tuple(tiles))
        t_c = t_p = 0.0
        note = ""
        for i, dep in enumerate(prog.dependences):
            t0 = time.perf_counter()
            tile_dependence(dep.delta, dep.src_ndim, g, g, method="inflate")
            t_c += time.perf_counter() - t0
            if smoke:
                t0 = time.perf_counter()
                tile_dependence_projection(dep.delta, dep.src_ndim, g, g)
                dt, timed_out = time.perf_counter() - t0, False
            else:
                dt, timed_out = _timed_projection(name, i, tiles)
            t_p += dt
            if timed_out:
                note = "projection-TIMEOUT(capped)"
        sp = t_p / max(t_c, 1e-9)
        speedups.append(sp)
        emit(f"{name},{len(prog.dependences)},{t_c*1e3:.2f},{t_p*1e3:.2f},"
             f"{sp:.2f},{note}", flush=True)
    geo = 1.0
    for s in speedups:
        geo *= s
    geo **= 1.0 / len(speedups)
    emit(f"# geomean speedup: {geo:.2f}x over {len(speedups)} programs "
         f"(timeouts capped at {TIMEOUT_S:.0f}s)")
    return speedups
