"""Bring-up smoke run of the EDT main path on one TPU chip.

    python chip_smoke.py

One process drives Session -> GraphCache cold fill (sharded process pool)
-> fused device sweep, then the decrement-only replay, both discover
sweeps, a PolyBench-sized coarse-tile replay, the other stencil bodies and
a cold request made after the chip is in use.  Every phase is checked
against the NumPy reference; a mismatch raises and the script exits
non-zero.  The last line of standard output is one JSON object naming the
device.  Without a TPU it exits non-zero and prints no result.

The seconds and bytes it prints describe this run only; they are not
benchmark measurements.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: The flagship fine-grain graph: 1,056,784 tasks, depth 558.
FLAGSHIP = ("jacobi2d", (2, 2, 2), {"T": 32, "N": 512})
#: Discover sweeps are O(depth * (V + E)): a moderate size.
DISCOVER = ("jacobi2d", (2, 2, 2), {"T": 16, "N": 128})
#: PolyBench/C 4.2 jacobi-2d LARGE (N=1300, TSTEPS=500) on this repo's
#: 9-point body, with a coarse tile.
COARSE = ("jacobi2d", (8, 64, 64), {"T": 500, "N": 1300})
#: The other stencil bodies, once each at a moderate size.
BODIES = (
    ("heat3d", (2, 4, 4, 4), {"T": 8, "N": 32}),
    ("stencil1d", (4, 64), {"T": 64, "N": 2048}),
    ("seidel1d", (2, 8), {"T": 32, "N": 256}),
)
#: A cold request after the chip is in use: N differs from every earlier
#: jacobi2d (2,2,2) request, so the cache cannot stitch it incrementally.
SECOND_COLD = ("jacobi2d", (2, 2, 2), {"T": 24, "N": 384})

#: float32 accumulation drift against the time-major NumPy reference
#: (the tolerance benchmarks/bench_fused.py holds the fused sweep to).
TOL = dict(rtol=1e-4, atol=1e-5)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def same_levels(a, b) -> bool:
    import numpy as np

    return len(a) == len(b) and all(np.array_equal(x, y)
                                    for x, y in zip(a, b))


def check_grid(got, spec, state, steps, what: str) -> None:
    import numpy as np

    from repro.kernels.stencils import reference_solve

    want = reference_solve(spec, state, steps)
    check(np.all(np.isfinite(got)), f"{what}: non-finite values")
    check(got.shape == want.shape, f"{what}: shape {got.shape}")
    err = float(np.max(np.abs(got - want)))
    check(np.allclose(got, want, **TOL), f"{what}: max |err| {err}")
    say(f"  {what}: grid {got.shape} matches reference_solve "
        f"(max |err| {err:.3g})")


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def child_processes() -> list:
    """Process ids whose parent is this process, from any of its threads."""
    task = f"/proc/{os.getpid()}/task"
    kids = []
    for tid in os.listdir(task):
        with open(f"{task}/{tid}/children") as f:
            kids += f.read().split()
    return kids


def flagship(session, graph, params, spec, device) -> None:
    """Cold fill, compile and two runs of the fused replay sweep."""
    import jax
    import numpy as np

    from repro.kernels.stencils import default_state

    t0 = time.perf_counter()
    session.fused_packed(graph, params)
    cold_s = time.perf_counter() - t0
    ig, sched = session.schedule(graph, params)
    say(f"  cold fill (scan + level + pack, 2 shards): {cold_s:.3f} s; "
        f"{ig.n} tasks, {ig.n_edges} edges, depth {sched.depth}")
    ex = session.fused_executor(graph, params, replay=True, validate=True,
                                dtype="float32")
    # compile ahead of the first run to read the executable's size; the
    # executor then runs the compiled sweep
    state = default_state(spec, params["N"], "float32")
    indeg0 = jax.ShapeDtypeStruct((ex.dg.n + 1,), np.int32)
    u0 = jax.ShapeDtypeStruct((2 * ex.size + 1,), np.float32)
    t0 = time.perf_counter()
    compiled = ex._build_replay().lower(indeg0, u0).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    ex._replay_fn = compiled
    t0 = time.perf_counter()
    ex.run()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run = ex.run()
    warm_s = time.perf_counter() - t0
    say(f"  compile {compile_s:.3f} s; first run {first_s:.3f} s; "
        f"warm run {warm_s:.3f} s")
    say(f"  executable: generated code {mem.generated_code_size_in_bytes} B, "
        f"arguments {mem.argument_size_in_bytes} B, "
        f"temporaries {mem.temp_size_in_bytes} B; "
        f"peak_bytes_in_use {peak_bytes(device)}")
    check(same_levels(run.levels, sched.levels),
          "fused replay frontiers differ from the cached schedule")
    check(run.counters.tasks_started == ig.n, "fused replay task count")
    check_grid(run.final, spec, state, params["T"], "fused replay")


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    import jax

    say(f"jax {jax.__version__}")
    devices = jax.devices()
    say(f"devices: {devices}")
    say(f"device_kind: {devices[0].device_kind}")
    say(f"compile cache: {cache_dir}")
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1

    import numpy as np

    from repro.core.edt import (ExecutionConfig, Session, synthesize_indexed)
    from repro.core.edt.shard import process_context, stop_process_servers
    from repro.core.poly import Tiling
    from repro.core.programs import PROGRAMS
    from repro.kernels.stencils import SPECS, default_state

    device = devices[0]
    graphs = {}

    def graph_of(session, name, tile):
        if (name, tile) not in graphs:
            graphs[name, tile] = session.graph(PROGRAMS[name](),
                                               {"S": Tiling(tile)})
        return graphs[name, tile]

    try:
        with Session(ExecutionConfig(backend="numpy", shards=2)) as session:
            say(f"session: {session.config.resolve_shards()} shards, pool "
                f"start method {process_context().get_start_method()}")

            name, tile, params = FLAGSHIP
            say(f"[1] fused replay {name} tile {tile} {params}")
            g = graph_of(session, name, tile)
            flagship(session, g, params, SPECS[name], device)

            say("[2] Session.executor replay (validated on device)")
            t0 = time.perf_counter()
            run = session.executor(g, params, replay=True).run()
            _, sched = session.schedule(g, params)
            check(same_levels(run.levels, sched.levels),
                  "decrement-only replay frontiers differ")
            say(f"  {run.counters.tasks_started} tasks in "
                f"{time.perf_counter() - t0:.3f} s including compile")

            name, tile, params = DISCOVER
            say(f"[3] discover sweeps {name} tile {tile} {params}")
            g = graph_of(session, name, tile)
            _, oracle = synthesize_indexed(g, params)
            spec = SPECS[name]
            for label, ex in (
                    ("DeviceExecutor",
                     session.executor(g, params, replay=False)),
                    ("FusedExecutor", session.fused_executor(
                        g, params, replay=False, dtype="float32"))):
                t0 = time.perf_counter()
                run = ex.run()
                check(run.level_of.tobytes() == oracle.level_of.tobytes(),
                      f"{label} discover levels differ from "
                      f"synthesize_indexed")
                say(f"  {label} discover: {len(run.levels)} levels identical "
                    f"to synthesize_indexed ({time.perf_counter() - t0:.3f} s "
                    f"including compile)")
            check_grid(run.final, spec, default_state(spec, params["N"],
                                                      "float32"),
                       params["T"], "fused discover")

            for name, tile, params in (COARSE,) + BODIES:
                say(f"[4] fused replay {name} tile {tile} {params}")
                g = graph_of(session, name, tile)
                spec = SPECS[name]
                t0 = time.perf_counter()
                ex = session.fused_executor(g, params, replay=True,
                                            dtype="float32")
                fill_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                run = ex.run()
                run_s = time.perf_counter() - t0
                say(f"  {ex.dg.n} tasks, depth {len(run.levels)}: cold fill "
                    f"{fill_s:.3f} s, run {run_s:.3f} s including compile")
                check_grid(run.final, spec,
                           default_state(spec, params["N"], "float32"),
                           params["T"], name)

            name, tile, params = SECOND_COLD
            say(f"[5] cold request after the chip is in use: {name} {params}")
            g = graph_of(session, name, tile)
            info = session.cache.info()
            t0 = time.perf_counter()
            ig, sched = session.schedule(g, params)
            cold_s = time.perf_counter() - t0
            after = session.cache.info()
            check(after["misses"] > info["misses"],
                  "second request was not cold")
            check(after["incremental_hits"] == info["incremental_hits"],
                  "second request was stitched, not scanned")
            check(session.runtime_config().pool is not None,
                  "session has no sharded pool")
            ref = synthesize_indexed(g, params)[0]
            check(np.array_equal(ig.edge_src, ref.edge_src)
                  and np.array_equal(ig.edge_tgt, ref.edge_tgt),
                  "pool-scanned graph differs from the in-process scan")
            say(f"  {ig.n} tasks scanned on the sharded pool in "
                f"{cold_s:.3f} s, identical to the in-process scan")
            name, tile, params = FLAGSHIP
            hits = session.cache.info()["hits"]
            misses = session.cache.info()["misses"]
            session.fused_packed(graph_of(session, name, tile), params)
            info = session.cache.info()
            check(info["hits"] > hits and info["misses"] == misses,
                  "repeated flagship request was not a cache hit")
            say(f"  repeated flagship request: cache hit ({info})")
    finally:
        # the pool is shut down; its forkserver and resource tracker
        # would outlive this process
        stop_process_servers()
    left = child_processes()
    check(not left, f"child processes still running: {left}")
    say("no child process left running")

    say(f"peak_bytes_in_use {peak_bytes(device)}")
    entries = os.listdir(cache_dir) if os.path.isdir(cache_dir) else []
    say(f"compile cache: {len(entries)} entries in {cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
