"""Run one chip-benchmark cell with the program's own spans and counters.

    python3 benchmarks/record_cell.py --workload <cell> --seed <n> \\
        --seconds <s> [--record on|off|alternate] [--trace <dir>]

From the root of a checkout, on a TPU (it exits 1 without one).  Set-up,
warm-up and the window's requests are the benchmark's own
(``benchmarks.chip.harness``: the same ``Runner``, traffic and check);
each window request runs inside ``repro.core.edt.tracing.request``.  With
``--record alternate`` (the default) every other request records, so a
warm window pairs requests with recording on and off; with ``on`` or
``off`` every request does or none does (a cold cell's requests differ in
size, so its pairs are two runs of one seed).  ``--trace <dir>`` also
profiles the window (use it with ``on``).

Prints one JSON line: ``correct``, each request's seconds and whether it
recorded, the mean seconds of the requests with recording on and off,
each span's mean host seconds per recorded request,
each counter per recorded request, the lane occupancy (``sweep.tasks`` /
``sweep.task_lanes``), for a wavefront tile body the wave occupancy
(``sweep.wave_points`` / ``sweep.wave_lanes``: points computed over the
point lanes its waves sliced), for a warm cell the sweep's generated code bytes
(``FusedExecutor.compiled()``, read after the window), and with a trace
the device seconds per solve of each ``edt.*`` scope and the idle gaps
named by ``bench:`` span, ``edt:`` span and JAX host event
(``benchmarks.chip.scopes``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import nullcontext
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def record(cell, seed: int, seconds: float, mode: str = "alternate",
           trace_dir=None, log=print) -> dict:
    """Set up, run ``seconds`` of the cell's traffic as described above,
    check, and return the result object."""
    import jax

    from benchmarks.chip import harness, scopes, traffic
    from repro.core.edt import tracing

    cold = cell.mix["cache"] == "cold"
    if cold:
        harness.cold_compile_cache()
    rows = []
    with harness.Monitor() as monitor:
        runner = harness.Runner(cell, monitor)
        try:
            harness.set_up(cell, runner, seed)
            harness.warm_up(cell, runner, seed, log)
            if trace_dir is not None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
            t0 = time.perf_counter()
            with harness.span("window"):
                for k, req in enumerate(traffic.requests(cell.mix,
                                                         cell.params, seed)):
                    if time.perf_counter() - t0 >= seconds:
                        break
                    state = traffic.initial_grid(seed, req.index,
                                                 cell.grid(req.params))
                    on = mode == "on" or (mode == "alternate"
                                          and k % 2 == 0)
                    with (tracing.recording() if on else nullcontext()), \
                            tracing.request(req.index) as rec:
                        r = runner.request(req.index, req.params, state,
                                           cold)
                    rows.append((r, rec, on))
            if trace_dir is not None:
                jax.profiler.stop_trace()
            code = None
            if not cold:
                ex = runner.session.fused_executor(
                    runner.graph, cell.params, replay=True,
                    validate=bool(cell.config["validate"]),
                    dtype=cell.config["dtype"])
                code = ex.compiled().memory_analysis()
                code = int(code.generated_code_size_in_bytes)
        finally:
            runner.close()
    checks = harness.check(cell, [r for r, _, _ in rows], 0)
    recorded = [rec for _, rec, on in rows if on]
    spans, counters = {}, {}
    for rec in recorded:
        for s in rec.spans:
            spans[s.name] = spans.get(s.name, 0.0) + s.seconds
        for name, v in rec.counters.items():
            counters[name] = counters.get(name, 0) + v
    n = max(len(recorded), 1)
    out = {
        "cell": cell.name,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "requests": {"on": len(recorded), "off": len(rows) - len(recorded)},
        "solves": [[r.index, r.params["N"], on, r.seconds]
                   for r, _, on in rows],
        "solve_s": {side: _mean([r.seconds for r, _, on in rows
                                 if on == want])
                    for side, want in (("on", True), ("off", False))},
        "spans_s": {k: v / n for k, v in spans.items()},
        "counters": {k: v / n for k, v in counters.items()},
        "lane_occupancy_pct": (100.0 * counters["sweep.tasks"]
                               / counters["sweep.task_lanes"]
                               if counters.get("sweep.task_lanes") else None),
        "wave_occupancy_pct": (100.0 * counters["sweep.wave_points"]
                               / counters["sweep.wave_lanes"]
                               if counters.get("sweep.wave_lanes") else None),
        "sweep_code_bytes": code,
    }
    if trace_dir is not None:
        from benchmarks.chip import trace

        path = trace.find_xplane(trace_dir)
        dev, host = trace.read_xplane(path)
        summary = scopes.reduce_scopes(
            dev, host, scopes.op_scopes(path.read_bytes()))
        out.update(
            busy_s=summary.busy_s, window_s=summary.window_s,
            scopes_per_solve_s={k: v / len(rows)
                                for k, v in summary.scopes.items()},
            idle_gaps=scopes.idle_gaps(dev, host)[:16])
    out["checks"] = checks
    return out


def _mean(values):
    return sum(values) / len(values) if values else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", choices=("on", "off", "alternate"),
                    default="alternate")
    ap.add_argument("--trace", type=Path, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness
    from repro.launch.compile_cache import use_compile_cache

    cell = harness.load_cell(args.workload)
    use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.default_backend() != "tpu":
        print("record_cell.py: needs a TPU; JAX finds "
              f"{jax.default_backend()}", file=sys.stderr)
        return 1
    out = record(cell, args.seed, args.seconds, args.record, args.trace,
                 log=lambda m: print(m, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
