"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only SECTION] [--smoke]
                                            [--json PATH]

Sections:
  compile   — §5.1 Fig 6: compression vs projection dependence-compute time
  taskgen   — task-generation throughput: fraction vs compiled vs numpy
              scanning backends on materialize / index_graph / pred_count /
              roots (graphs verified identical), plus sharded rows
              (``shards=2/4`` through the process-pool engine) and the
              ≥1M-task shard-scale curve
  sync      — §2 Table 2: overhead counters per synchronization model
  executor  — §5.2: makespan comparison across models (+ threaded autodec)
  faults    — recovery overhead: fault-free vs one recoverable injected
              worker crash at 2/4 shards, recovered arrays verified
              byte-identical (docs/robustness.md)
  service   — graph-cache serving: cold fill vs warm hit per product kind
              (incl. the ≥1M-task flagship, sub-ms warm target), plus
              ScheduleService coalescing and warm throughput
              (docs/service.md)
  fused     — fused stencil execution: the counted sweep computing real
              tiles, priced per task / per grid point against the
              decrement-only sweep, the host-dispatch NumPy twin, and
              the handwritten jax solve (docs/device_exec.md, "Fused
              execution")
  distributed — rank-partitioned counted-sync execution: per-rank task
              rate and cross-rank message volume on the ≥1M-task
              flagship, inline and process transports, frontiers
              verified byte-identical to the single-host sweep
              (docs/distributed.md)

``--smoke`` runs a fast subset of every section (small suites, no
subprocess projection timeouts) — a correctness-and-entry-point check that
finishes in well under a minute; full runs remain the default.

``--json PATH`` writes a machine-readable result file so CI can upload and
diff perf artifacts across PRs.  Stable schema (version 8):

    {"schema_version": 8, "smoke": bool, "host": {"cpus": int},
     "sections": {name: {"ok": bool, "seconds": float, "data": ...}}}

where ``data`` is the section's own return value (e.g. taskgen emits
``{"rows": [{"program", "backend", "shards", "tasks_per_s", ...}],
"geomean": ..., "shard_scale": [...]}``) and MUST be JSON-serializable:
a section returning anything ``json.dumps`` rejects is recorded with
``ok = False`` and an ``unserializable`` error entry, and the harness
exits non-zero.  (Through v7 such data was silently downgraded to
``repr(...)``, which is how the ``sync`` section shipped opaque for five
schema versions.)  Sharded rows record their shard count in ``shards``;
single-process rows carry ``shards = 1``.

New in v3: the ``executor`` section returns structured data instead of a
repr — ``{"models": [...], "dispatch": [...]}`` where each ``dispatch``
row prices driving one synthesized wavefront schedule through a host or
device path (``path`` in {host, device_replay, device_discover}) with
``seconds`` / ``per_task_us`` / ``verified`` fields, so the artifact
tracks host-vs-device dispatch cost per task across PRs.

New in v4: the ``faults`` section prices the robustness layer — rows
``{shards, fault, clean_s, faulty_s, overhead_ratio, verified}`` compare
fault-free sharded materialization against a run recovering from one
injected worker crash (retry + backoff, byte-identity verified), so the
artifact tracks the recovery tax across PRs.

New in v5: the ``service`` section prices the parametric graph cache —
rows ``{case, kind, cold_ms, warm_ms, speedup, sub_ms_warm, verified}``
per product kind (index graph / schedule / packed device columns), a
``flagship`` row for the ≥1M-task jacobi2d instance (acceptance: warm
hit < 1 ms, ≥50x over cold, arrays verified against an uncached oracle),
and ``service`` stats from a concurrent ScheduleService burst
(cold fills, coalesced requests, warm requests/s, hit rate).

New in v6: the ``fused`` section prices end-to-end device-resident
stencil execution — rows ``{program, path, tasks, points, seconds,
per_task_us, per_point_ns, vs_handwritten, verified}`` per execution path
(``path`` in {handwritten, device_replay, fused, fused_novalidate,
host_dispatch}), numerics verified against the handwritten solve, plus an
``acceptance`` record for the ≥1M-task flagship asserting the fused
per-task time does not exceed the decrement-only sweep.

New in v7: the ``distributed`` section prices the rank-partitioned
runtime — rows ``{program, tasks, ranks, engine, transport, seconds,
per_task_us, msgs, batches, cross_frac, attempts, per_rank, verified}``
where ``per_rank`` breaks out each rank's task count, message traffic and
µs/task, and every row's merged frontiers are verified byte-identical to
the single-host sweep before it is recorded.

New in v8: the ``sync`` section is the Table-2 overhead atlas
(docs/sync_atlas.md) — ``{rows, fits, growth, crossover, ...}`` where
``rows`` are per-(program, model, size, grain) counter measurements over
the atlas workloads, ``fits`` assert each counter's fitted asymptotic
class {1, r, n, e, n^2} against the paper's Table-2 bound, ``growth``
reports lo->hi growth factors with measured task/edge/width ratios, and
``crossover`` prices the counted model through the host simulator, the
device replay sweep, and a two-rank distributed run.  Unserializable
section data now fails the harness instead of degrading to ``repr``.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

SCHEMA_VERSION = 8


def encode_section_data(data):
    """Validate section data for the JSON report.

    Returns ``(ok, data)``: the data unchanged when ``json.dumps`` accepts
    it, else ``(False, {"unserializable": ...})`` describing the failure —
    never a silent ``repr`` downgrade (the bug that shipped the ``sync``
    section as an opaque string from schema v2 through v7).
    """
    try:
        json.dumps(data)
    except (TypeError, ValueError) as e:
        return False, {"unserializable": repr(e), "type": type(data).__name__}
    return True, data


def section_registry() -> dict:
    """Name -> run function for every benchmark section (import on call)."""
    from . import (bench_compile, bench_distributed, bench_executor,
                   bench_faults, bench_fused, bench_service,
                   bench_sync_overheads, bench_taskgen)

    return {
        "compile": bench_compile.run,
        "taskgen": bench_taskgen.run,
        "sync": bench_sync_overheads.run,
        "executor": bench_executor.run,
        "faults": bench_faults.run,
        "service": bench_service.run,
        "fused": bench_fused.run,
        "distributed": bench_distributed.run,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "compile", "taskgen", "sync", "executor",
                             "faults", "service", "fused", "distributed"])
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset of each section (sub-minute total)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable results to PATH")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sections = section_registry()
    if args.only:
        sections = {args.only: sections[args.only]}
    rc = 0
    report = {"schema_version": SCHEMA_VERSION, "smoke": bool(args.smoke),
              "host": {"cpus": os.cpu_count()}, "sections": {}}
    for name, fn in sections.items():
        print(f"\n===== bench:{name} =====", flush=True)
        t0 = time.time()
        kw = {}
        if args.smoke and "smoke" in inspect.signature(fn).parameters:
            kw["smoke"] = True
        ok, data = True, None
        try:
            data = fn(**kw)
        except Exception as e:  # noqa: BLE001 — report and continue
            print(f"# section {name} failed: {e!r}")
            ok = False
            data = repr(e)
            rc = 1
        dt = time.time() - t0
        if ok:
            ok, data = encode_section_data(data)
            if not ok:
                print(f"# section {name} returned unserializable data: "
                      f"{data['unserializable']}")
                rc = 1
        report["sections"][name] = {"ok": ok, "seconds": round(dt, 3),
                                    "data": data}
        print(f"# bench:{name} took {dt:.1f}s", flush=True)
    from repro.core.edt.shard import stop_process_servers
    stop_process_servers()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
