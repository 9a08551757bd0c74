"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout.  Loads, warms up, measures ``--seconds`` of
the cell's traffic and prints one JSON line last: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``checks`` (each number compared, beside its limit).
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result; without the program's ``src/`` beside it, 2; when the
window's traffic is not what the cell claims, 3.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
#: JAX's persistent compile cache: a fixed directory inside the checkout.
CACHE_DIR = ROOT / ".jax_cache"
#: The last traced window's profile (overwritten by the next traced run).
TRACE_DIR = ROOT / "benchmarks" / "chip" / ".trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness, roofline

    try:
        cell = harness.load_cell(args.workload)
    except (FileNotFoundError, KeyError, ValueError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < cell.chips:
        print(f"run.py: the cell needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {jax.default_backend()} device(s)",
              file=sys.stderr)
        return 1
    roofline.peaks(devices[0].device_kind)     # an unknown kind is an error
    trace_dir = None
    if args.trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    log = (lambda msg: print(msg, flush=True))
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, trace_dir,
                                  devices[0], T_START, log)
    except harness.TrafficViolation as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
