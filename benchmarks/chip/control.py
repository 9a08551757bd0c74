"""Readings that a cell's limit on ``max_rel_err`` is set from.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 13,14,15 --requests 3

One process sets the cell up as a run does, then for each seed makes the
first ``--requests`` requests of that seed's traffic through the timed
entry: for ``--seeds`` as the configuration states it (float32: the lower
reading), for ``--control-seeds`` with the program's own bfloat16 path
switched on (the control: the upper reading).  Each request's grid is
compared with the float64 reference as a run compares it.  Prints one JSON
line per seed.  Needs the chip, as a run does.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def readings(cell, seeds, control_seeds, n_requests, log=print):
    import jax.numpy as jnp

    from benchmarks.chip import harness, traffic
    from benchmarks.chip.reference import rel_err

    cold = cell.mix["cache"] == "cold"
    if cold:
        harness.cold_compile_cache()
    with harness.Monitor() as monitor:
        runner = harness.Runner(cell, monitor)
        try:
            harness.set_up(cell, runner, 0)
            runs = ([(s, "program", None) for s in seeds]
                    + [(s, "control", jnp.bfloat16) for s in control_seeds])
            for seed, label, dtype in runs:
                worst = 0.0
                for req in itertools.islice(
                        traffic.requests(cell.mix, cell.params, seed),
                        n_requests):
                    state = traffic.initial_grid(seed, req.index,
                                                 cell.grid(req.params))
                    rec = runner.request(req.index, req.params, state,
                                         cold, dtype=dtype)
                    want = cell.solve(state, req.params["T"])
                    worst = max(worst, rel_err(rec.final, want))
                log(json.dumps({"seed": seed, label: worst}))
        finally:
            runner.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--requests", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from benchmarks.chip import harness

    if jax.default_backend() != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 1
    cell = harness.load_cell(args.workload)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    readings(cell, [int(s) for s in args.seeds.split(",")],
             [int(s) for s in args.control_seeds.split(",")], args.requests,
             log=lambda m: print(m, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
