"""One run of one cell: set-up, the measured window, the check.

The entry the window drives is the one users call: ``Session.graph`` (built
once, in set-up) -> ``Session.fused_executor(graph, params, replay=True)``
-> ``.run(state)``, with the grid read back on the host.  A cold request
first asks ``Session.index_graph`` (scan), ``Session.schedule`` (leveling)
and ``Session.fused_packed`` (packing and upload) for an instance the
process has not seen.  Each of those calls sits in a span of its own: a
host-clock reading, and a ``jax.profiler.TraceAnnotation`` named
``bench:<call>`` that shares the device trace's clock.

What decides ``correct`` (see :func:`check`): every window request's grid
against the plain reference that the configuration names (``"reference":
{"solve": "<module>:<function>", ...}``, a module file beside this one),
within the cell's limit from ``limits/<cell>.json``; every request's task
count; and for a cold cell, on a sample of its requests, the graph the
request itself scanned and leveled, against the configuration's
``"graph"`` reference (a cold cell whose configuration names none is
refused).
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
import traceback
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from . import roofline, traffic
from .reference import rel_err
from .trace import SPAN_PREFIX

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
#: Index of the set-up request's grid stream (window requests count from 0).
SETUP_INDEX = 2**31


class TrafficViolation(RuntimeError):
    """The window's traffic was not what the cell claims (a warm request
    missed the cache, a cold one hit it)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list
    limits: dict
    solve: Callable               # (initial grid, T) -> the reference grid
    graph_check: Optional[Callable] = None   # see reference.graph_check

    @property
    def params(self) -> dict:
        """The program's parameters: ``T`` sweeps (``TSTEPS`` times the
        sweeps each of the source's time steps makes) and ``N``."""
        cfg = self.config
        return {"T": int(cfg["TSTEPS"]) * int(cfg["sweeps_per_tstep"]),
                "N": int(cfg["N"])}

    def grid(self, params: dict) -> tuple:
        """The shape of a request's grid: ``N`` along each space dim."""
        return (int(params["N"]),) * (len(self.config["tile"]) - 1)


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json",
              bench_dir: Path = BENCH_DIR) -> Cell:
    """Find a cell, its configuration, its traffic mix, its metrics and
    its limits by the names ``BENCHMARK.json`` gives them."""
    bench = json.loads(Path(bench_file).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((Path(bench_file).parent
                         / configs[cell["config"]]["file"]).read_text())
    mix = traffic.load(cell["traffic"], bench_dir / "traffic")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [])
                 or ("workloads" not in m and m["moves"] in reported)]
    limits = json.loads((bench_dir / "limits" / f"{name}.json").read_text())
    ref = config.get("reference", {})
    if "solve" not in ref:
        raise ValueError(f"configuration {cell['config']!r} names no "
                         "reference solve")
    if mix["cache"] == "cold" and "graph" not in ref:
        raise ValueError(f"cold cell {name!r}: configuration "
                         f"{cell['config']!r} names no graph reference")
    return Cell(name, int(cell["chips"]), config, mix, e2e, per_layer,
                limits, load_function(ref["solve"], bench_dir),
                load_function(ref["graph"], bench_dir)
                if "graph" in ref else None)


def _load_module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_function(spec: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``"<module>:<function>"``: a function of ``<bench_dir>/<module>.py``."""
    module, _, func = spec.partition(":")
    return getattr(_load_module(bench_dir / f"{module}.py", "chip_ref_"),
                   func)


def load_reader(metric: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return _load_module(bench_dir / "metrics" / f"{metric}.py",
                        "chip_metric_").read


# ------------------------------------------------------------ observation
class Monitor:
    """JAX's own compile events, counted while registered.

    ``backend_compile_duration`` wraps the persistent cache's lookup too,
    so a load from the cache counts there and in ``cache_hits``; a compile
    is a backend event that was no hit."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = {self.COMPILE: 0.0, self.TRACE: 0.0, self.LOWER: 0.0}
        self.count = {self.COMPILE: 0, self.TRACE: 0, self.LOWER: 0,
                      self.HITS: 0}

    def _duration(self, event, duration, **_):
        if event in self.seconds:
            self.seconds[event] += duration
            self.count[event] += 1

    def _event(self, event, **_):
        if event == self.HITS:
            self.count[event] += 1

    def __enter__(self):
        import jax.monitoring as m

        m.register_event_duration_secs_listener(self._duration)
        m.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as m

        m.unregister_event_duration_listener(self._duration)
        m.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds[self.COMPILE],
                "trace_s": self.seconds[self.TRACE],
                "lower_s": self.seconds[self.LOWER],
                "backend_compiles": self.count[self.COMPILE],
                "cache_hits": self.count[self.HITS]}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


@dataclass
class RequestRecord:
    index: int
    params: dict
    seconds: float = 0.0
    spans: dict = field(default_factory=dict)     # call -> host seconds
    jax: dict = field(default_factory=dict)       # Monitor delta
    state: Optional[np.ndarray] = None
    final: Optional[np.ndarray] = None
    tasks_started: int = -1
    n_tasks: int = -1
    depth: int = -1
    max_width: int = -1
    graph: Optional[tuple] = None   # (coords, edge_src, edge_tgt, level_of)
    error: Optional[str] = None


@contextmanager
def span(name: str, record: Optional[RequestRecord] = None):
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield
    if record is not None:
        record.spans[name] = (record.spans.get(name, 0.0)
                              + time.perf_counter() - t0)


@dataclass
class Observed:
    """What a run saw; the per-layer readers take their numbers from it."""
    cell: Cell
    requests: list
    trace: object = None            # trace.TraceSummary, traced runs only
    peak: Optional[dict] = None     # peaks.json row of the device kind


# ------------------------------------------------------------------- run
class Runner:
    """Holds the session and the graph; makes requests through them."""

    def __init__(self, cell: Cell, monitor: Monitor):
        from repro.core.edt import ExecutionConfig, Session
        from repro.core.poly import Tiling
        from repro.core.programs import PROGRAMS

        cfg = cell.config
        self.cell = cell
        self.monitor = monitor
        self.session = Session(ExecutionConfig(
            backend=cfg["backend"], shards=int(cfg["shards"])))
        program = PROGRAMS[cfg["program"]]()
        tilings = {s: Tiling(tuple(cfg["tile"])) for s in program.statements}
        self.graph = self.session.graph(program, tilings)
        self.dtype = cfg["dtype"]

    def request(self, index: int, params: dict, state: np.ndarray,
                cold: bool, keep_graph: bool = False,
                dtype=None) -> RequestRecord:
        rec = RequestRecord(index, dict(params), state=state)
        s, g = self.session, self.graph
        before = self.monitor.snapshot()
        t0 = time.perf_counter()
        with span("request"):
            if cold:
                with span("Session.index_graph", rec):
                    s.index_graph(g, params)
                with span("Session.schedule", rec):
                    ig, sched = s.schedule(g, params)
                with span("Session.fused_packed", rec):
                    s.fused_packed(g, params)
            with span("Session.fused_executor", rec):
                ex = s.fused_executor(g, params, replay=True,
                                      validate=bool(self.cell.config[
                                          "validate"]),
                                      dtype=dtype or self.dtype)
            with span("FusedExecutor.run", rec):
                run = ex.run(state)
                final = np.array(run.final)
        rec.seconds = time.perf_counter() - t0
        rec.jax = delta(self.monitor.snapshot(), before)
        rec.final = final
        c = run.counters
        rec.tasks_started, rec.n_tasks = int(c.tasks_started), int(ex.dg.n)
        rec.depth, rec.max_width = int(c.depth), int(c.max_in_flight)
        if keep_graph:
            rec.graph = (ig.stmt_blocks[0][1], ig.edge_src, ig.edge_tgt,
                         sched.level_of)
        return rec

    def close(self):
        from repro.core.edt.shard import stop_process_servers

        if self.session is not None:
            self.session.close()
            stop_process_servers()
            self.session = None


def cold_compile_cache():
    """Every request compiles as a user's first request does."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def set_up(cell: Cell, runner: Runner, seed: int) -> RequestRecord:
    """The set-up request: the cell's cold fill and compile (a warm mix's
    own instance; a cold mix's ``setup_params``)."""
    sp = traffic.setup_params(cell.mix, cell.params)
    return runner.request(-1, sp, traffic.initial_grid(
        seed, SETUP_INDEX, cell.grid(sp)), cold=True)


def warm_up(cell: Cell, runner: Runner, seed: int, log=print) -> None:
    """Requests through the window's own path for the mix's
    ``warmup_seconds``: the executable's load from the compile cache gets
    faster over a warm process's first requests, and the window measures
    the process after that."""
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < cell.mix.get("warmup_seconds", 0):
        rec = runner.request(-2 - n, cell.params, traffic.initial_grid(
            seed, SETUP_INDEX + 1 + n, cell.grid(cell.params)),
            cold=cell.mix["cache"] == "cold")
        log(f"warm-up request {n}: {rec.seconds:.3f} s, jax {rec.jax}")
        n += 1


def child_processes() -> list:
    kids = []
    task = f"/proc/{os.getpid()}/task"
    for tid in os.listdir(task):
        with open(f"{task}/{tid}/children") as f:
            kids += f.read().split()
    return kids


def run_cell(cell: Cell, seed: int, seconds: float, trace_dir=None,
             device=None, t_start: Optional[float] = None,
             log=print) -> dict:
    """Set up, measure ``seconds`` of the cell's traffic, check, and return
    the result line's object (without printing it)."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    device = device if device is not None else jax.devices()[0]
    mix, cold = cell.mix, cell.mix["cache"] == "cold"
    if cold:
        cold_compile_cache()
    children = set(child_processes())
    with Monitor() as monitor:
        runner = Runner(cell, monitor)
        try:
            return _measure(cell, runner, monitor, seed, seconds, trace_dir,
                            device, t_start, cold, log, children)
        finally:
            runner.close()


def _measure(cell, runner, monitor, seed, seconds, trace_dir, device,
             t_start, cold, log, children) -> dict:
    import jax

    mix = cell.mix
    setup_req = set_up(cell, runner, seed)
    log(f"set-up request {setup_req.params}: {setup_req.seconds:.3f} s, "
        f"{setup_req.n_tasks} tasks, depth {setup_req.depth}, "
        f"jax {setup_req.jax}")
    warm_up(cell, runner, seed, log)
    setup_s = time.perf_counter() - t_start

    # ---- the window
    cache0 = dict(runner.session.cache.info())
    jax0 = monitor.snapshot()
    sample = _graph_sample(mix, cell.params, seed) if cold else set()
    records, failed = [], 0
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t0 = time.perf_counter()
    with span("window"):
        for req in traffic.requests(mix, cell.params, seed):
            if time.perf_counter() - t0 >= seconds:
                break
            state = traffic.initial_grid(seed, req.index,
                                         cell.grid(req.params))
            try:
                rec = runner.request(req.index, req.params, state, cold,
                                     keep_graph=req.params["N"] in sample)
            except Exception:           # a failed request is counted
                failed += 1
                rec = RequestRecord(req.index, req.params, state=state,
                                    error=traceback.format_exc())
                print(rec.error, file=sys.stderr)
            records.append(rec)
    window_s = time.perf_counter() - t0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    stats = device.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    cache1 = runner.session.cache.info()
    jax1 = monitor.snapshot()
    runner.close()
    left = set(child_processes()) - children
    if left:
        raise RuntimeError(f"child processes still running: {left}")

    done = [r for r in records if r.error is None]
    window_jax = delta(jax1, jax0)
    misses = cache1["misses"] - cache0["misses"]
    incremental = cache1["incremental_hits"] - cache0["incremental_hits"]
    for r in done:
        log(f"request {r.index} {r.params}: {r.seconds:.4f} s; "
            f"tasks {r.tasks_started}/{r.n_tasks}, depth {r.depth}, "
            f"widest level {r.max_width}; spans "
            + ", ".join(f"{k} {v:.4f}" for k, v in r.spans.items())
            + f"; jax compile {r.jax['compile_s']:.4f} s "
            f"({r.jax['backend_compiles']} backend, "
            f"{r.jax['cache_hits']} cache hits), trace "
            f"{r.jax['trace_s']:.4f} s, lower {r.jax['lower_s']:.4f} s")
    log(f"window: {len(records)} requests in {window_s:.3f} s; graph cache "
        f"hits {cache1['hits'] - cache0['hits']}, misses {misses}, "
        f"incremental hits {incremental}; jax {window_jax}; "
        f"peak_bytes_in_use {peak_bytes}")
    _enforce_traffic(cold, len(records), misses, incremental, window_jax)

    checks = check(cell, records, failed)
    obs = Observed(cell, records)
    out_device = {"platform": device.platform, "kind": device.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": peak_bytes}
    if trace_dir is None:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        report = mix["reports"]
        unit = {m["name"]: m["unit"] for m in cell.end_to_end}[report]
        metrics[report] = {"value": sum(r.seconds for r in records)
                           / max(len(records), 1), "unit": unit}
    else:
        from .trace import summarize

        obs.trace = summarize(trace_dir)
        obs.peak = roofline.peaks(device.device_kind)
        out_device.update(busy_s=obs.trace.busy_s,
                          window_s=obs.trace.window_s)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"])(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": out_device}
    if obs.trace is not None:
        result["breakdown"] = {"device_ops": obs.trace.device_ops,
                               "idle_gaps": obs.trace.idle_gaps}
    result["checks"] = checks
    return result


def _graph_sample(mix: dict, base: dict, seed: int) -> set:
    """The N of the cold requests whose graph is checked: the largest of
    the set, and one more drawn from the seed."""
    ns = sorted({r.params["N"] for r in traffic.requests(mix, base, seed)})
    rest = ns[:-1]
    pick = rest[traffic.rng(seed, 3).integers(len(rest))] if rest else None
    return {ns[-1]} | ({pick} if pick is not None else set())


def _enforce_traffic(cold, n, misses, incremental, window_jax):
    if cold:
        if misses < n or incremental or window_jax["cache_hits"]:
            raise TrafficViolation(
                f"cold window: {n} requests, {misses} graph-cache misses, "
                f"{incremental} incremental hits, "
                f"{window_jax['cache_hits']} compile-cache hits")
    elif misses:
        raise TrafficViolation(f"warm window: {misses} graph-cache misses")


# ------------------------------------------------------------- the check
def check(cell: Cell, records: list, failed: int) -> dict:
    """Each number compared, beside its limit.  ``correct`` is every
    value at or under its limit."""
    lim = cell.limits
    worst = 0.0
    tasks_wrong = 0
    tiles_wrong = edges_missing = order_wrong = 0
    for r in records:
        if r.error is not None:
            continue
        want = cell.solve(r.state, r.params["T"])
        worst = max(worst, rel_err(r.final, want))
        tasks_wrong += int(r.tasks_started != r.n_tasks)
        if r.graph is not None:
            t, m, o = cell.graph_check(r.params, tuple(cell.config["tile"]),
                                       *r.graph)
            tiles_wrong += t
            edges_missing += m
            order_wrong += o
    checks = {"max_rel_err": {"value": worst,
                              "limit": lim["max_rel_err"]},
              "failed_requests": {"value": failed, "limit": 0},
              "tasks_not_run": {"value": tasks_wrong, "limit": 0}}
    if cell.mix["cache"] == "cold":
        checks.update(
            tiles_wrong={"value": tiles_wrong, "limit": 0},
            edges_missing={"value": edges_missing, "limit": 0},
            levels_out_of_order={"value": order_wrong, "limit": 0})
    return checks
