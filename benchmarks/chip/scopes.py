"""Device time by the program's ``edt.*`` scopes, from a profiler trace.

The fused sweep wraps each level's ops in a ``jax.named_scope``
(``edt.frontier``, ``edt.validate``, ``edt.body``, ``edt.decrement``;
``repro/core/edt/fused.py``).  The scope rides into the trace: each
device op's event metadata carries a ``tf_op`` stat, the HLO op's
``op_name`` (``jit(sweep)/while/body/closed_call/edt.body/gather:``).
``jax.profiler.ProfileData`` does not expose event metadata, so
:func:`op_scopes` reads it from the ``.xplane.pb`` itself (protobuf wire
format; the field numbers are those of ``tsl/profiler/protobuf/
xplane.proto``).

:func:`reduce_scopes` gives each op's self time (as ``trace.py`` does) to
the innermost ``edt.*`` scope in its ``op_name``, and to ``unattributed``
where there is none (ops outside the sweep, and ops XLA made without
metadata); the scopes and ``unattributed`` add up to the busy time.
:func:`idle_gaps` names idle time as ``trace.py`` does, with the
program's own ``edt:`` span (``repro.core.edt.tracing``) between the
``bench:`` span and JAX's host event.
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import numpy as np

from . import trace

#: The program's host spans in a trace (``repro.core.edt.tracing.PREFIX``).
EDT_PREFIX = "edt:"
UNATTRIBUTED = "unattributed"
#: The counted sync: every scope of the sweep but the tile body.
SYNC = ("edt.frontier", "edt.validate", "edt.decrement")
BODY = "edt.body"
_SCOPE = re.compile(r"(?:^|/)(edt\.[A-Za-z_]+)(?=[/:]|$)")


@dataclass
class ScopeSummary:
    busy_s: float
    window_s: float
    devices: int
    scopes: dict = field(default_factory=dict)    # scope -> device seconds


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``edt.*`` scope of an ``op_name``, or ``None``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


# ------------------------------------------------------- protobuf reading
def _varint(buf: bytes, pos: int) -> tuple:
    b = buf[pos]
    pos += 1
    if b < 0x80:
        return b, pos
    value, shift = b & 0x7F, 7
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int):
    """``(field, value)`` of each field of the message in
    ``buf[pos:end]``; a length-delimited value is its ``(start, stop)``."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire == 1:
            value, pos = None, pos + 8
        elif wire == 5:
            value, pos = None, pos + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes(buf: bytes) -> dict:
    """Device plane name -> {op event name -> ``edt.*`` scope or ``None``}
    of an ``.xplane.pb``'s bytes.  An event name whose metadata entries
    disagree on the scope maps to ``None``."""
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:                                   # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:                              # XPlane.name
                name = _text(buf, v)
            elif pf == 4:                            # event_metadata map
                events.append(v)
            elif pf == 5:                            # stat_metadata map
                for ef, ev in _fields(buf, *v):
                    if ef == 2:
                        sid, sname = None, ""
                        for sf, sv in _fields(buf, *ev):
                            if sf == 1:
                                sid = sv
                            elif sf == 2:
                                sname = _text(buf, sv)
                        stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        scopes = {}
        for entry in events:
            for ef, meta in _fields(buf, *entry):
                if ef != 2:
                    continue
                ev_name, scope = "", None
                for mf, mv in _fields(buf, *meta):
                    if mf == 2:                      # XEventMetadata.name
                        ev_name = _text(buf, mv)
                    elif mf == 5:                    # XEventMetadata.stats
                        sid, text = _stat(buf, mv, stat_names)
                        if sid in tf_op:
                            scope = scope_of(text)
                if ev_name in scopes and scopes[ev_name] != scope:
                    scope = None
                scopes[ev_name] = scope
        out[name] = scopes
    return out


def _stat(buf: bytes, stat: tuple, stat_names: dict) -> tuple:
    """``(metadata id, string value)`` of an ``XStat``; a string held by
    reference is a stat-metadata name."""
    sid, text = None, ""
    for sf, sv in _fields(buf, *stat):
        if sf == 1:                                  # XStat.metadata_id
            sid = sv
        elif sf == 5:                                # str_value
            text = _text(buf, sv)
        elif sf == 7:                                # ref_value
            text = stat_names.get(sv, "")
    return sid, text


# -------------------------------------------------------------- reduction
def _window(host_events: list) -> tuple:
    windows = [(s, e) for _, n, s, e in host_events if n == trace.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW} span, found "
                         f"{len(windows)}")
    return windows[0]


def reduce_scopes(device_ops: dict, host_events: list,
                  scopes: dict) -> ScopeSummary:
    """Device seconds per scope inside the ``bench:window`` span,
    averaged over the device planes.  ``device_ops`` and ``host_events``
    as :func:`trace.reduce_events` takes them; ``scopes`` as
    :func:`op_scopes` gives them."""
    lo, hi = _window(host_events)
    if not device_ops:
        raise ValueError("the trace holds no device plane")
    busy_ns, per_scope = 0.0, defaultdict(float)
    for plane, ops in device_ops.items():
        of = scopes.get(plane, {})
        inside = [(of.get(n) or UNATTRIBUTED, max(s, lo), min(e, hi))
                  for n, s, e in ops if e > lo and s < hi]
        u = trace._union(np.asarray([(s, e) for _, s, e in inside],
                                    dtype=np.float64).reshape(-1, 2))
        busy_ns += float(np.sum(u[:, 1] - u[:, 0]))
        for name, t in trace._self_times(inside):
            per_scope[name] += t
    ndev = len(device_ops)
    return ScopeSummary(
        busy_s=busy_ns / ndev * 1e-9, window_s=(hi - lo) * 1e-9,
        devices=ndev,
        scopes={k: v / ndev * 1e-9 for k, v in sorted(per_scope.items())})


def idle_gaps(device_ops: dict, host_events: list) -> list:
    """``[[label, seconds]]`` of the first device plane's idle time in the
    window, most first: each piece is named ``<bench: span> / <edt: span>
    / <JAX host event>``, the innermost of each there, a part left out
    where none covers it (``outside any span`` where neither span does)."""
    lo, hi = _window(host_events)
    lines = {ln for ln, n, _, _ in host_events if n == trace.WINDOW}
    bench, edt, jax_ev = [], [], []
    for ln, n, s, e in host_events:
        if n.startswith(trace.SPAN_PREFIX):
            if n != trace.WINDOW:
                bench.append((n[len(trace.SPAN_PREFIX):], s, e))
        elif n.startswith(EDT_PREFIX):
            edt.append((n, s, e))
        elif ln in lines and e - s >= trace.MIN_HOST_EVENT_NS:
            jax_ev.append((n, s, e))
    ops = next(iter(device_ops.values()))
    busy = trace._union(np.asarray(
        [(max(s, lo), min(e, hi)) for _, s, e in ops if e > lo and s < hi],
        dtype=np.float64).reshape(-1, 2))
    cuts = np.unique([t for _, s, e in bench + edt + jax_ev
                      for t in (s, e)])
    pieces = []
    for s, e in trace._gaps(busy, lo, hi):
        inner = cuts[(cuts > s) & (cuts < e)]
        edges = np.concatenate([[s], inner, [e]])
        pieces.append(np.stack([edges[:-1], edges[1:]], 1))
    pieces = np.concatenate(pieces) if pieces else np.zeros((0, 2))
    mid = 0.5 * (pieces[:, 0] + pieces[:, 1])
    picks = [trace._innermost(mid, evs) for evs in (bench, edt, jax_ev)]
    by_label = defaultdict(float)
    for k, (s, e) in enumerate(pieces):
        parts = [evs[i[k]][0] for i, evs in zip(picks, (bench, edt, jax_ev))
                 if i[k] >= 0]
        if picks[0][k] < 0 and picks[1][k] < 0:
            parts.insert(0, "outside any span")
        by_label[" / ".join(parts)] += e - s
    return [[n, float(v) * 1e-9] for n, v in
            sorted(by_label.items(), key=lambda kv: -kv[1])]


# ------------------------------------------------------------ a run's trace
def summarize(path: Path) -> ScopeSummary:
    path = Path(path)
    return reduce_scopes(*trace.read_xplane(path),
                         op_scopes(path.read_bytes()))


@functools.lru_cache(maxsize=1)
def _summarize_file(path: str, mtime_ns: int, size: int) -> ScopeSummary:
    return summarize(Path(path))


def of_run(run, trace_dir: Optional[Path] = None) -> Optional[ScopeSummary]:
    """The scope summary of a traced run's window (``run`` a
    ``harness.Observed``), read once per trace file; ``None`` for an
    untraced run or where the trace found is not this run's (its window
    differs from ``run.trace``'s)."""
    if run.trace is None:
        return None
    if trace_dir is None:
        from .run import TRACE_DIR as trace_dir
    try:
        path = trace.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    st = path.stat()
    s = _summarize_file(str(path), st.st_mtime_ns, st.st_size)
    if abs(s.window_s - run.trace.window_s) > 1e-6 * max(s.window_s, 1e-9):
        return None
    return s


def per_solve(run, names) -> Optional[float]:
    """Device seconds per solve under the scopes ``names`` in a traced
    run; ``None`` where the trace holds none of them (a program without
    the scopes)."""
    s = of_run(run)
    n = sum(1 for r in run.requests if r.error is None)
    if s is None or not n or not any(k in s.scopes for k in names):
        return None
    return sum(s.scopes.get(k, 0.0) for k in names) / n
