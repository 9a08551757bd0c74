"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device operations are the events of the ``XLA Ops`` line of every device
plane.  Busy time is the union of their intervals inside the measured
window, which the harness marks with its ``bench:window`` host span;
``busy_s`` averages it over the device planes.  Operations nest (a
``while`` holds its body's operations), so each operation's time in
``device_ops`` is its self time: its interval less its children's.  An
idle gap is an interval of the window in which no device operation runs;
it is cut at the edges of the host's events, and each piece is attributed to
the innermost ``bench:`` span covering it and, beside it, to the innermost
other host event there (what JAX itself was doing), if any.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

SPAN_PREFIX = "bench:"
WINDOW = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
#: Entries of each ``breakdown`` list.
TOP = 10
#: Host events shorter than this do not name an idle gap.
MIN_HOST_EVENT_NS = 100_000


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    devices: int
    device_ops: list = field(default_factory=list)   # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)    # [[name, seconds]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted ``[start, end)`` rows of possibly overlapping rows."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = reach[np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], 1)


def _gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def reduce_events(device_ops: dict, host_events: list) -> TraceSummary:
    """``device_ops``: plane name -> list of ``(name, start_ns, end_ns)``;
    ``host_events``: list of ``(line, name, start_ns, end_ns)``."""
    windows = [(s, e) for _, n, s, e in host_events if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0]
    if not device_ops:
        raise ValueError("the trace holds no device plane")
    busy_ns, per_op = 0.0, defaultdict(float)
    unions = []
    for ops in device_ops.values():
        iv = np.asarray([(max(s, lo), min(e, hi)) for _, s, e in ops
                         if e > lo and s < hi], dtype=np.float64)
        u = _union(iv.reshape(-1, 2))
        unions.append(u)
        busy_ns += float(np.sum(u[:, 1] - u[:, 0]))
        inside = [(op_label(n), max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        for name, t in _self_times(inside):
            per_op[name] += t
    ndev = len(device_ops)
    # idle time of the first plane (one chip per cell today), cut at every
    # host event's edge and each piece named by what the host was doing
    spans, others = _host_events(host_events)
    cuts = np.unique([t for _, s, e in spans + others for t in (s, e)])
    pieces = []
    for s, e in _gaps(unions[0], lo, hi):
        inner = cuts[(cuts > s) & (cuts < e)]
        edges = np.concatenate([[s], inner, [e]])
        pieces.append(np.stack([edges[:-1], edges[1:]], 1))
    pieces = np.concatenate(pieces) if pieces else np.zeros((0, 2))
    labels = _labels(0.5 * (pieces[:, 0] + pieces[:, 1]), spans, others)
    by_label = defaultdict(float)
    for label, (s, e) in zip(labels, pieces):
        by_label[label] += e - s
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        busy_s=busy_ns / ndev * 1e-9, window_s=(hi - lo) * 1e-9,
        devices=ndev,
        device_ops=[[n, float(v) / ndev * 1e-9] for n, v in top],
        idle_gaps=[[n, float(v) * 1e-9] for n, v in idle])


_HLO = re.compile(r"^(%?[\w.\-]+) = .*? ([\w\-]+)\(")
_KIND = re.compile(r"kind=(k\w+)")


def op_label(hlo: str) -> str:
    """``%fusion.12 fusion kLoop`` from an HLO instruction's text."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    kind = _KIND.search(hlo)
    return " ".join([m.group(1), m.group(2)] + ([kind.group(1)] if kind
                                                 else []))


def _self_times(ops: list):
    """``(name, self time)`` of nested ``(name, start, end)`` intervals:
    each interval less the intervals directly inside it."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    stack, out = [], []            # stack rows: [name, end, self]
    for name, s, e in ops:
        while stack and stack[-1][1] <= s:
            out.append(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend(stack)
    return [(n, t) for n, _, t in out]


def _innermost(points: np.ndarray, events: list) -> np.ndarray:
    """Index into ``events`` of the shortest event covering each point,
    -1 where none does."""
    out = np.full(len(points), -1)
    for i in sorted(range(len(events)),
                    key=lambda k: events[k][1] - events[k][2]):
        _, s, e = events[i]
        out[(points >= s) & (points < e)] = i
    return out


def _host_events(host_events: list) -> tuple:
    """The ``bench:`` spans, and the other host events of at least
    :data:`MIN_HOST_EVENT_NS` on the spans' line (what JAX was doing)."""
    spans = [(n, s, e) for _, n, s, e in host_events
             if n.startswith(SPAN_PREFIX) and n != WINDOW]
    lines = {ln for ln, n, _, _ in host_events if n == WINDOW}
    others = [(n, s, e) for ln, n, s, e in host_events
              if ln in lines and not n.startswith(SPAN_PREFIX)
              and e - s >= MIN_HOST_EVENT_NS]
    return spans, others


def _labels(points: np.ndarray, spans: list, others: list) -> list:
    """What the host was doing at each point: the innermost span, and the
    innermost other event there."""
    si, oi = _innermost(points, spans), _innermost(points, others)
    out = []
    for a, b in zip(si, oi):
        name = spans[a][0][len(SPAN_PREFIX):] if a >= 0 else "outside any span"
        out.append(f"{name} / {others[b][0]}" if b >= 0 else name)
    return out


def read_xplane(path: Path) -> tuple:
    """``(device_ops, host_events)`` of an ``.xplane.pb``, as
    :func:`reduce_events` takes them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops, host_events = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.extend((line.name, ev.name, ev.start_ns,
                                    ev.end_ns) for ev in line.events)
    return device_ops, host_events


def summarize(log_dir: Path) -> TraceSummary:
    return reduce_events(*read_xplane(find_xplane(log_dir)))
