"""Device time by ``edt.*`` scope, idle gaps named by ``edt:`` span, and
the readers of the scope and occupancy metrics: on hand-built events, on
hand-encoded trace bytes and on a recorded trace."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness, roofline, scopes, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
BODY_OP = "jit(sweep)/while/body/closed_call/edt.body/gather:"
WARM = ["jacobi2d-coarse-large.warm", "jacobi2d-fine-medium.warm"]


# ------------------------------------------------- hand-encoded protobuf
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _len(field: int, payload) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name: str, events, ref: bool = False) -> bytes:
    """An ``XPlane`` whose event metadata carry a ``tf_op`` stat (as a
    string, or with ``ref`` as a reference to a stat-metadata name)."""
    body = _len(2, name)
    body += _len(3, _int(1, 0))                     # a line, skipped
    stats = {1: "program_id", 2: "tf_op"}
    events = events.items() if isinstance(events, dict) else events
    for k, (ev, op) in enumerate(events, start=10):
        if op is None:
            stat = _int(1, 1) + _int(3, 77)
        elif ref:
            stats[100 + k] = op
            stat = _int(1, 2) + _int(7, 100 + k)
        else:
            stat = _int(1, 2) + _len(5, op)
        meta = _int(1, k) + _len(2, ev) + _len(4, ev[:8]) + _len(5, stat)
        body += _len(4, _int(1, k) + _len(2, meta))
    for sid, sname in stats.items():
        body += _len(5, _int(1, sid) + _len(2, _int(1, sid) + _len(2, sname)))
    return _len(1, body)


def test_op_scopes_reads_tf_op_from_event_metadata():
    buf = (_plane("/host:CPU", {"%fusion.1 = x": BODY_OP})
           + _plane("/device:TPU:0", {
               "%fusion.1 = a": BODY_OP,
               "%fusion.2 = b": "jit(sweep)/while/body/closed_call/"
                                "edt.decrement/scatter-add:",
               "%while.3 = c": "jit(sweep)/while:",
               "%copy.4 = d": None})
           + _plane("/device:TPU:1", {"%fusion.1 = a": BODY_OP}, ref=True))
    got = scopes.op_scopes(buf)
    assert set(got) == {"/device:TPU:0", "/device:TPU:1"}
    assert got["/device:TPU:0"] == {"%fusion.1 = a": "edt.body",
                                    "%fusion.2 = b": "edt.decrement",
                                    "%while.3 = c": None, "%copy.4 = d": None}
    assert got["/device:TPU:1"] == {"%fusion.1 = a": "edt.body"}


def test_an_event_name_with_two_scopes_is_unattributed():
    # the same name in two metadata entries of one plane
    buf = _plane("/device:TPU:0", [("%f = a", BODY_OP),
                                   ("%f = a", "jit(x)/y:")])
    assert scopes.op_scopes(buf)["/device:TPU:0"] == {"%f = a": None}


@pytest.mark.parametrize("op_name,want", [
    (BODY_OP, "edt.body"),
    ("jit(sweep)/edt.validate/reduce_sum:", "edt.validate"),
    ("jit(sweep)/edt.frontier/edt.body/add", "edt.body"),
    ("jit(sweep)/while/body/closed_call/gather:", None),
    ("jit(sweep)/xedt.body/gather:", None),
])
def test_scope_of_an_op_name(op_name, want):
    assert scopes.scope_of(op_name) == want


# ------------------------------------------------------------ reduction
def _events():
    dev = {"/device:TPU:0": [
        ("%while.1 = w", 0 * MS, 10 * MS),
        ("%fusion.2 = body", 3 * MS, 6 * MS),
        ("%fusion.3 = dec", 6 * MS, 7 * MS),
        ("%fusion.4 = hoisted", 7 * MS, 8 * MS),
        ("%copy.5 = up", 12 * MS, 13 * MS),
    ]}
    host = [
        ("python", "bench:window", 2 * MS, 20 * MS),
        ("python", "bench:FusedExecutor.run", 2 * MS, 19 * MS),
        ("python", "edt:dispatch", 10 * MS, 14 * MS),
        ("python", "PjitFunction(sweep)", 10 * MS, 11 * MS),
        ("python", "edt:readback", 15 * MS, 16 * MS),
        ("python", "np.asarray(jax.Array)", 15 * MS, 16 * MS),
    ]
    of = {"/device:TPU:0": {"%fusion.2 = body": "edt.body",
                            "%fusion.3 = dec": "edt.decrement",
                            "%while.1 = w": None}}
    return dev, host, of


def test_scopes_and_unattributed_add_up_to_busy():
    dev, host, of = _events()
    s = scopes.reduce_scopes(dev, host, of)
    assert s.busy_s == pytest.approx(0.009)          # [2, 10) and [12, 13)
    assert s.window_s == pytest.approx(0.018)
    # the while's [2, 10) less its children; the hoisted fusion and the
    # copy carry no scope
    assert s.scopes == {"edt.body": pytest.approx(0.003),
                        "edt.decrement": pytest.approx(0.001),
                        "unattributed": pytest.approx(0.005)}
    assert sum(s.scopes.values()) == pytest.approx(s.busy_s)
    assert s.busy_s == pytest.approx(trace.reduce_events(dev, host).busy_s)


def test_idle_gaps_name_the_edt_span():
    dev, host, _ = _events()
    gaps = dict(scopes.idle_gaps(dev, host))
    # [10, 12): dispatch, JAX's call [10, 11) inside; [13, 19): dispatch
    # to 14, then the run alone but for the read-back [15, 16); [19, 20):
    # no span
    assert gaps == {
        "FusedExecutor.run / edt:dispatch / PjitFunction(sweep)":
            pytest.approx(0.001),
        "FusedExecutor.run / edt:dispatch": pytest.approx(0.002),
        "FusedExecutor.run": pytest.approx(0.004),
        "FusedExecutor.run / edt:readback / np.asarray(jax.Array)":
            pytest.approx(0.001),
        "outside any span": pytest.approx(0.001)}


def test_recorded_trace_without_scopes():
    """The recorded window of a program without scopes or ``edt:`` spans:
    every op is unattributed, and the gaps read as ``trace.py``'s."""
    path = DATA / "tiny_warm.xplane.pb"
    s = scopes.summarize(path)
    dev, host = trace.read_xplane(path)
    t = trace.reduce_events(dev, host)
    assert set(s.scopes) == {"unattributed"}
    assert s.scopes["unattributed"] == pytest.approx(t.busy_s, rel=1e-9)
    assert s.window_s == t.window_s
    gaps = scopes.idle_gaps(dev, host)
    assert gaps[:trace.TOP] == [[n, pytest.approx(v, rel=1e-9)]
                                for n, v in t.idle_gaps]


def test_recorded_trace_with_scopes_and_spans():
    """A traced window of one small warm request with recording on,
    recorded on one TPU v5e through ``benchmarks/record_cell.py``: the ops
    carry their ``edt.*`` scopes, and the idle gaps name the ``edt:``
    span they fall in."""
    path = DATA / "tiny_warm_edt.xplane.pb"
    s = scopes.summarize(path)
    dev, host = trace.read_xplane(path)
    t = trace.reduce_events(dev, host)
    assert set(s.scopes) == {"edt.body", "edt.decrement", "edt.frontier",
                             "edt.validate", "unattributed"}
    assert sum(s.scopes.values()) == pytest.approx(t.busy_s, rel=1e-9)
    assert s.scopes["edt.body"] == pytest.approx(0.001099685, rel=1e-6)
    assert s.scopes["unattributed"] < 0.05 * t.busy_s
    gaps = scopes.idle_gaps(dev, host)
    assert gaps[0][0] == ("FusedExecutor.run / edt:dispatch / "
                          "lower_sharding_computation")
    assert gaps[0][1] == pytest.approx(0.297039204, rel=1e-6)
    assert gaps[1][0] == ("FusedExecutor.run / edt:dispatch / "
                          "PjitFunction(sweep)")
    # the same idle time, with the edt: span between the two others
    assert t.idle_gaps[0] == ["FusedExecutor.run / lower_sharding_computation",
                              pytest.approx(gaps[0][1], rel=1e-9)]
    assert sum(v for _, v in gaps) == pytest.approx(t.window_s - t.busy_s,
                                                    rel=1e-9)


# -------------------------------------------------------------- readers
def _observed(monkeypatch, summary):
    from benchmarks.chip.trace import TraceSummary

    cell = harness.load_cell(WARM[0])
    reqs = [harness.RequestRecord(i, {"T": 10, "N": 100}, n_tasks=30,
                                  depth=4, max_width=12) for i in range(2)]
    obs = harness.Observed(cell, reqs,
                           TraceSummary(busy_s=0.5, window_s=2.0, devices=1),
                           roofline.peaks("TPU v5 lite"))
    monkeypatch.setattr(scopes, "of_run", lambda run: summary)
    return obs


SUMMARY = scopes.ScopeSummary(busy_s=0.5, window_s=2.0, devices=1, scopes={
    "edt.body": 0.3, "edt.decrement": 0.1, "edt.frontier": 0.04,
    "edt.validate": 0.02, "unattributed": 0.04})


@pytest.mark.parametrize("metric,want", [
    ("body_device_s.warm", 0.15),
    ("sync_device_s.warm", 0.08),
    # two solves of 2 * 100**2 * 4 * 10 B = 800 kB each, at 819 GB/s, in
    # 0.3 s of body time
    ("body_hbm_roofline", 100 * 1.6e6 / 819e9 / 0.3),
    # 60 tasks in 2 * 4 * 12 lanes
    ("lane_occupancy.warm", 62.5),
])
def test_reader_arithmetic_by_hand(monkeypatch, metric, want):
    obs = _observed(monkeypatch, SUMMARY)
    assert harness.load_reader(metric)(obs) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["body_device_s.warm",
                                    "sync_device_s.warm",
                                    "body_hbm_roofline"])
@pytest.mark.parametrize("summary", [
    None, scopes.ScopeSummary(0.5, 2.0, 1, {"unattributed": 0.5})])
def test_scope_readers_return_nothing_without_scopes(monkeypatch, metric,
                                                     summary):
    assert harness.load_reader(metric)(_observed(monkeypatch,
                                                 summary)) is None


@pytest.mark.parametrize("name", WARM)
def test_new_metrics_belong_to_the_warm_cells(name):
    names = {m["name"] for m in harness.load_cell(name).per_layer}
    assert {"body_device_s.warm", "sync_device_s.warm", "body_hbm_roofline",
            "lane_occupancy.warm"} <= names


def test_of_run_finds_only_its_own_trace(tmp_path):
    from benchmarks.chip.trace import TraceSummary

    prof = tmp_path / "plugins" / "profile" / "1"
    prof.mkdir(parents=True)
    (prof / "x.xplane.pb").write_bytes(
        (DATA / "tiny_warm.xplane.pb").read_bytes())
    s = scopes.summarize(DATA / "tiny_warm.xplane.pb")
    run = harness.Observed(None, [], TraceSummary(s.busy_s, s.window_s, 1))
    assert scopes.of_run(run, tmp_path) == s
    other = harness.Observed(None, [], TraceSummary(s.busy_s, 1.0, 1))
    assert scopes.of_run(other, tmp_path) is None
    assert scopes.of_run(run, tmp_path / "empty") is None
    assert scopes.of_run(harness.Observed(None, []), tmp_path) is None
