"""The trace reduction, on hand-built events and on a recorded trace."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def test_busy_union_gaps_and_labels_by_hand():
    dev = {"/device:TPU:0": [
        # starts before the window, and holds the fusion
        ("%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
         "condition=%c, body=%b", 0 * MS, 6 * MS),
        ("%fusion.2 = f32[8]{0:T(128)} fusion(f32[8]{0} %x), kind=kLoop, "
         "calls=%f", 3 * MS, 5 * MS),
        ("scatter", 10 * MS, 12 * MS),
        ("copy", 19 * MS, 19 * MS + 500_000),
        ("late", 30 * MS, 40 * MS),          # after the window
    ]}
    host = [
        ("python", "bench:window", 2 * MS, 20 * MS),
        ("python", "bench:request", 2 * MS, 19 * MS),
        ("python", "bench:FusedExecutor.run", 7 * MS, 19 * MS),
        ("python", "backend_compile", 7 * MS, 9 * MS),
        ("python", "tiny", 13 * MS, 13 * MS + 50_000),   # too short to name
        ("other", "elsewhere", 12 * MS, 20 * MS),        # another thread
    ]
    s = trace.reduce_events(dev, host)
    assert s.window_s == pytest.approx(0.018)
    # busy: [2, 6), [10, 12) and [19, 19.5) inside the window
    assert s.busy_s == pytest.approx(0.0065)
    assert s.idle_share == pytest.approx(1 - 6.5 / 18)
    assert s.devices == 1
    ops = dict((n, v) for n, v in s.device_ops)
    # self times: the while's [2, 6) less its fusion's [3, 5)
    assert ops == {"%while.1 while": pytest.approx(0.002),
                   "%fusion.2 fusion kLoop": pytest.approx(0.002),
                   "scatter": pytest.approx(0.002),
                   "copy": pytest.approx(0.0005)}
    gaps = dict((n, v) for n, v in s.idle_gaps)
    # [6, 10) is cut at 7 and 9: request, then FusedExecutor.run inside
    # backend_compile, then FusedExecutor.run; [12, 19): FusedExecutor.run
    # (the short event does not cut, the other thread's does not count);
    # [19.5, 20): the window only
    assert gaps == {"request": pytest.approx(0.001),
                    "FusedExecutor.run / backend_compile": pytest.approx(0.002),
                    "FusedExecutor.run": pytest.approx(0.008),
                    "outside any span": pytest.approx(0.0005)}


def test_two_device_planes_average_busy():
    ops = [("f", 0, 5 * MS)]
    dev = {"/device:TPU:0": ops, "/device:TPU:1": ops + [("g", 6 * MS,
                                                           10 * MS)]}
    host = [("python", "bench:window", 0, 10 * MS)]
    s = trace.reduce_events(dev, host)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(0.007)    # (5 + 9) / 2 ms


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": []}, [])
    with pytest.raises(ValueError):
        trace.reduce_events({}, [("python", "bench:window", 0, MS)])


def test_recorded_chip_trace():
    """A traced window of one small warm request, recorded on one TPU v5e
    through the harness (``bench:`` spans and all)."""
    dev, host = trace.read_xplane(DATA / "tiny_warm.xplane.pb")
    assert list(dev) == ["/device:TPU:0"]
    s = trace.reduce_events(dev, host)
    assert s.devices == 1
    assert s.busy_s == pytest.approx(0.001078199, rel=1e-6)
    assert s.window_s == pytest.approx(0.761059381, rel=1e-9)
    # the sweep's while loop, then the tile body's gather fusions
    assert s.device_ops[0][0] == "%while.5 while"
    assert s.device_ops[0][1] == pytest.approx(0.000173955, rel=1e-6)
    assert all(n.startswith("%fusion.") for n, _ in s.device_ops[1:])
    assert len(s.device_ops) == len(s.idle_gaps) == trace.TOP
    # the window's idle time went to compiling and lowering the sweep
    assert s.idle_gaps[0][0] == ("FusedExecutor.run / "
                                 "backend_compile_and_load")
    assert s.idle_gaps[0][1] == pytest.approx(0.449662045, rel=1e-6)
    idle = sum(t for _, t in s.idle_gaps)
    assert idle <= s.window_s - s.busy_s
