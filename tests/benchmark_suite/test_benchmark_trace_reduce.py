"""The reduction from a profiler trace to busy, idle, kernel and
collective shares (``benchmarks/harness/trace_reduce.py``).

Two witnesses: a trace small enough to count by hand, and a piece of a
real chip trace (``benchmarks/testdata/trace_events.json``: names,
starts and durations of the device's XLA ops and the benchmark's own
marks, cut from the builder's traced run of ``gpt2_124m.train.1chip``)
whose numbers are counted again here the slow way, on a raster of the
window, and must agree with the interval arithmetic.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmarks", "testdata", "trace_events.json")

#   time      0    100  150  200              500  600  700  800
#   marks          |90 ------ group 1 -------|500 -- group 2 --|800
#   device 0       [a  ]     [while: b, custom-call.1 ]  [all-reduce]
HAND = {
    "devices": {"/device:TPU:0": [
        ["a", 100, 50],
        ["while", 200, 300],            # holds b and custom-call.1
        ["b", 210, 100],
        ["custom-call.1", 320, 100],
        ["all-reduce.2", 600, 100]]},
    "marks": [["bench.group", 90, 410], ["bench.group", 500, 300]],
}


def test_hand_counted_busy_idle_kernel_and_collective_shares():
    r = tr.reduce_trace(HAND, ["%custom-call.1"], {}, [])
    assert r["n_devices"] == 1 and r["n_marks"] == 2
    assert r["window_s"] == pytest.approx(710e-9)      # 90 .. 800
    assert r["busy_s"] == pytest.approx(450e-9)        # 50 + 300 + 100
    assert r["idle_share"] == pytest.approx(1 - 450 / 710)
    assert r["kernel_time_share"] == pytest.approx(100 / 450)
    assert r["collective_time_share"] == pytest.approx(100 / 710)


def test_hand_counted_self_times_and_gaps():
    r = tr.reduce_trace(HAND, ["%custom-call.1"], {}, [])
    ops = dict(r["device_ops"])
    # the while's own time is what its body does not cover: 300 - 200
    assert ops["while"] == pytest.approx(100e-9)
    assert ops["b"] == pytest.approx(100e-9)
    assert ops["a"] == pytest.approx(50e-9)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    gaps = dict(r["idle_gaps"])
    assert gaps == {
        "between-groups/after:start": pytest.approx(10e-9),   # 90..100
        "inside-group/after:a": pytest.approx(50e-9),         # 150..200
        "between-groups/after:while": pytest.approx(100e-9),  # 500..600
        "between-groups/after:all-reduce.2": pytest.approx(100e-9)}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_two_devices_are_averaged_and_ops_outside_the_window_clipped():
    ev = {"devices": {
        "/device:TPU:0": [["x", 0, 100], ["y", 150, 100]],    # 50 + 50 in
        "/device:TPU:1": [["all-gather.1", 50, 150]]},        # all in
        "marks": [["bench.group", 50, 150]]}
    r = tr.reduce_trace(ev, [], {}, [])
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(150e-9)
    assert r["busy_s"] == pytest.approx((100 + 150) / 2 * 1e-9)
    assert r["collective_time_share"] == pytest.approx(150 / 2 / 150)
    assert r["kernel_time_share"] == 0.0


def test_a_trace_with_no_marks_or_no_device_gives_nothing():
    assert tr.reduce_trace({"devices": {}, "marks": HAND["marks"]},
                           [], {}, []) == {}
    assert tr.reduce_trace({"devices": HAND["devices"], "marks": []},
                           [], {}, []) == {}


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) \
        == [[0, 4], [5, 7]]
    assert tr.total([[0, 4], [5, 7]]) == 6
    assert tr.clip([[0, 4], [5, 7]], 3, 6) == [[3, 4], [5, 6]]
    assert tr.is_collective("%all-reduce-start.3")
    assert tr.is_collective("reduce-scatter.1")
    assert not tr.is_collective("fusion.12")


def _raster(events, kernel_names, step_ns):
    """The same shares counted the slow way: sample the window."""
    marks = events["marks"]
    lo = marks[0][1]
    hi = max(s + d for _, s, d in marks)
    kernels = {n.lstrip("%") for n in kernel_names}
    n = (hi - lo) // step_ns
    out = []
    for ops in events["devices"].values():
        busy = bytearray(n)
        kern = bytearray(n)
        for name, s, d in ops:
            a = max(0, -(-(s - lo) // step_ns))
            b = min(n, -(-(s + d - lo) // step_ns))
            if b > a:
                busy[a:b] = b"\x01" * (b - a)
                if name in kernels:
                    kern[a:b] = b"\x01" * (b - a)
        out.append((sum(busy) / n, sum(kern) / max(sum(busy), 1)))
    return out


def test_recorded_chip_trace_against_a_raster_count():
    with open(RECORDED) as f:
        rec = json.load(f)
    assert os.path.getsize(RECORDED) < 1_000_000
    r = tr.reduce_trace(rec["events"], rec["kernel_names"], {}, [])
    (busy_share, kernel_share), = _raster(rec["events"],
                                          rec["kernel_names"], 1000)
    assert 1 - r["idle_share"] == pytest.approx(busy_share, abs=2e-3)
    assert r["kernel_time_share"] == pytest.approx(kernel_share, abs=2e-3)
    assert r["n_marks"] == len(rec["events"]["marks"]) >= 1
    assert len(r["device_ops"]) == 10
    # and the values the builder read off this piece on the day
    for key, want in rec["expected"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
