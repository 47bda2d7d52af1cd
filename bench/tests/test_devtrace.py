"""The reduction from a profiler trace to busy, collective and idle time.

Run by hand: ``python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import devtrace

RECORDED = Path(__file__).with_name("data") / "recorded_planes.json"


def plane(name, line, events):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def synthetic():
    host = plane("/host:CPU", "python", [
        ["bench.window", 0.0, 1000.0],
        ["bench.dispatch", 0.0, 50.0],
        ["bench.readback", 50.0, 950.0],
    ])
    dev0 = plane("/device:TPU:0", "XLA Ops", [
        ["while.1", 100.0, 600.0],        # encloses the two ops below
        ["fusion.2", 100.0, 300.0],
        ["all-gather.3", 400.0, 100.0],
        ["fusion.2", 500.0, 200.0],       # overlaps all-gather.3 and ends at 700
        ["copy.4", 900.0, 200.0],         # runs past the window's end at 1000
    ])
    dev1 = plane("/device:TPU:1", "XLA Ops", [
        ["fusion.2", -50.0, 150.0],       # starts before the window
        ["all-gather-start.5", 300.0, 100.0],
    ])
    return [host, dev0, dev1]


def test_busy_is_the_union_of_op_intervals_in_the_window():
    red = devtrace.reduce(synthetic())
    # dev0: [100, 700] + [900, 1000] = 700 ns; dev1: [0, 100] + [300, 400] = 200 ns
    assert red["busy_s"] == pytest.approx((700 + 200) / 2 / 1e9)
    assert red["window_s"] == pytest.approx(1000 / 1e9)
    assert red["devices"] == 2


def test_collectives_are_attributed_on_the_busiest_collective_device():
    red = devtrace.reduce(synthetic())
    assert red["collective_s"] == pytest.approx(100 / 1e9)
    assert red["collectives"] == 1


def test_enclosing_ops_are_not_counted_twice_in_the_breakdown():
    red = devtrace.reduce(synthetic())
    names = dict(red["device_ops"])
    assert "while.1" not in names
    # fusion.2: dev0 300 + 200, dev1 100 (clipped), mean over two devices
    assert names["fusion.2"] == pytest.approx(600 / 2 / 1e9)


def test_idle_gaps_are_named_by_the_host_span_at_their_middle():
    red = devtrace.reduce(synthetic())
    # busiest device is dev0: gaps [0, 100] and [700, 900]
    assert red["idle_gaps"][0] == ["bench.readback", pytest.approx(200 / 1e9)]
    assert red["idle_gaps"][1] == ["bench.dispatch", pytest.approx(100 / 1e9)]


def test_union_merges_touching_and_nested_intervals():
    assert devtrace.union([(5, 7), (0, 2), (2, 3), (6, 6.5)]) == [[0, 3], [5, 7]]


def test_a_trace_without_device_ops_reads_no_busy_time():
    red = devtrace.reduce([synthetic()[0]])
    assert red["busy_s"] == 0.0 and red["collectives"] == 0


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        devtrace.reduce(synthetic()[1:])


def test_recorded_trace_reduces_within_its_window():
    """30 ms of a traced sec5a_5k.steady_airlock window on one v5e chip.

    The device plane's ``XLA Ops`` line holds the scan's ``while`` op around
    the tick's ops; ops under 20 us were dropped to keep the file small.
    """
    planes = json.loads(RECORDED.read_text())
    red = devtrace.reduce(planes)
    assert red["devices"] == 1
    # the first ~4 ms of the window is the first dispatch, before the program runs
    assert 0.8 * red["window_s"] < red["busy_s"] <= red["window_s"]
    names = [n for n, _ in red["device_ops"]]
    assert not any(n.startswith("while") for n in names)
    assert names[0].startswith("fusion.") and " f32[1048576]" in names[0]
    assert red["collectives"] == 0
    assert red["idle_gaps"][0][0] == "bench.dispatch"


def test_short_op_names():
    name = "%fusion.927 = f32[1048576]{0:T(1024)} fusion(f32[5000]{0} %a), kind=kCustom"
    assert devtrace.short(name) == "fusion.927 f32[1048576]"
    assert devtrace.short("jit_run(123)") == "jit_run(123)"
