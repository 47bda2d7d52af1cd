"""Device time split by the program's ``laminar.<stage>`` scopes.

Run by hand: ``python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

import tiny
import devtrace
import stages
from test_devtrace import plane

RECORDED = Path(__file__).with_name("data") / "recorded_stages.json"
SCOPES = {
    "while.1": "jit(run)/while",
    "fusion.2": "jit(run)/while/body/closed_call/laminar.addressing/gather",
    "fusion.3": "jit(run)/while/body/closed_call/laminar.arbitration/jit(argsort)/sort",
    "copy.5": "jit(run)/while/body/closed_call/add",
}


def host():
    return plane("/host:CPU", "python", [["bench.window", 0.0, 1000.0]])


def dev0():
    return plane("/device:TPU:0", "XLA Ops", [
        ["%while.1 = (s32[]) while(...)", 100.0, 800.0],   # encloses the ops below
        ["%fusion.2 = f32[8]{0} fusion(...)", 100.0, 200.0],
        ["%fusion.2 = f32[8]{0} fusion(...)", 250.0, 100.0],  # overlaps: [100, 350]
        ["%fusion.3 = s32[8]{0} fusion(...)", 400.0, 200.0],
        ["%copy.4 = s32[8]{0} copy(...)", 600.0, 100.0],     # not in the scope map
        ["%fusion.3 = s32[8]{0} fusion(...)", 950.0, 150.0],  # runs past the window
    ])


def dev1():
    return plane("/device:TPU:1", "XLA Ops", [
        ["%fusion.2 = f32[8]{0} fusion(...)", -100.0, 200.0],  # starts before it
        ["%copy.5 = s32[8]{0} copy(...)", 200.0, 100.0],       # op_name, no stage
    ])


def test_stage_time_counts_the_loop_s_ops_not_the_loop():
    got = stages.stages([host(), dev0()], SCOPES)
    # the while op (800 ns, unscoped) is left out; copy.4 is the only unscoped leaf
    assert got["unscoped"] == pytest.approx(100 / 1e9)
    assert got["addressing"] == pytest.approx(250 / 1e9)


def test_stage_time_is_clipped_to_the_window():
    got = stages.stages([host(), dev0()], SCOPES)
    assert got["arbitration"] == pytest.approx((200 + 50) / 1e9)


def test_stage_time_is_the_mean_over_devices():
    got = stages.stages([host(), dev0(), dev1()], SCOPES)
    assert got == pytest.approx({
        "addressing": (250 + 100) / 2 / 1e9,
        "arbitration": 250 / 2 / 1e9,
        "unscoped": (100 + 100) / 2 / 1e9,
    })


def test_ops_outside_the_scopes_go_to_unscoped():
    assert stages.stage_of(None) == stages.UNSCOPED
    assert stages.stage_of("jit(run)/while/body/add") == stages.UNSCOPED
    assert stages.stage_of("jit(run)/laminar.intake/jit(f)/laminar.arbitration/x") \
        == "intake"


def test_a_program_without_scopes_has_no_stages():
    planes = [host(), dev0()]
    assert stages.stages(planes, {}) is None
    assert stages.stages(planes, {"fusion.2": "jit(run)/while/body/gather"}) is None


def test_a_stage_with_no_ops_is_left_out_of_the_per_tick_numbers():
    got = stages.ms_per_tick({"addressing": 0.2, "survival": 0.0, "unscoped": 0.004}, 40)
    assert got == pytest.approx({"addressing": 5.0, "unscoped": 0.1})


def test_op_scopes_reads_the_optimized_hlo_text():
    text = "\n".join([
        "HloModule jit_run, is_scheduled=true",
        "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
        '  ROOT %gather.3 = f32[8]{0} gather(f32[8]{0} %param_0), '
        'metadata={op_name="jit(run)/laminar.addressing/gather" source_line=7}',
        "}",
        "ENTRY %main.9 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  ROOT %fusion.924 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p), kind=kLoop, "
        'calls=%fused_computation.1, metadata={op_name="jit(run)/laminar.addressing/gather"}',
        "}",
    ])
    assert stages.op_scopes(text) == {
        "gather.3": "jit(run)/laminar.addressing/gather",
        "fusion.924": "jit(run)/laminar.addressing/gather",
    }


def test_recorded_trace_splits_into_the_stages():
    """2 ms of a traced exp5_5k.steady_airlock chunk on one v5e chip, with the
    scope map of its program's optimized HLO (event names cut to 200 chars)."""
    rec = json.loads(RECORDED.read_text())
    planes, scopes = rec["planes"], rec["scopes"]
    got = stages.stages(planes, scopes)
    busy = devtrace.reduce(planes)["busy_s"]
    assert set(got) == {"survival", "completions", "dissemination", "intake",
                        "addressing", "arbitration", "unscoped"}
    assert max(got, key=got.get) == "survival"
    assert sum(got.values()) == pytest.approx(busy, rel=0.02)
    assert got["unscoped"] < 0.05 * busy


def test_measure_runs_a_cell_end_to_end():
    """On the CPU the trace has no TPU plane, so there is no split to give."""
    out = stages.measure(tiny.tiny_spec(), seed=2**33 + 5, require_tpu=False)
    assert out["ticks"] == 20
    assert out["window_ticks_per_s"] > 0
    assert out["device_ms_per_tick"] == 0.0
    assert out["stage_ms_per_tick"] is None
