"""The Exp2 scale-out cell: its tiny cut is correct on four devices, and
its exchange readers read the reduced trace.

Run by hand: ``python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import devtrace
import spec as bspec
from test_devtrace import plane

CELL = "exp2_32k.steady_nomem"
DRIVE = f"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, {str(Path(__file__).resolve().parent)!r})
import tiny, run
r = run.run_cell(tiny.tiny_spec({CELL!r}, devices=4), 2**33 + 15, 1.0, False,
                 require_tpu=False)
print(json.dumps(r))
"""


def test_tiny_cut_is_correct_on_four_devices():
    out = subprocess.run([sys.executable, "-c", DRIVE], capture_output=True,
                         text=True, timeout=600, check=True)
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


def test_cell_runs_the_sharded_engine_at_exp2_geometry():
    c = bspec.cell(CELL)
    assert c["chips"] == 4 and c["cell"]["engine"] == "zone_sharded"
    kw = bspec.laminar_kwargs(c)
    assert (kw["num_nodes"], kw["two_phase"], kw["regeneration"]) == (32000, False, True)
    assert kw["memory"]["enabled"] is False and kw["airlock"] is False
    assert [m["name"] for m in c["per_layer"]][-2:] == [
        "collective_ms_per_tick", "collectives_per_tick"]
    # the one-chip cells run no collective, so they do not report the exchange
    for other in ("exp5_5k.steady_airlock", "sec5a_5k.steady_nomem"):
        names = {m["name"] for m in bspec.cell(other)["per_layer"]}
        assert not names & {"collective_ms_per_tick", "collectives_per_tick"}


def planes(*device_ops) -> list:
    """A traced window of 1,000 ns with one ``XLA Ops`` line per device."""
    host = plane("/host:CPU", "python", [["bench.window", 0.0, 1000.0]])
    return [host] + [plane(f"/device:TPU:{i}", "XLA Ops", ops)
                     for i, ops in enumerate(device_ops)]


EXCHANGE = planes(
    [["%while.1 = (s32[]) while(...)", 100.0, 500.0],   # encloses the ops below
     ["%fusion.2 = f32[8]{0} fusion(...)", 100.0, 200.0],
     ["%all-gather-start.3 = (f32[2], f32[8]) all-gather-start(...)", 300.0, 20.0],
     ["%all-gather-done.3 = f32[8]{0} all-gather-done(...)", 320.0, 80.0],
     ["%fusion.4 = f32[8]{0} fusion(...)", 400.0, 200.0]],
    [["%all-gather.5 = f32[8]{0} all-gather(...)", 100.0, 50.0]],
)


@pytest.mark.parametrize("name,want", [
    # the busiest device in collectives: TPU:0, 100 ns in 2 ops, over 2 ticks
    ("collective_ms_per_tick", 100e-9 * 1e3 / 2),
    ("collectives_per_tick", 2 / 2),
])
def test_exchange_readers(name, want):
    read = bspec.reader(name)
    assert read({"trace": devtrace.reduce(EXCHANGE), "ticks": 2}) == pytest.approx(want)
    no_collective = planes([["%fusion.2 = f32[8]{0} fusion(...)", 100.0, 200.0]])
    assert read({"trace": devtrace.reduce(no_collective), "ticks": 2}) == 0
    assert read({"trace": devtrace.reduce(planes()), "ticks": 2}) is None
