"""A cell cut to a size the CPU runs in seconds, for the harness's tests."""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spec as bspec  # noqa: E402


def tiny_spec(name: str = "exp5_5k.steady_airlock", devices: int = 1) -> dict:
    spec = bspec.cell(name)
    spec["config"]["laminar"].update(num_nodes=256, zone_size=64)
    spec["cell"] = {
        "engine": "zone_sharded" if devices > 1 else "flat",
        "probe_capacity": 4096,
        "max_arrivals_per_tick": 128,
        "trace_chunks": 1,
    }
    spec["chips"] = devices
    return spec
