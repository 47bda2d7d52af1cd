"""Smoke run of the Laminar engine on a TPU, through its public entry points.

    python chip_smoke.py             # one chip: the paper's §V-A deployment
    python chip_smoke.py --chips 4   # four chips: zone-sharded vs flat engine

One chip (the default) runs the §V-A geometry — 5,000 nodes, 65,536 probe
slots, 512 arrivals per tick, rho = 0.8, the ``storm`` scenario, memory
dynamics and Airlock on, the ``balanced`` tier mix — for 1,000 ticks (0.5 s
of simulated time: the 150 ms probe TTL turns over 3.3 times and the 120 ms
survival TTL 4.2 times). Only the horizon is cut, to keep the whole script
well inside its 1,200 s on one v5e chip.

  (a) ``LaminarEngine.run`` on the default jnp path;
  (b) ``LaminarEngine.run_batch`` over seeds 0 and 1 on the default path;
  (c) (a) again with ``use_pallas=True``: its program must hold
      ``tpu_custom_call`` (the kernels ran natively, not interpreted) and
      every integer counter must equal (a)'s.

``--chips 4`` runs only the zone-sharded engine on four chips and the flat
engine on one, at 32,000 nodes (131,072 slots) for 200 ticks, and requires
equal integer counters. Every run must satisfy the task-conservation
identity, start work, and (Airlock) suspend.

It times nothing: the benchmark (``bench/run.py``) measures speed. The
script exits non-zero, printing no result, unless JAX's first device is a
TPU; the last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
MAIN_TICKS = 1000
MESH_NODES = 32000
MESH_TICKS = 200
MESH_DEVICES = 4


def int_counters(out: dict) -> dict:
    return {
        k: int(v)
        for k, v in out.items()
        if isinstance(v, int) and not isinstance(v, bool)
    }


def check_run(label: str, out: dict, airlock: bool) -> None:
    """Task conservation, non-degeneracy and (Airlock) survival activity."""
    accounted = (
        out["completed"]
        + out["oom_kill_f"]
        + out["oom_kill_l"]
        + out["reclaimed"]
        + out["evicted_killed"]
        + out["resident_end"]
    )
    print(f"{label}: counters {json.dumps(int_counters(out), sort_keys=True)}")
    if out["started"] != accounted:
        raise RuntimeError(
            f"{label}: started={out['started']} != accounted={accounted}"
        )
    if out["started"] <= 0:
        raise RuntimeError(f"{label}: no task started")
    if airlock and out["suspended_cnt"] <= 0:
        raise RuntimeError(f"{label}: Airlock never suspended a task")


def first_diverging_tick(a: dict, b: dict) -> int | None:
    """Earliest tick at which any per-tick counter of the two runs differs."""
    ticks = [
        int(np.flatnonzero(a["timeseries"][f] != b["timeseries"][f])[0])
        for f in a["timeseries"]
        if not np.array_equal(a["timeseries"][f], b["timeseries"][f])
    ]
    return min(ticks, default=None)


def require_equal(label: str, a: dict, b: dict) -> None:
    ca, cb = int_counters(a), int_counters(b)
    diff = {k: (ca[k], cb.get(k)) for k in ca if ca[k] != cb.get(k)}
    print(f"{label}: integer counters equal={not diff} ({len(ca)} compared)")
    if diff or ca.keys() != cb.keys():
        raise RuntimeError(
            f"{label}: counters differ {diff}; first diverging tick "
            f"{first_diverging_tick(a, b)}"
        )


def lowered_text(engine, num_ticks: int) -> str:
    """StableHLO of the engine's compiled scan (lowered, not compiled)."""
    s, lam = engine.init(0)
    return engine._runner(lam, num_ticks).lower(s).as_text()


def print_peak_memory(label: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"{label}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def smoke_cfg(num_nodes: int | None = None):
    from benchmarks.common import bench_cfg
    from repro.core import SCENARIOS, MemoryConfig

    return bench_cfg(
        full=True,
        num_nodes=num_nodes,
        memory=MemoryConfig(enabled=True),
        airlock=True,
        scenario=SCENARIOS["storm"],
    )


def run_one_chip(cfg, num_ticks: int) -> None:
    from repro.core import LaminarEngine

    ref = LaminarEngine(cfg).run(seed=0, num_ticks=num_ticks)
    check_run("jnp run", ref, cfg.airlock)

    outs = LaminarEngine(cfg).run_batch([0, 1], num_ticks=num_ticks)
    for out in outs:
        check_run(f"run_batch seed {out['seed']}", out, cfg.airlock)
    print_peak_memory("after run_batch")

    pal_engine = LaminarEngine(dataclasses.replace(cfg, use_pallas=True))
    pal = pal_engine.run(seed=0, num_ticks=num_ticks)
    check_run("pallas run", pal, cfg.airlock)
    if "tpu_custom_call" not in lowered_text(pal_engine, num_ticks):
        raise RuntimeError("pallas run: no tpu_custom_call in its program")
    print("pallas run: tpu_custom_call present")
    require_equal("pallas vs jnp", pal, ref)
    print_peak_memory("after pallas run")


def run_four_chips(cfg, num_ticks: int, devices: int) -> None:
    from repro.core import LaminarEngine
    from repro.parallel.engine_mesh import ZoneShardedEngine

    mesh_engine = ZoneShardedEngine(cfg, num_devices=devices)
    print(f"traffic_model: {json.dumps(mesh_engine.traffic(seed=0))}")
    mesh = mesh_engine.run(seed=0, num_ticks=num_ticks)
    check_run("sharded run", mesh, cfg.airlock)
    flat = LaminarEngine(cfg).run(seed=0, num_ticks=num_ticks)
    check_run("flat run", flat, cfg.airlock)
    require_equal("sharded vs flat", mesh, flat)
    print_peak_memory("after runs")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, MESH_DEVICES),
        default=1,
        help="4: run only the zone-sharded vs flat comparison on four chips",
    )
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind})"
        )
    count = len(jax.devices())
    if count < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} found only {count} devices")

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro import compile_cache

    print(f"device_kind={dev.device_kind} count={count}")
    print(f"compile cache: {compile_cache.enable()}")
    if args.chips == MESH_DEVICES:
        run_four_chips(smoke_cfg(MESH_NODES), MESH_TICKS, MESH_DEVICES)
    else:
        run_one_chip(smoke_cfg(), MAIN_TICKS)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": count,
                },
            }
        )
    )


if __name__ == "__main__":
    main()
