"""A cell's files, found by name, and what the harness derives from them.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
configuration is ``configs/<config>.json``, the traffic mix
``traffic/<traffic>.json``, the cell's engine and buffer sizes
``cells/<cell>.json`` and each per-layer metric a reader
``metrics/<metric>.py``. Adding any of them is a new file plus an entry.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import math
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(REPO / "BENCHMARK.json")


def cell(name: str, bench: dict | None = None) -> dict:
    """Everything one cell runs from: its entry and its three files."""
    bench = benchmark() if bench is None else bench
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    entry = entries[0]
    return {
        "name": name,
        "chips": int(entry["chips"]),
        "config": load_json(HERE / "configs" / f"{entry['config']}.json"),
        "traffic": load_json(HERE / "traffic" / f"{entry['traffic']}.json"),
        "cell": load_json(HERE / "cells" / f"{name}.json"),
        "end_to_end": [
            m for m in bench["end_to_end"]
            if name in m.get("workloads", [name])
        ],
        "per_layer": [
            m for m in bench["per_layer"]
            if name in m.get("workloads", [name])
        ],
    }


def laminar_kwargs(spec: dict) -> dict:
    """Keyword arguments of ``LaminarConfig``: configuration, traffic, buffers.

    Nested groups (``workload``, ``memory``) stay dicts; ``build_config``
    turns them into the config module's own dataclasses.
    """
    kw = copy.deepcopy(spec["config"]["laminar"])
    traffic, buffers = spec["traffic"], spec["cell"]
    kw["airlock"] = bool(traffic["airlock"])
    kw["memory"]["enabled"] = bool(traffic["memory_enabled"])
    kw["workload"]["tier_probs"] = list(traffic["tier_mix"])
    kw["probe_capacity"] = int(buffers["probe_capacity"])
    kw["max_arrivals_per_tick"] = int(buffers["max_arrivals_per_tick"])
    return kw


def build_config(module, kw: dict):
    """``module.LaminarConfig`` from ``kw``; ``module`` is a config module."""
    kw = copy.deepcopy(kw)
    tup = lambda d: {k: tuple(v) if isinstance(v, list) else v for k, v in d.items()}
    workload = module.WorkloadConfig(**tup(kw.pop("workload")))
    memory = module.MemoryConfig(**tup(kw.pop("memory")))
    return module.LaminarConfig(workload=workload, memory=memory, **kw)


def cap_floor(lam_per_tick: float) -> int:
    """Least arrival cap that leaves Poisson(lambda) untruncated.

    lambda + 6 sqrt(lambda), rounded up to a multiple of 128.
    """
    x = lam_per_tick
    return int(math.ceil((x + 6.0 * math.sqrt(x)) / 128.0)) * 128


def check_cap(cap: int, lam_per_tick: float) -> None:
    need = cap_floor(lam_per_tick)
    if cap < need:
        raise ValueError(
            f"max_arrivals_per_tick={cap} truncates the offered load: "
            f"lambda={lam_per_tick} needs at least {need}"
        )


def seed_keys(seed: int):
    """(key, sched_key) of a run seed; any non-negative int, 64 bits and up."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)
    return key, jax.random.fold_in(key, 0x5C4ED)


def window_counts(before: dict, after: dict) -> tuple[int, int]:
    """(attempted, failed) between two counter readings.

    Attempted: arrivals offered, admitted or not; failed: arrivals the
    probe table had no slot for.
    """
    failed = int(after["dropped_capacity"]) - int(before["dropped_capacity"])
    attempted = int(after["arrived"]) - int(before["arrived"]) + failed
    return attempted, failed


def reader(name: str):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
