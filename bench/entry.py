"""The one place where the benchmark calls into the program under test.

It builds the engine a cell names, its initial state at the configuration's
geometry seed, and the compiled "advance K ticks" program. The engine has no
public call for that yet, so this file uses the engines' private ``_runner``
(one jitted ``lax.scan`` over the tick); nothing else in the benchmark does.
"""

from __future__ import annotations

import jax


def build(cfg, cell: dict, chips: int):
    """The engine that ``cell["engine"]`` names, over ``chips`` devices."""
    if cell["engine"] == "flat":
        from repro.core.engine import LaminarEngine

        return LaminarEngine(cfg)
    if cell["engine"] == "zone_sharded":
        from repro.parallel.engine_mesh import ZoneShardedEngine

        return ZoneShardedEngine(cfg, num_devices=chips)
    raise ValueError(f"unknown engine {cell['engine']!r}")


def place(engine, state):
    """Put the state where the compiled program takes it."""
    mesh = getattr(engine, "mesh", None)
    if mesh is None:
        return jax.device_put(state, jax.devices()[0])
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(state, NamedSharding(mesh, PartitionSpec()))


def chunk(engine, lam: float, ticks: int):
    """``state -> state`` advanced by ``ticks`` ticks, one compiled program."""
    runner = engine._runner(lam, ticks)
    return lambda s: runner(s)[0]
