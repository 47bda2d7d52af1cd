"""A plain simulator of the Laminar deployment, written for the benchmark.

It implements the scheduler's tick from its stated semantics (the paper's
section III and docs/ARCHITECTURE.md): Airlock's memory ladder,
completions, Z-HAF reports, the gateway's zone routing, probe hops with
loss and regeneration, candidate addressing, per-node arbitration with
best-fit atom placement, the two-phase pending stage and the absolute
timeout. It imports nothing of the program. Where the program packs bits,
elects by chains of scatter-max or densifies zones, this code keeps a
boolean atom plane, elects by a lexicographic sort, places atoms by ranking
and sums zones by segment. It draws the same random numbers from the same
keys, so the same seed gives the same run; it covers the benchmark's
deployments only (stationary arrivals, no node failures, best-fit
placement, no squatters).
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from . import codes, geometry, tick


class Config(SimpleNamespace):
    """The configuration's values by name, with its two nested groups."""

    def __init__(self, kw: dict):
        kw = dict(kw)
        super().__init__(**kw)
        self.wl = SimpleNamespace(**kw["workload"])
        self.mem = SimpleNamespace(**kw["memory"])
        if self.alloc_policy != "best":
            raise ValueError("the reference places atoms best-fit only")
        if self.wl.squatter_ratio != 0.0:
            raise ValueError("the reference runs no squatters")
        if self.mem.enabled and not self.airlock:
            raise ValueError("the reference runs memory dynamics with Airlock only")

    def ticks(self, ms: float) -> int:
        return max(1, int(round(ms / self.dt_ms)))


def arrivals_per_tick(cfg: Config, free_atoms: float) -> float:
    """Poisson rate per tick at which the free atoms are loaded to rho."""
    wl = cfg.wl
    f_mass = sum(m * p for m, p in zip(wl.f_masses, wl.f_mass_probs))
    l_mass = sum(m * p for m, p in zip(wl.l_masses, wl.l_mass_probs))
    l_mean_ms = wl.l_service_median_ms * math.exp(0.5 * wl.l_service_sigma**2)
    atom_s = (wl.f_share * (wl.f_service_mean_ms / 1e3) * f_mass
              + (1.0 - wl.f_share) * (l_mean_ms / 1e3) * l_mass)
    per_s = cfg.rho * (free_atoms / atom_s)
    return per_s * cfg.dt_ms / 1e3


def initial(kw: dict, geometry_seed: int, key) -> tuple[Config, dict, float]:
    """(config, state on the host, arrivals per tick) for one run key."""
    cfg = Config(kw)
    state, free_atoms = geometry.initial_state(cfg, geometry_seed)
    state["key"] = np.asarray(key)
    state["metrics"] = {
        **{k: np.int32(0) for k in codes.COUNTERS},
        **{k: np.zeros(codes.NUM_TIERS, np.int32) for k in codes.TIER_COUNTERS},
        "lat_hist": np.zeros(codes.HIST_BUCKETS, np.int32),
        "lat_hist_tier": np.zeros((codes.NUM_TIERS, codes.HIST_BUCKETS), np.int32),
    }
    return cfg, state, arrivals_per_tick(cfg, free_atoms)


def runner(cfg: Config, lam: float, ticks: int, after_tick=None):
    """``state -> state`` over ``ticks`` ticks, one compiled scan.

    ``after_tick`` maps the state after each tick (the control rounds it).
    """
    after_tick = after_tick or (lambda s: s)

    def body(s, _):
        return after_tick(tick.step(cfg, lam, s)), None

    return jax.jit(lambda s: jax.lax.scan(body, s, None, length=ticks)[0])


def to_device(state: dict, device=None) -> dict:
    return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), device), state)
