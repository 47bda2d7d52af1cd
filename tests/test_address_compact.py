"""DA addressing decides only the addressing slots, and decides them exactly.

``da.address`` compacts the slots with ``st == ADDRESSING`` and decides them
``events.row_block`` at a time. ``full_table_address`` below is the
addressing round that decides every slot of the table and keeps the
results of the addressing ones; the two must return the same ``SimState``,
every field and counter bit for bit, whatever the number of addressing
slots and whichever ``utility_topk`` path runs.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LaminarConfig, LaminarEngine, da, events, hotpath, zhaf
from repro.core.engine import summarize
from repro.core.state import ADDRESSING, BOUNCING, EMPTY, QUEUED, SUSPENDED, SimState
from repro.core.utility import unified_utility

CFG = LaminarConfig(
    num_nodes=128,
    zone_size=32,
    probe_capacity=2048,
    max_arrivals_per_tick=128,
    horizon_ms=10.0,
    rho=0.8,
)
P = CFG.probe_capacity
C = events.row_block(CFG, P)


def full_table_address(cfg, s, key, view):
    """Every slot decided, the addressing slots' results kept."""
    P = s.st.shape[0]
    k = cfg.candidate_k
    k_cand, k_noise = jax.random.split(key)

    active = s.st == ADDRESSING

    zc = jnp.maximum(s.zcount[s.zone], 1).astype(jnp.float32)
    r = jax.random.uniform(k_cand, (P, k - 1))
    cand = s.zstart[s.zone][:, None] + jnp.floor(r * zc[:, None]).astype(jnp.int32)
    cand = jnp.clip(cand, 0, cfg.num_nodes - 1)
    cand = jnp.concatenate([jnp.maximum(s.node, 0)[:, None], cand], axis=1)

    s_eff, h_eff, run_eff = zhaf.project(cfg, s, cand)
    here = jnp.maximum(s.node, 0)
    s_eff = s_eff.at[:, 0].set(view.s_true[here])
    h_eff = h_eff.at[:, 0].set(view.h_true[here])
    run_eff = run_eff.at[:, 0].set(view.run_true[here])
    mass_f = s.mass.astype(jnp.float32)[:, None]
    feas = jnp.where(s.contig[:, None], run_eff >= mass_f, s_eff >= mass_f)

    eps = cfg.addr_noise_sigma * jax.random.normal(k_noise, s_eff.shape)
    best, best_score = hotpath.utility_topk(
        cfg, s_eff, h_eff, eps, feas, cfg.gamma_repulsion
    )

    any_feas = jnp.any(feas, axis=1)
    target = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]

    here_ok = feas[:, 0]
    here_score = jnp.where(
        here_ok,
        unified_utility(s_eff[:, 0], h_eff[:, 0], cfg.gamma_repulsion) + eps[:, 0],
        -jnp.inf,
    )
    prefer_here = here_ok & (best_score <= here_score + cfg.stay_margin)
    target = jnp.where(prefer_here, jnp.maximum(s.node, 0), target)

    stay = active & any_feas & (target == s.node)
    bounce = active & any_feas & (target != s.node)

    patience = jnp.where(active, s.patience - cfg.eval_cost, s.patience)
    patience = jnp.where(bounce, patience - cfg.bounce_cost, patience)

    st = jnp.where(stay, QUEUED, s.st)
    st = jnp.where(bounce, BOUNCING, st)
    node = jnp.where(bounce, target, s.node)
    timer = jnp.where(bounce, 1, s.timer)
    zone = jnp.where(bounce, s.zone_id[target], s.zone)

    ff = active & (patience < cfg.fastfail_floor)
    st = jnp.where(ff & s.migrating, SUSPENDED, st)
    st = jnp.where(ff & ~s.migrating, EMPTY, st)

    m = s.metrics
    m = m._replace(
        op_eval=m.op_eval + jnp.sum(active.astype(jnp.int32)),
        op_bounce=m.op_bounce + jnp.sum((bounce & ~ff).astype(jnp.int32)),
        fastfail=m.fastfail + jnp.sum(ff.astype(jnp.int32)),
    )
    return s._replace(
        st=st, node=node, zone=zone, timer=timer, patience=patience, metrics=m
    )


@pytest.fixture(scope="module")
def warm_state():
    """A state 20 ticks into a run: real reports, heat and zones."""
    engine = LaminarEngine(CFG)
    s, lam = engine.init(0)
    return engine._runner(lam, 20)(s)[0]


def with_addressing(s: SimState, n: int, migrating: bool, seed: int) -> SimState:
    """``s`` with exactly ``n`` addressing slots, each at a random node of
    its zone with a random demand and patience (some below the Fast-Fail
    floor); a third of them migrating DAs when ``migrating``."""
    rng = np.random.default_rng(seed)
    st = np.asarray(s.st).copy()
    st[st == ADDRESSING] = QUEUED
    pick = rng.choice(P, size=n, replace=False)
    st[pick] = ADDRESSING
    node = rng.integers(0, CFG.num_nodes, P).astype(np.int32)
    node[rng.uniform(size=P) < 0.05] = -1
    zone = np.asarray(s.zone_id)[np.maximum(node, 0)]
    mig = np.zeros(P, bool)
    if migrating:
        mig[pick[: max(n // 3, 1)]] = True
    return s._replace(
        st=jnp.asarray(st),
        node=jnp.asarray(node),
        zone=jnp.asarray(zone),
        mass=jnp.asarray(rng.integers(1, CFG.atoms_per_node + 1, P).astype(np.int32)),
        contig=jnp.asarray(rng.uniform(size=P) < 0.4),
        patience=jnp.asarray(rng.uniform(-2.0, 40.0, P).astype(np.float32)),
        timer=jnp.asarray(rng.integers(0, 5, P).astype(np.int32)),
        migrating=jnp.asarray(mig),
    )


CASES = {
    "none": (0, False),
    "one": (1, False),
    "block-1": (C - 1, False),
    "block": (C, False),
    "block+1": (C + 1, False),
    "all": (P, False),
    "migrating": (C + 1, True),
}


@pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("case", list(CASES))
def test_compacted_address_equals_full_table(warm_state, case, pallas):
    assert 1 < C < P  # block+1 and all need a second trip
    n, migrating = CASES[case]
    cfg = dataclasses.replace(CFG, use_pallas=pallas)
    s = with_addressing(warm_state, n, migrating, seed=n)
    view = zhaf.build_view(cfg, s)
    key = jax.random.PRNGKey(n + 17)

    got = jax.jit(lambda s, k: da.address(cfg, s, k, view))(s, key)
    want = jax.jit(lambda s, k: full_table_address(cfg, s, k, view))(s, key)

    assert int(got.metrics.op_eval - s.metrics.op_eval) == n
    for path, a, b in zip(
        jax.tree_util.tree_flatten_with_path(got)[0],
        jax.tree.leaves(got),
        jax.tree.leaves(want),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path[0])
        )
    # each addressing DA paid for its evaluation, and no other slot did
    assert int(jnp.sum(got.patience != s.patience)) == n


def test_address_block_is_derived_from_the_arrival_cap():
    assert events.row_block(CFG, P) == 1024
    big = dataclasses.replace(CFG, max_arrivals_per_tick=1664)
    assert events.row_block(big, 131072) == 4096
    assert events.row_block(big, 65536) == 4096
    assert events.row_block(big, 2048) == 2048  # capped at the table


def test_run_batch_seeds_with_different_trip_counts_match_their_runs():
    """Under ``run_batch`` the block loop runs as many trips as the busiest
    seed needs; every other seed must still get its own run's result."""
    cfg = LaminarConfig(
        num_nodes=64,
        zone_size=32,
        probe_capacity=16384,
        max_arrivals_per_tick=512,
        horizon_ms=40.0,
        rho=20.0,
    )
    block = events.row_block(cfg, cfg.probe_capacity)
    seeds = [0, 1, 2]
    engine = LaminarEngine(cfg)
    outs = engine.run_batch(seeds)

    trips = np.stack([
        -(-np.diff(o["timeseries"]["op_eval"], prepend=0) // block) for o in outs
    ])
    assert trips.max() >= 2
    assert (trips.min(axis=0) != trips.max(axis=0)).sum() >= 3

    batch, lam = engine.init_batch(seeds)
    runner = engine._runner(lam, cfg.num_ticks)
    for i, out in enumerate(outs):
        final, ts = runner(jax.tree.map(lambda x, i=i: x[i], batch))
        single = summarize(cfg, final, np.asarray(ts))
        assert out["addr_trips_max"] == single["addr_trips_max"] == trips[i].max()
        for k, v in single.items():
            if k == "timeseries":
                for f in v:
                    np.testing.assert_array_equal(out[k][f], v[f], err_msg=f)
            elif k == "lat_hist":
                np.testing.assert_array_equal(out[k], v)
            elif isinstance(v, float) and np.isnan(v):
                assert np.isnan(out[k]), k
            else:
                assert out[k] == v, (k, out[k], v)
