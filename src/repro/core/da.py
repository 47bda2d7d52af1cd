"""DA: the Decentralized Agent lifecycle (§III-F, §IV-C).

Phase I (kinetic addressing) lives here: connectionless hops with loss,
bounded candidate evaluation against the projected Z-HAF field, single-hop
bounce to j*, patience accounting, Fast-Fail, and TEG-side regeneration of
lost probes (bounded instances, quiet interval).

Phase II (resident sentinel) and Phase III (secondary reactivation) are
state-machine modes handled by ``arbiter``/``airlock``; a migrating DA re-uses
exactly this addressing path (same utility field, same bounded search).

Sharding contract: the probe table is replicated under the zone-sharded
engine, and ``address`` gathers candidates from replicated node-float
arrays (the reported Z-HAF field plus the all-gathered true view), so a
probe can evaluate candidates in ANY zone without cross-shard traffic —
this is what lets probes hop zones every tick while the node-bitmap plane
stays sharded.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import events, hotpath, zhaf
from repro.core.config import LaminarConfig
from repro.core.state import (
    ADDRESSING,
    BOUNCING,
    EMPTY,
    LOST_WAIT,
    QUEUED,
    ROUTING,
    SUSPENDED,
    SimState,
)
from repro.core.utility import unified_utility


def _dissipate_st(st: jax.Array, migrating: jax.Array, mask: jax.Array) -> jax.Array:
    """Kill the control incarnation. A migrating DA reverts to the suspended
    glass-state (the task still awaits T_surv reclamation at the source); an
    ordinary probe's slot is freed."""
    st = jnp.where(mask & migrating, SUSPENDED, st)
    return jnp.where(mask & ~migrating, EMPTY, st)


def move(cfg: LaminarConfig, s: SimState, key: jax.Array) -> Tuple[SimState, jax.Array]:
    """Advance in-flight probes one tick; returns (state, regen_dispatch_mask).

    Packet loss applies to DA *bounce* hops (probe-to-probe fabric traffic).
    The TEG first-hop rides the gateway fleet's load-balanced delivery path
    (endpoint table, §IV-A) and is not subject to the probe loss process.
    """
    k_loss = key
    in_flight = (s.st == ROUTING) | (s.st == BOUNCING)
    timer = jnp.where(in_flight | (s.st == LOST_WAIT), s.timer - 1, s.timer)
    arrived = in_flight & (timer <= 0)

    lost = (
        arrived
        & (s.st == BOUNCING)
        & (jax.random.uniform(k_loss, s.st.shape) < cfg.hop_loss)
    )
    ok = arrived & ~lost

    st = s.st
    st = jnp.where(ok & (s.st == ROUTING), ADDRESSING, st)
    st = jnp.where(ok & (s.st == BOUNCING), QUEUED, st)

    m = s.metrics
    if cfg.regeneration:
        st = jnp.where(lost, LOST_WAIT, st)
        timer = jnp.where(lost, cfg.ticks(cfg.regen_quiet_ms), timer)
    else:
        st = _dissipate_st(st, s.migrating, lost)
        m = m._replace(lost=m.lost + jnp.sum(lost.astype(jnp.int32)))

    # regeneration: quiet interval elapsed -> respawn via TEG (fresh patience,
    # bounded instance count), else exhausted -> dissipate.
    quiet_done = (s.st == LOST_WAIT) & (timer <= 0)
    can_regen = quiet_done & (s.regen < cfg.regen_cap)
    exhausted = quiet_done & ~can_regen
    st = _dissipate_st(st, s.migrating, exhausted)

    regen = jnp.where(can_regen, s.regen + 1, s.regen)
    patience = jnp.where(can_regen, s.ev, s.patience)

    if cfg.regeneration:
        m = m._replace(
            lost=m.lost + jnp.sum(lost.astype(jnp.int32)),
            regen_spawned=m.regen_spawned + jnp.sum(can_regen.astype(jnp.int32)),
            regen_exhausted=m.regen_exhausted
            + jnp.sum(exhausted.astype(jnp.int32)),
        )

    s = s._replace(st=st, timer=timer, regen=regen, patience=patience, metrics=m)
    return s, can_regen


def _decide(
    cfg: LaminarConfig,
    s: SimState,
    view: zhaf.NodeView,
    slot: jax.Array,
    r: jax.Array,
    eps: jax.Array,
):
    """Addressing decisions of the kinetic DAs in ``slot``.

    Returns their new (st, node, zone, timer, patience) and the bounce and
    Fast-Fail masks. Each row reads only its own slot and the node tables,
    so a DA's decision does not depend on which other slots are decided.
    """
    zone_r, node_r = s.zone[slot], s.node[slot]
    zc = jnp.maximum(s.zcount[zone_r], 1).astype(jnp.float32)
    cand = s.zstart[zone_r][:, None] + jnp.floor(r * zc[:, None]).astype(jnp.int32)
    cand = jnp.clip(cand, 0, cfg.num_nodes - 1)
    here = jnp.maximum(node_r, 0)
    cand = jnp.concatenate([here[:, None], cand], axis=1)

    s_eff, h_eff, run_eff = zhaf.project(cfg, s, cand)
    # Candidate 0 is the node the DA is physically standing on: its local
    # T_zone replica is exact for itself (no staleness), so the launchpad is
    # evaluated against TRUE local state — stale false-optimism can only come
    # from remote candidates and is finally rejected at arbitration.
    s_eff = s_eff.at[:, 0].set(view.s_true[here])
    h_eff = h_eff.at[:, 0].set(view.h_true[here])
    run_eff = run_eff.at[:, 0].set(view.run_true[here])
    mass_f = s.mass[slot].astype(jnp.float32)[:, None]
    feas = jnp.where(s.contig[slot][:, None], run_eff >= mass_f, s_eff >= mass_f)

    # fused utility scoring + candidate argmax: the paper's 13.7 ns hot-path
    # op. Symmetry-breaking noise is pre-sampled so kernel and reference see
    # the same eps (Addr_jk = log2(1+S) - gamma*log2(1+H) + eps, masked).
    best, best_score = hotpath.utility_topk(
        cfg, s_eff, h_eff, eps, feas, cfg.gamma_repulsion
    )

    any_feas = jnp.any(feas, axis=1)
    target = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]

    # Controlled sub-optimality: a feasible launchpad is "sufficiently good"
    # unless a remote candidate beats it by more than stay_margin bits.
    here_ok = feas[:, 0]
    here_score = jnp.where(
        here_ok,
        unified_utility(s_eff[:, 0], h_eff[:, 0], cfg.gamma_repulsion) + eps[:, 0],
        -jnp.inf,
    )
    prefer_here = here_ok & (best_score <= here_score + cfg.stay_margin)
    target = jnp.where(prefer_here, here, target)

    stay = any_feas & (target == node_r)
    bounce = any_feas & (target != node_r)

    patience = s.patience[slot] - cfg.eval_cost
    patience = jnp.where(bounce, patience - cfg.bounce_cost, patience)

    st = jnp.where(stay, QUEUED, ADDRESSING)
    st = jnp.where(bounce, BOUNCING, st)
    node = jnp.where(bounce, target, node_r)
    timer = jnp.where(bounce, 1, s.timer[slot])  # single hop
    zone = jnp.where(bounce, s.zone_id[target], zone_r)

    # Fast-Fail: patience below the floor dissipates the probe locally.
    ff = patience < cfg.fastfail_floor
    st = _dissipate_st(st, s.migrating[slot], ff)
    return (st, node, zone, timer, patience), bounce, ff


def address(
    cfg: LaminarConfig, s: SimState, key: jax.Array, view: zhaf.NodeView
) -> SimState:
    """One bounded addressing round for every kinetic DA (st == ADDRESSING).

    Candidate 0 is the current launchpad; k-1 more are sampled uniformly inside
    the Zone. Scores come from the projected Z-HAF field; the stale-view
    feasibility mask (S / max-run vs demand) prunes false candidates. If j* is
    the launchpad we enqueue locally; otherwise one physical bounce.

    Only the addressing slots are decided: ``events.for_rows`` hands them
    over ``events.row_block`` at a time, as many trips as their count needs.
    The random numbers are drawn for the whole table, so every slot sees the
    numbers it would see were all decided.
    """
    P = s.st.shape[0]
    k = cfg.candidate_k
    k_cand, k_noise = jax.random.split(key)
    r = jax.random.uniform(k_cand, (P, k - 1))
    eps = cfg.addr_noise_sigma * jax.random.normal(k_noise, (P, k))

    def trip(row, on, carry):
        fields, n_active, n_bounce, n_ff = carry
        new, bounce, ff = _decide(cfg, s, view, row, r[row], eps[row])
        tgt = jnp.where(on, row, P)  # scatters drop the lanes that are off
        fields = tuple(f.at[tgt].set(v, mode="drop") for f, v in zip(fields, new))
        n_active = n_active + jnp.sum(on.astype(jnp.int32))
        n_bounce = n_bounce + jnp.sum((on & bounce & ~ff).astype(jnp.int32))
        n_ff = n_ff + jnp.sum((on & ff).astype(jnp.int32))
        return fields, n_active, n_bounce, n_ff

    zero = jnp.zeros((), jnp.int32)
    fields = (s.st, s.node, s.zone, s.timer, s.patience)
    fields, n_active, n_bounce, n_ff = events.for_rows(
        s.st == ADDRESSING,
        events.row_block(cfg, P),
        trip,
        (fields, zero, zero, zero),
    )
    st, node, zone, timer, patience = fields

    m = s.metrics
    m = m._replace(
        op_eval=m.op_eval + n_active,
        op_bounce=m.op_bounce + n_bounce,
        fastfail=m.fastfail + n_ff,
    )
    return s._replace(
        st=st, node=node, zone=zone, timer=timer, patience=patience, metrics=m
    )
