"""Node Arbitrator (§III-G, §IV-D).

The node layer is Laminar's atomic correctness boundary: admission closes at a
single node through

  1. a pre-admission physical check (memory watermark -> throttle),
  2. winner selection among queued DAs by static routing weight E_v,init,
  3. feasibility against the *true* residual resource bitmap
     (false optimism from stale views is rejected here, never propagated),
  4. a TTL-bounded logical reservation with a frozen patience deposit,
  5. payload pull within the valid window -> execution start,
  6. timing-wheel expiry: reservation removal, bitmap restore, deposit forfeit.

The same two-phase discipline closes secondary (migration) landings: a
reactivated DA's win creates a destination reservation in ``alloc2``; the new
execution epoch is recognized only after the suspended state is pulled within
both the destination window and the shared survival TTL.

Implementation note: arbitration is computed *per node* (one winner per node
per tick), so all bitmap work is (N, A)-shaped, never (P, A)-shaped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitmap, events, hotpath
from repro.core.config import LaminarConfig
from repro.core.state import (
    ADDRESSING,
    EMPTY,
    QUEUED,
    RESERVED,
    RUNNING,
    SimState,
    tier_counts,
)

INF_TICK = jnp.int32(1 << 30)


def arbitrate(
    cfg: LaminarConfig,
    s: SimState,
    key: jax.Array,
    throttled: jax.Array,
    bits: jax.Array,
    plane=None,
):
    """One admission round per node: highest-E_v queued DA, bitmap-feasible.

    Takes and returns the (N, A) free bit-plane so multiple rounds per tick
    avoid re-unpacking the word bitmap. Returns (state, bits').

    ``plane`` routes the node-plane math (feasibility + allocation on the
    bit plane) through a strategy object: the zone-sharded engine computes
    it on its local zone block and exchanges only the per-node result words
    (``repro.parallel.engine_mesh.MeshPlane``). With ``plane=None`` the math
    runs inline on the flat (N, A) plane — today's path, bit for bit; in
    that case ``bits`` is the flat plane, otherwise it is whatever blocked
    representation the plane threads across rounds."""
    P = s.st.shape[0]
    N = cfg.num_nodes
    node_c = jnp.clip(s.node, 0, N - 1)

    queued = (s.st == QUEUED) & ~throttled[node_c]
    # winner by E_v with an exact integer tiebreak: encode (E_v, slot) into an
    # int64-safe int32 pair via two-stage scatter-max — E_v ties must still
    # elect exactly ONE probe per node, or atoms would be double-assigned.
    slot = jnp.arange(P, dtype=jnp.int32)
    score = jnp.where(queued, s.ev, -jnp.inf)
    tgt = jnp.where(queued, s.node, N)
    best = jnp.full((N + 1,), -jnp.inf, jnp.float32).at[tgt].max(score)
    top_ev = queued & (score == best[jnp.clip(s.node, 0, N)]) & jnp.isfinite(score)
    # among equal-E_v toppers, take the max slot index (unique per node)
    wslot = jnp.full((N + 1,), -1, jnp.int32).at[
        jnp.where(top_ev, s.node, N)
    ].max(jnp.where(top_ev, slot, -1))
    winner = top_ev & (slot == wslot[jnp.clip(s.node, 0, N)])
    has_w = wslot[:N] >= 0
    ws = jnp.clip(wslot[:N], 0, P - 1)

    # feasibility against the TRUE residual bitmap, computed once per node
    # for its winner's demand — the paper's 4.02 ns bitmap-check hot op,
    # routed through the dispatch layer so engine runs exercise (and
    # benchmarks measure) the same code path as the standalone kernels.
    # The pallas path runs the word-level kernel on ``s.free`` (kept in
    # sync with the ``bits`` plane across rounds); the jnp path reuses the
    # threaded bit plane so no round re-unpacks the words. For winner rows
    # feas_hot agrees with the allocation routines' internal feasibility
    # (the parity tests enforce it); the AND is a guard so a kernel
    # regression could only reject admissions, never reserve a probe with
    # an empty atom mask.
    if plane is None:
        feas_hot = (
            hotpath.bitmap_fit(cfg, s.free, s.mass[ws], s.contig[ws], bits=bits) != 0
        )
        alloc_bits, feas_n = bitmap.alloc_for_class(
            bits, s.mass[ws], s.contig[ws], policy=cfg.alloc_policy
        )
        feas_n = feas_n & feas_hot & has_w
        taken = alloc_bits & feas_n[:, None]
        alloc_words_n = bitmap.pack_bits(taken)
        bits = bits & ~taken
    else:
        alloc_words_n, feas_n, bits = plane.alloc_round(cfg, s, bits, ws, has_w)
    free = s.free & ~alloc_words_n

    admit = winner & feas_n[node_c]
    reject = winner & ~admit

    # --- state transitions ---------------------------------------------
    st = s.st
    migrating = s.migrating
    probe_alloc = alloc_words_n[node_c]  # (P, W) gather

    # ordinary admission -> two-phase reservation (atoms held logically)
    prim = admit & ~migrating
    if cfg.two_phase:
        dep = jnp.minimum(cfg.deposit, jnp.maximum(s.patience, 0.0))
    else:
        dep = jnp.zeros_like(s.patience)
    patience = jnp.where(prim, s.patience - dep, s.patience)
    deposit = jnp.where(prim, dep, s.deposit)

    st = jnp.where(prim, RESERVED, st)
    alloc = jnp.where(prim[:, None], probe_alloc, s.alloc)
    alloc_node = jnp.where(prim, s.node, s.alloc_node)
    squatting = s.squat if cfg.workload.squatter_ratio > 0 else jnp.zeros_like(s.squat)
    timer = jnp.where(prim, jnp.where(squatting, INF_TICK, s.pull_dur), s.timer)
    pull_deadline = jnp.where(
        prim,
        (s.t + cfg.ticks(cfg.pull_ttl_ms)) if cfg.two_phase else INF_TICK,
        s.pull_deadline,
    )

    # migration landing -> destination reservation in alloc2 (state pull)
    alloc2, node2 = s.alloc2, s.node2
    if cfg.airlock and cfg.memory.enabled:
        mig = admit & migrating
        st = jnp.where(mig, RESERVED, st)
        alloc2 = jnp.where(mig[:, None], probe_alloc, s.alloc2)
        node2 = jnp.where(mig, s.node, s.node2)
        state_pull = (
            jnp.ceil(
                s.mass.astype(jnp.float32) * cfg.state_pull_ms_per_atom / cfg.dt_ms
            ).astype(jnp.int32)
            + 1
        )
        timer = jnp.where(mig, state_pull, timer)
        pull_deadline = jnp.where(
            mig, s.t + cfg.ticks(cfg.pull_ttl_ms), pull_deadline
        )

    # infeasible winner: pay a re-address, return to kinetic addressing
    st = jnp.where(reject, ADDRESSING, st)
    patience = jnp.where(reject, patience - cfg.eval_cost, patience)

    m = s.metrics
    m = m._replace(
        op_arb=m.op_arb + jnp.sum(winner.astype(jnp.int32)),
        infeasible_winner=m.infeasible_winner + jnp.sum(reject.astype(jnp.int32)),
        throttled_rounds=m.throttled_rounds + jnp.sum(throttled.astype(jnp.int32)),
    )
    s = s._replace(
        st=st,
        free=free,
        alloc=alloc,
        alloc_node=alloc_node,
        alloc2=alloc2,
        node2=node2,
        timer=timer,
        patience=patience,
        deposit=deposit,
        pull_deadline=pull_deadline,
        metrics=m,
    )
    return s, bits


def pending_stage(cfg: LaminarConfig, s: SimState) -> SimState:
    """Payload / state pull progress, execution start, reservation expiry."""
    airlock_on = cfg.airlock and cfg.memory.enabled
    reserved = s.st == RESERVED
    timer = jnp.where(reserved, s.timer - 1, s.timer)

    done = reserved & (timer <= 0) & (s.t <= s.pull_deadline)
    expired = reserved & (timer > 0) & (s.t >= s.pull_deadline)

    # ---- primary landing: execution start ------------------------------
    start_now = done & ~s.migrating
    st = jnp.where(start_now, RUNNING, s.st)
    start = jnp.where(start_now, s.t, s.start)
    patience = jnp.where(start_now, s.patience + s.deposit, s.patience)  # unfreeze
    deposit = jnp.where(start_now, 0.0, s.deposit)

    alloc, alloc_node = s.alloc, s.alloc_node
    alloc2, node2 = s.alloc2, s.node2
    migrating = s.migrating
    m = s.metrics
    prim_exp = expired & ~s.migrating
    frees = prim_exp  # the slots whose ``alloc`` returns to the free words

    # ---- migration landing: new execution epoch recognized --------------
    if airlock_on:
        mig_ok = done & s.migrating & (s.t <= s.surv_deadline)
        mig_late = done & s.migrating & (s.t > s.surv_deadline)
        mig_fail = (expired & s.migrating) | mig_late
        # source freed on success; both sides freed on bounded reclamation
        frees = frees | mig_ok | mig_fail
        alloc = jnp.where(mig_ok[:, None], s.alloc2, alloc)
        alloc = jnp.where(mig_fail[:, None], jnp.uint32(0), alloc)
        alloc_node = jnp.where(mig_ok, s.node2, alloc_node)
        alloc_node = jnp.where(mig_fail, -1, alloc_node)
        alloc2 = jnp.where((mig_ok | mig_fail)[:, None], jnp.uint32(0), alloc2)
        node2 = jnp.where(mig_ok | mig_fail, -1, node2)
        st = jnp.where(mig_ok, RUNNING, st)
        st = jnp.where(mig_fail, EMPTY, st)
        migrating = jnp.where(mig_ok | mig_fail, False, migrating)
        m = m._replace(
            migrated=m.migrated + jnp.sum(mig_ok.astype(jnp.int32)),
            reclaimed=m.reclaimed + jnp.sum(mig_fail.astype(jnp.int32)),
            reclaimed_tier=m.reclaimed_tier + tier_counts(s.tier, mig_fail),
        )

    # ---- primary reservation expiry --------------------------------------
    # restore bitmap, forfeit deposit, re-address (or dissipate)
    squat_exp = prim_exp & s.squat
    retry = prim_exp & ~s.squat
    alloc = jnp.where(prim_exp[:, None], jnp.uint32(0), alloc)
    alloc_node = jnp.where(prim_exp, -1, alloc_node)
    deposit = jnp.where(prim_exp, 0.0, deposit)  # forfeited
    st = jnp.where(retry & (patience >= cfg.fastfail_floor), ADDRESSING, st)
    st = jnp.where(retry & (patience < cfg.fastfail_floor), EMPTY, st)
    st = jnp.where(squat_exp, EMPTY, st)

    # ---- freed atoms and latency histograms --------------------------------
    # every row reads the stage's incoming allocations, so the expiries and
    # landings share one accumulator of freed words
    def add_events(row, on, carry):
        acc, hists = carry
        acc = events.add_freed(acc, s.alloc[row], s.alloc_node[row], on & frees[row])
        if airlock_on:
            fail = on & mig_fail[row]
            acc = events.add_freed(acc, s.alloc2[row], s.node2[row], fail)
        hists = events.count_latency(
            cfg, s.t, s.arrival[row], s.tier[row], on & start_now[row], hists
        )
        return acc, hists

    acc, (hist, hist_tier) = events.accumulate(
        cfg,
        frees | start_now,
        add_events,
        (events.no_freed(s.free), (m.lat_hist, m.lat_hist_tier)),
    )
    free = s.free | acc[:-1]

    # ---- metrics ----------------------------------------------------------
    m = m._replace(
        started=m.started + jnp.sum(start_now.astype(jnp.int32)),
        started_f=m.started_f + jnp.sum((start_now & ~s.contig).astype(jnp.int32)),
        started_l=m.started_l + jnp.sum((start_now & s.contig).astype(jnp.int32)),
        started_tier=m.started_tier + tier_counts(s.tier, start_now),
        reserve_expired=m.reserve_expired + jnp.sum(prim_exp.astype(jnp.int32)),
        squat_expired=m.squat_expired + jnp.sum(squat_exp.astype(jnp.int32)),
        lat_hist=hist,
        lat_hist_tier=hist_tier,
    )
    return s._replace(
        st=st,
        timer=timer,
        start=start,
        patience=patience,
        deposit=deposit,
        free=free,
        alloc=alloc,
        alloc_node=alloc_node,
        alloc2=alloc2,
        node2=node2,
        migrating=migrating,
        metrics=m,
    )


def completions(cfg: LaminarConfig, s: SimState) -> SimState:
    """Service progress; normal completion retires the resident DA with it."""
    running = s.st == RUNNING
    service = jnp.where(running, s.service - 1, s.service)
    done = running & (service <= 0)

    free = events.release(cfg, s.free, done, [(s.alloc, s.alloc_node)])
    m = s.metrics
    n_done = jnp.sum(done.astype(jnp.int32))
    m = m._replace(
        completed=m.completed + n_done,
        completed_f=m.completed_f + jnp.sum((done & ~s.contig).astype(jnp.int32)),
        completed_l=m.completed_l + jnp.sum((done & s.contig).astype(jnp.int32)),
        completed_tier=m.completed_tier + tier_counts(s.tier, done),
    )
    return s._replace(
        st=jnp.where(done, EMPTY, s.st),
        service=service,
        free=free,
        alloc=jnp.where(done[:, None], jnp.uint32(0), s.alloc),
        alloc_node=jnp.where(done, -1, s.alloc_node),
        mem=jnp.where(done, 0.0, s.mem),
        metrics=m,
    )


def timeouts(cfg: LaminarConfig, s: SimState) -> SimState:
    """Absolute arrival->start timeout for control-phase probes (not running,
    not suspended/migrating: those are governed by T_susp / T_surv)."""
    from repro.core.state import LOST_WAIT  # local import to avoid cycle noise

    control = (((s.st > EMPTY) & (s.st < RUNNING)) | (s.st == LOST_WAIT)) & ~s.migrating
    late = control & ((s.t - s.arrival) > cfg.ticks(cfg.task_timeout_ms))
    # RESERVED probes may hold atoms: restore
    free = events.release(cfg, s.free, late, [(s.alloc, s.alloc_node)])
    m = s.metrics
    m = m._replace(timeout=m.timeout + jnp.sum(late.astype(jnp.int32)))
    return s._replace(
        st=jnp.where(late, EMPTY, s.st),
        free=free,
        alloc=jnp.where(late[:, None], jnp.uint32(0), s.alloc),
        alloc_node=jnp.where(late, -1, s.alloc_node),
        metrics=m,
    )
