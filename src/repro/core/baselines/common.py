"""Shared substrate for the three optimistic baseline models (§V-A).

All baselines share Laminar's cluster geometry, rigid pre-occupancy, bitmap
allocation machinery, open-loop workload, network ground rules (0.5 ms hop,
10 ms heartbeat) and metrics — only the control path differs. Engineering
inefficiencies of the real systems (etcd fsync, TCP retransmit, GCS
serialization) are deliberately *omitted*: each model is optimistic in favor
of the baseline, so any gap favoring Laminar is a lower bound.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap, events, workload
from repro.core.config import NUM_TIERS, TIER_NAMES, BaselineConfig, LaminarConfig
from repro.core.disrupt import disrupted_capacity
from repro.core.state import (
    HIST_BUCKETS,
    hist_quantile,
    init_state,
    tier_counts,
)
from repro.workloads import schedule as wl_schedule
from repro.workloads.disruption import disruption_step

# task states shared by the baseline models
B_EMPTY = 0
B_QUEUED = 1  # waiting in whichever queue the paradigm uses
B_MOVING = 2  # in-flight redirect / dispatch / rollback hop
B_RUNNING = 3
B_BACKOFF = 4  # retry backoff / rollback backoff


class BaseMetrics(NamedTuple):
    arrived: jax.Array
    started: jax.Array
    completed: jax.Array
    failed: jax.Array
    dropped: jax.Array
    timeout: jax.Array
    retries: jax.Array
    spillbacks: jax.Array
    rollbacks: jax.Array
    # started tasks killed by a hard node failure — the baselines' only
    # post-start death, so it IS their execution-survival numerator. Distinct
    # from ``failed``, which some models also use for pre-start give-ups.
    disrupt_killed: jax.Array
    # per-tier lifecycle counters, (NUM_TIERS,) each
    started_tier: jax.Array
    completed_tier: jax.Array
    disrupt_killed_tier: jax.Array
    lat_hist: jax.Array
    lat_hist_tier: jax.Array  # (NUM_TIERS, HIST_BUCKETS)

    @staticmethod
    def zeros() -> "BaseMetrics":
        z = jnp.zeros((), jnp.int32)
        zt = jnp.zeros((NUM_TIERS,), jnp.int32)
        return BaseMetrics(
            *([z] * 10),
            started_tier=zt,
            completed_tier=zt,
            disrupt_killed_tier=zt,
            lat_hist=jnp.zeros((HIST_BUCKETS,), jnp.int32),
            lat_hist_tier=jnp.zeros((NUM_TIERS, HIST_BUCKETS), jnp.int32),
        )


# BaseMetrics fields that are arrays rather than scalar counters
BASE_VECTOR_FIELDS = (
    "started_tier",
    "completed_tier",
    "disrupt_killed_tier",
    "lat_hist",
    "lat_hist_tier",
)


class TaskTable(NamedTuple):
    st: jax.Array
    contig: jax.Array
    tier: jax.Array  # workload class: 0 prod / 1 batch / 2 best-effort
    mass: jax.Array
    node: jax.Array
    shard: jax.Array
    timer: jax.Array
    retries: jax.Array
    arrival: jax.Array
    service: jax.Array
    alloc: jax.Array  # (P, W)
    alloc_node: jax.Array

    @staticmethod
    def empty(P: int, W: int) -> "TaskTable":
        zi = jnp.zeros((P,), jnp.int32)
        return TaskTable(
            st=zi,
            contig=jnp.zeros((P,), jnp.bool_),
            tier=zi,
            mass=zi,
            node=jnp.full((P,), -1, jnp.int32),
            shard=zi,
            timer=zi,
            retries=zi,
            arrival=zi,
            service=zi,
            alloc=jnp.zeros((P, W), jnp.uint32),
            alloc_node=jnp.full((P,), -1, jnp.int32),
        )


def init_cluster(cfg: LaminarConfig, seed: int):
    """Reuse Laminar's painted post-landing cluster; return (free_words, lam)."""
    s = init_state(cfg, seed)
    free = s.free
    lam = workload.lambda_per_tick(cfg, float(np.asarray(s.rep_S).sum()))
    return free, lam


# ---------------------------------------------------------------------------
# scenario threading (arrival-rate schedule + node disruption): the baselines
# consume the exact same schedule functions and disruption event process as
# the Laminar engine, so scenario sweeps stay head-to-head fair.
# ---------------------------------------------------------------------------


class ScenarioState(NamedTuple):
    """Per-run scenario process state carried through a baseline's scan."""

    sched_key: jax.Array  # per-run arrival-schedule key (constant)
    node_up: jax.Array  # (N,) bool
    down_until: jax.Array  # (N,) i32
    free0: jax.Array  # (N, W) painted bitmap (recovery restore base)


def scenario_init(cfg: LaminarConfig, seed: int, free: jax.Array) -> ScenarioState:
    return ScenarioState(
        sched_key=wl_schedule.schedule_key(seed),
        node_up=jnp.ones((cfg.num_nodes,), jnp.bool_),
        down_until=jnp.zeros((cfg.num_nodes,), jnp.int32),
        free0=free,
    )


def scenario_lam(cfg: LaminarConfig, scen: ScenarioState, lam: float, t: jax.Array):
    """Per-tick arrival intensity under the configured schedule.

    Returns the plain float ``lam`` for the stationary schedule so baseline
    arrival streams stay bit-for-bit identical to the pre-scenario models.
    """
    sched = cfg.scenario.schedule
    if sched.kind == "stationary":
        return lam
    return wl_schedule.rate_per_tick(sched, lam, t, scen.sched_key, cfg.dt_ms)


def scenario_tick(
    cfg: LaminarConfig,
    scen: "ScenarioState",
    tt: TaskTable,
    free: jax.Array,
    m: BaseMetrics,
    t: jax.Array,
    k_dis,
    lam: float,
):
    """One scenario tick for a baseline step: disruption (when enabled,
    ``k_dis`` must be the extra key the step split off) then the scheduled
    per-tick rate. Returns ``(scen, tt, free, m, lam_t)`` — the single
    call every baseline makes, so the threading cannot drift per model."""
    if cfg.scenario.disruption.enabled:
        scen, tt, free, m = scenario_disrupt(cfg, scen, tt, free, m, t, k_dis)
    return scen, tt, free, m, scenario_lam(cfg, scen, lam, t)


def scenario_disrupt(
    cfg: LaminarConfig,
    scen: ScenarioState,
    tt: TaskTable,
    free: jax.Array,
    m: BaseMetrics,
    t: jax.Array,
    key: jax.Array,
):
    """Apply one disruption tick to a baseline's tables.

    Down nodes advertise zero capacity (every admission against them fails
    and flows into the model's own retry/spillback/rollback path); a hard
    failure kills residents outright (counted as ``failed`` — the baselines
    have no survival ladder, which is exactly the contrast Exp6 measures);
    a drain lets residents finish. Recovery restores the painted bitmap
    minus atoms still held by surviving residents.
    """
    d = cfg.scenario.disruption
    N = cfg.num_nodes
    up, down_until, fail, recover = disruption_step(
        d, scen.node_up, scen.down_until, t, key, cfg.dt_ms
    )

    if not d.drain:
        hit = (tt.alloc_node >= 0) & fail[jnp.clip(tt.alloc_node, 0, N - 1)]
        victim = (tt.st == B_RUNNING) & hit
        n_victim = jnp.sum(victim.astype(jnp.int32))
        m = m._replace(
            failed=m.failed + n_victim,
            disrupt_killed=m.disrupt_killed + n_victim,
            disrupt_killed_tier=m.disrupt_killed_tier
            + tier_counts(tt.tier, victim),
        )
        tt = tt._replace(
            st=jnp.where(victim, B_EMPTY, tt.st),
            alloc=jnp.where(victim[:, None], jnp.uint32(0), tt.alloc),
            alloc_node=jnp.where(victim, -1, tt.alloc_node),
        )

    free = disrupted_capacity(free, scen.free0, up, recover, tt.alloc, tt.alloc_node)
    return ScenarioState(scen.sched_key, up, down_until, scen.free0), tt, free, m


def inject(
    cfg: LaminarConfig,
    tt: TaskTable,
    m: BaseMetrics,
    key: jax.Array,
    lam: float,
    t: jax.Array,
) -> Tuple[TaskTable, BaseMetrics, jax.Array]:
    """Write the tick's Poisson arrivals into free slots; returns new-task mask."""
    batch = workload.sample_arrivals(cfg, key, lam)
    n_max = cfg.max_arrivals_per_tick
    want = jnp.arange(n_max) < batch.n
    slots = jnp.nonzero(tt.st == B_EMPTY, size=n_max, fill_value=-1)[0]
    ok = want & (slots >= 0)
    slot = jnp.maximum(slots, 0)
    # scatters drop invalid rows (clamping to 0 could clobber slot 0)
    tgt_s = jnp.where(ok, slot, tt.st.shape[0])

    def put(arr, val):
        return arr.at[tgt_s].set(val, mode="drop")

    tt = tt._replace(
        st=put(tt.st, jnp.full((n_max,), B_QUEUED, jnp.int32)),
        contig=put(tt.contig, batch.contig),
        tier=put(tt.tier, batch.tier),
        mass=put(tt.mass, batch.mass),
        node=put(tt.node, jnp.full((n_max,), -1, jnp.int32)),
        timer=put(tt.timer, jnp.zeros((n_max,), jnp.int32)),
        retries=put(tt.retries, jnp.zeros((n_max,), jnp.int32)),
        arrival=put(tt.arrival, jnp.full((n_max,), 1, jnp.int32) * t),
        service=put(tt.service, batch.service),
        alloc=tt.alloc.at[tgt_s].set(jnp.uint32(0), mode="drop"),
        alloc_node=put(tt.alloc_node, jnp.full((n_max,), -1, jnp.int32)),
    )
    mask = jnp.zeros_like(tt.st, jnp.bool_).at[tgt_s].set(True, mode="drop")
    m = m._replace(
        arrived=m.arrived + jnp.sum(ok.astype(jnp.int32)),
        dropped=m.dropped + (batch.n - jnp.sum(ok.astype(jnp.int32))),
    )
    return tt, m, mask


def admit_fifo(
    cfg: LaminarConfig,
    tt: TaskTable,
    free: jax.Array,
    cand: jax.Array,
    t: jax.Array,
    m: BaseMetrics,
):
    """Admit at most one candidate per node (earliest arrival wins), against
    the true bitmap. Returns (tt, free, m, admit_mask, reject_mask); the
    start counters (global + per-tier) and latency histograms update here —
    the ONE shared admission site — so per-tier accounting cannot drift
    between the three baseline models.
    """
    P = tt.st.shape[0]
    N = cfg.num_nodes
    node_c = jnp.clip(tt.node, 0, N - 1)
    slot = jnp.arange(P, dtype=jnp.int32)

    score = jnp.where(cand, -(tt.arrival.astype(jnp.float32)) * 1e3 - slot.astype(jnp.float32) * 1e-3, -jnp.inf)
    tgt = jnp.where(cand, tt.node, N)
    best = jnp.full((N + 1,), -jnp.inf, jnp.float32).at[tgt].max(score)
    winner = cand & (score == best[jnp.clip(tt.node, 0, N)]) & jnp.isfinite(score)

    wslot = jnp.full((N + 1,), -1, jnp.int32).at[
        jnp.where(winner, tt.node, N)
    ].max(jnp.where(winner, slot, -1))
    has_w = wslot[:N] >= 0
    ws = jnp.clip(wslot[:N], 0, P - 1)

    bits = bitmap.unpack_bits(free, cfg.atoms_per_node)
    alloc_bits, feas_n = bitmap.alloc_for_class(
        bits, tt.mass[ws], tt.contig[ws], policy=cfg.alloc_policy
    )
    feas_n = feas_n & has_w
    taken = alloc_bits & feas_n[:, None]
    alloc_words_n = bitmap.pack_bits(taken)
    free = free & ~alloc_words_n

    admit = winner & feas_n[node_c]
    reject = winner & ~admit

    probe_alloc = alloc_words_n[node_c]
    tt = tt._replace(
        st=jnp.where(admit, B_RUNNING, tt.st),
        alloc=jnp.where(admit[:, None], probe_alloc, tt.alloc),
        alloc_node=jnp.where(admit, tt.node, tt.alloc_node),
    )

    def add_starts(row, on, hists):
        return events.count_latency(cfg, t, tt.arrival[row], tt.tier[row], on, hists)

    hist, hist_tier = events.accumulate(
        cfg, admit, add_starts, (m.lat_hist, m.lat_hist_tier)
    )
    m = m._replace(
        started=m.started + jnp.sum(admit.astype(jnp.int32)),
        started_tier=m.started_tier + tier_counts(tt.tier, admit),
        lat_hist=hist,
        lat_hist_tier=hist_tier,
    )
    return tt, free, m, admit, reject


def complete(cfg: LaminarConfig, tt: TaskTable, free: jax.Array, m: BaseMetrics):
    running = tt.st == B_RUNNING
    service = jnp.where(running, tt.service - 1, tt.service)
    done = running & (service <= 0)
    free = events.release(cfg, free, done, [(tt.alloc, tt.alloc_node)])
    m = m._replace(
        completed=m.completed + jnp.sum(done.astype(jnp.int32)),
        completed_tier=m.completed_tier + tier_counts(tt.tier, done),
    )
    tt = tt._replace(
        st=jnp.where(done, B_EMPTY, tt.st),
        service=service,
        alloc=jnp.where(done[:, None], jnp.uint32(0), tt.alloc),
        alloc_node=jnp.where(done, -1, tt.alloc_node),
    )
    return tt, free, m


def expire(
    cfg: LaminarConfig,
    bcfg: BaselineConfig,
    tt: TaskTable,
    m: BaseMetrics,
    t: jax.Array,
    use_timeout: bool = True,
):
    if not use_timeout:
        return tt, m
    waiting = (tt.st != B_EMPTY) & (tt.st != B_RUNNING)
    late = waiting & ((t - tt.arrival) > cfg.ticks(bcfg.task_timeout_ms))
    m = m._replace(timeout=m.timeout + jnp.sum(late.astype(jnp.int32)))
    return tt._replace(st=jnp.where(late, B_EMPTY, tt.st)), m


def summarize_baseline(cfg: LaminarConfig, m: BaseMetrics, tt: TaskTable):
    mm = jax.tree.map(np.asarray, m)
    arrived = max(int(mm.arrived), 1)
    st = np.asarray(tt.st)
    in_flight = int(((st != B_EMPTY) & (st != B_RUNNING)).sum())
    hist = np.asarray(mm.lat_hist, np.float64)
    total = hist.sum()
    if total > 0:
        p50 = hist_quantile(hist, 0.50)
        p99 = hist_quantile(hist, 0.99)
    else:
        p50 = p99 = float("nan")
    out = {
        **{
            f: int(getattr(mm, f))
            for f in BaseMetrics._fields
            if f not in BASE_VECTOR_FIELDS
        },
        "in_flight_end": in_flight,
        "start_success_ratio": int(mm.started) / max(arrived - in_flight, 1),
        "start_success_raw": int(mm.started) / arrived,
        # offered-load success: queue-capacity drops count against the
        # scheduler ("infinite queuing disabled" -- saturation must show)
        "start_success_total": int(mm.started)
        / max(arrived + int(mm.dropped), 1),
        # mirror of the engine's exec_survival_ratio: node-failure kills are
        # the baselines' only post-start death
        "exec_survival_ratio": 1.0
        - int(mm.disrupt_killed) / max(int(mm.started), 1),
        "p50_ms": p50,
        "p99_ms": p99,
    }
    tier_np = np.asarray(tt.tier)
    resident_tier = np.bincount(
        tier_np[st == B_RUNNING], minlength=NUM_TIERS
    )[:NUM_TIERS]
    for i, nm in enumerate(TIER_NAMES):
        started_i = int(mm.started_tier[i])
        th = np.asarray(mm.lat_hist_tier[i], np.float64)
        out[f"{nm}_started"] = started_i
        out[f"{nm}_completed"] = int(mm.completed_tier[i])
        out[f"{nm}_disrupt_killed"] = int(mm.disrupt_killed_tier[i])
        out[f"{nm}_resident_end"] = int(resident_tier[i])
        out[f"{nm}_survival"] = 1.0 - int(
            mm.disrupt_killed_tier[i]
        ) / max(started_i, 1)
        out[f"{nm}_p50_ms"] = (
            hist_quantile(th, 0.50) if th.sum() > 0 else float("nan")
        )
        out[f"{nm}_p99_ms"] = (
            hist_quantile(th, 0.99) if th.sum() > 0 else float("nan")
        )
    return out
