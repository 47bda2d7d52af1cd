"""Sparse per-slot events are accumulated from their compacted rows, exactly.

``events.for_rows`` hands a mask's set rows to a body ``events.row_block``
at a time. The tick's event accumulations (freed atom words, per-tier
counts, latency histograms) run over those rows; the oracle below is the
masked scatter over every row of the table that they replace. The two must
agree bit for bit whatever the number of rows, and a whole engine run must
give the same metrics, tick by tick, with the compacted accumulations as
with the dense ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LaminarConfig, LaminarEngine, MemoryConfig, arbiter, events
from repro.core.config import NUM_TIERS, TIER_NAMES
from repro.core.engine import make_step, summarize
from repro.core.state import (
    ADDRESSING,
    EMPTY,
    HIST_BUCKETS,
    RESERVED,
    RUNNING,
    latency_bucket,
    tier_counts,
)

CFG = LaminarConfig(
    num_nodes=64,
    zone_size=32,
    probe_capacity=4096,
    max_arrivals_per_tick=128,
    horizon_ms=10.0,
)
P, N, W = CFG.probe_capacity, CFG.num_nodes, 2
C = events.row_block(CFG, P)


# ---- the oracle: masked scatters over all P rows ---------------------------


def dense_free(free, alloc, node, mask):
    upd = jnp.where(mask[:, None], alloc, jnp.uint32(0))
    tgt = jnp.where(mask, node, free.shape[0])
    acc = jnp.zeros((free.shape[0] + 1, free.shape[1]), jnp.uint32)
    return free | acc.at[tgt].add(upd)[:-1]


def dense_tiers(tier, mask):
    tgt = jnp.where(mask, tier, NUM_TIERS)
    return jnp.zeros((NUM_TIERS,), jnp.int32).at[tgt].add(
        mask.astype(jnp.int32), mode="drop"
    )


def dense_hists(hist, hist_tier, bucket, tier, mask):
    one = mask.astype(jnp.int32)
    hist = hist.at[jnp.where(mask, bucket, 0)].add(one)
    hist_tier = hist_tier.at[jnp.where(mask, tier, 0), jnp.where(mask, bucket, 0)].add(one)
    return hist, hist_tier


# ---- random tables with disjoint allocations --------------------------------


def table(n: int, seed: int) -> dict:
    """A probe table whose held allocations (``alloc`` at ``alloc_node``,
    ``alloc2`` at ``node2``) are pairwise disjoint, a free-word plane
    disjoint from all of them, and a mask of exactly ``n`` rows."""
    rng = np.random.default_rng(seed)
    node = rng.integers(-1, N, P).astype(np.int32)
    node2 = np.where(rng.uniform(size=P) < 0.3, rng.integers(0, N, P), -1).astype(np.int32)
    alloc = np.zeros((P, 32 * W), bool)
    alloc2 = np.zeros((P, 32 * W), bool)
    held = rng.uniform(size=(N, 32 * W)) < 0.8
    for j in range(N):  # each held atom goes to one holder at its node
        at = np.concatenate([np.flatnonzero(node == j), P + np.flatnonzero(node2 == j)])
        for a in np.flatnonzero(held[j]):
            o = rng.choice(at)
            (alloc if o < P else alloc2)[o % P, a] = True
    free = ~held & (rng.uniform(size=held.shape) < 0.5)
    mask = np.zeros(P, bool)
    mask[rng.choice(P, size=n, replace=False)] = True

    def words(bits):
        return np.packbits(bits.reshape(bits.shape[0], W, 32), axis=-1,
                           bitorder="little").view("<u4")[..., 0]

    return dict(
        free=jnp.asarray(words(free)), alloc=jnp.asarray(words(alloc)),
        node=jnp.asarray(node), alloc2=jnp.asarray(words(alloc2)),
        node2=jnp.asarray(node2), mask=jnp.asarray(mask),
        tier=jnp.asarray(rng.integers(0, NUM_TIERS, P).astype(np.int32)),
        lat=jnp.asarray(rng.integers(0, 4000, P).astype(np.int32)),
        sub=jnp.asarray(rng.uniform(size=P) < 0.5),
    )


COUNTS = {"none": 0, "one": 1, "block-1": C - 1, "block": C, "block+1": C + 1,
          "all": P}


def test_blocks_are_small_enough_for_several_trips():
    assert 1 < C < P // 2


@pytest.mark.parametrize("case", list(COUNTS))
def test_rows_are_the_mask_s_rows_in_ceil_n_over_block_trips(case):
    n = COUNTS[case]
    mask = table(n, seed=n)["mask"]

    def trip(row, on, carry):
        rows, trips = carry
        rows = rows.at[trips].set(jnp.where(on, row, -1))
        return rows, trips + 1

    init = (jnp.full((P // C + 1, C), -1, jnp.int32), jnp.zeros((), jnp.int32))
    rows, trips = jax.jit(lambda m: events.for_rows(m, C, trip, init))(mask)
    assert int(trips) == -(-n // C)
    rows = np.asarray(rows).ravel()
    np.testing.assert_array_equal(rows[rows >= 0], np.flatnonzero(np.asarray(mask)))


ODD_TABLES = {  # (rows, block, set rows): tables that end inside a lookup group
    "short-table": (events.LOOKUP_GROUP // 2 + 3, 16, 40),
    "group+1": (events.LOOKUP_GROUP + 1, 64, events.LOOKUP_GROUP + 1),
    "last-row-only": (3 * events.LOOKUP_GROUP + 7, 32, 1),
    "sparse-odd": (5 * events.LOOKUP_GROUP - 9, 48, 200),
}


@pytest.mark.parametrize("case", list(ODD_TABLES))
def test_rows_of_tables_that_end_inside_a_lookup_group(case):
    rows_n, block, n = ODD_TABLES[case]
    rng = np.random.default_rng(rows_n)
    mask = np.zeros(rows_n, bool)
    if case == "last-row-only":
        mask[-1] = True
    else:
        mask[rng.choice(rows_n, size=n, replace=False)] = True

    def trip(row, on, carry):
        rows, trips = carry
        rows = rows.at[trips].set(jnp.where(on, row, -1))
        return rows, trips + 1

    init = (jnp.full((rows_n // block + 1, block), -1, jnp.int32),
            jnp.zeros((), jnp.int32))
    rows, trips = jax.jit(lambda m: events.for_rows(m, block, trip, init))(
        jnp.asarray(mask))
    assert int(trips) == -(-n // block)
    rows = np.asarray(rows).ravel()
    np.testing.assert_array_equal(rows[rows >= 0], np.flatnonzero(mask))


@pytest.mark.parametrize("case", list(COUNTS))
def test_release_equals_the_dense_scatters(case):
    """Freed words of both allocations, and the per-tier count."""
    n = COUNTS[case]
    t = table(n, seed=100 + n)

    def compact(t):
        return events.release(CFG, t["free"], t["mask"],
                              [(t["alloc"], t["node"]), (t["alloc2"], t["node2"])])

    free = jax.jit(compact)(t)
    want = dense_free(t["free"], t["alloc"], t["node"], t["mask"])
    want = dense_free(want, t["alloc2"], t["node2"], t["mask"] & (t["node2"] >= 0))
    np.testing.assert_array_equal(np.asarray(free), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(tier_counts(t["tier"], t["mask"])),
                                  np.asarray(dense_tiers(t["tier"], t["mask"])))
    m = np.asarray(t["mask"])
    holds = (np.asarray(t["alloc"])[m] != 0).any() or (
        np.asarray(t["alloc2"])[m & (np.asarray(t["node2"]) >= 0)] != 0).any()
    assert (np.asarray(free) != np.asarray(t["free"])).any() == holds


@pytest.mark.parametrize("case", list(COUNTS))
def test_sub_masked_freed_words_and_starts_equal_the_dense_scatters(case):
    """One rank over a union; each accumulation takes its own sub-mask, as
    ``pending_stage`` does: freed words from two masks into one
    accumulator, and both latency histograms of the starts."""
    n = COUNTS[case]
    t = table(n, seed=200 + n)
    now = jnp.int32(4000)
    frees = t["mask"] & t["sub"]
    starts = t["mask"] & ~t["sub"]
    counts0 = (jnp.arange(HIST_BUCKETS, dtype=jnp.int32),
               jnp.ones((NUM_TIERS, HIST_BUCKETS), jnp.int32))

    def compact(t):
        def body(row, on, carry):
            acc, hists = carry
            acc = events.add_freed(acc, t["alloc"][row], t["node"][row], on & frees[row])
            acc = events.add_freed(acc, t["alloc2"][row], t["node2"][row],
                                   on & frees[row] & (t["node2"][row] >= 0))
            hists = events.count_latency(CFG, now, t["lat"][row], t["tier"][row],
                                         on & starts[row], hists)
            return acc, hists

        acc, hists = events.for_rows(t["mask"], C, body,
                                     (events.no_freed(t["free"]), counts0))
        return t["free"] | acc[:-1], hists

    free, (hist, hist_tier) = jax.jit(compact)(t)
    want = dense_free(t["free"], t["alloc"], t["node"], frees)
    want = dense_free(want, t["alloc2"], t["node2"], frees & (t["node2"] >= 0))
    np.testing.assert_array_equal(np.asarray(free), np.asarray(want))
    bucket = latency_bucket((now - t["lat"]).astype(jnp.float32) * CFG.dt_ms)
    want_h, want_ht = dense_hists(counts0[0], counts0[1], bucket, t["tier"], starts)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(want_h))
    np.testing.assert_array_equal(np.asarray(hist_tier), np.asarray(want_ht))
    assert int(np.asarray(hist).sum() - np.asarray(counts0[0]).sum()) == int(starts.sum())


# ---- whole runs: compacted against dense, tick by tick ----------------------

SMALL = dict(num_nodes=128, zone_size=32, probe_capacity=2048,
             max_arrivals_per_tick=128, horizon_ms=40.0, rho=0.9)
MEM = MemoryConfig(enabled=True, burst_rate=0.2, burst_scale=0.5, drift_kappa=0.3)
RUNS = {
    # Exp5's survival deployment: Airlock on, two-phase and regeneration
    # off; windows short enough that reclamations and failed landings occur
    "exp5": dict(memory=MEM, airlock=True, two_phase=False, regeneration=False,
                 hop_loss=0.0, t_susp_ms=1.0, t_surv_ms=6.0, pull_ttl_ms=2.0),
    # the sec. V-A defaults, two-phase and regeneration on; short pull
    # windows and timeouts, so reservations expire and probes time out
    "sec5a": dict(pull_ttl_ms=1.5, task_timeout_ms=6.0),
    # kernel OOM: memory on, Airlock off
    "oom": dict(memory=MEM, airlock=False),
}
SMALL_BLOCK = 16


def dense_for_rows(mask, block, body, init):
    """Every row of the table in one trip, the mask as ``on``: the masked
    scatters over all P rows."""
    return body(jnp.arange(mask.shape[0], dtype=jnp.int32), mask, init)


# The stages as they were before their events were compacted: a masked
# scatter over all P rows for each freed allocation, per-tier count and
# histogram, in the order they were made.


def dense_pending_stage(cfg, s):
    airlock_on = cfg.airlock and cfg.memory.enabled
    reserved = s.st == RESERVED
    timer = jnp.where(reserved, s.timer - 1, s.timer)
    done = reserved & (timer <= 0) & (s.t <= s.pull_deadline)
    expired = reserved & (timer > 0) & (s.t >= s.pull_deadline)

    start_now = done & ~s.migrating
    st = jnp.where(start_now, RUNNING, s.st)
    start = jnp.where(start_now, s.t, s.start)
    patience = jnp.where(start_now, s.patience + s.deposit, s.patience)
    deposit = jnp.where(start_now, 0.0, s.deposit)

    free, alloc, alloc_node = s.free, s.alloc, s.alloc_node
    alloc2, node2 = s.alloc2, s.node2
    migrating = s.migrating
    m = s.metrics
    if airlock_on:
        mig_ok = done & s.migrating & (s.t <= s.surv_deadline)
        mig_late = done & s.migrating & (s.t > s.surv_deadline)
        mig_fail = (expired & s.migrating) | mig_late
        free = dense_free(free, s.alloc, s.alloc_node, mig_ok | mig_fail)
        free = dense_free(free, s.alloc2, s.node2, mig_fail)
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
            reclaimed_tier=m.reclaimed_tier + dense_tiers(s.tier, mig_fail),
        )

    prim_exp = expired & ~s.migrating
    squat_exp = prim_exp & s.squat
    retry = prim_exp & ~s.squat
    free = dense_free(free, s.alloc, s.alloc_node, prim_exp)
    alloc = jnp.where(prim_exp[:, None], jnp.uint32(0), alloc)
    alloc_node = jnp.where(prim_exp, -1, alloc_node)
    deposit = jnp.where(prim_exp, 0.0, deposit)
    st = jnp.where(retry & (patience >= cfg.fastfail_floor), ADDRESSING, st)
    st = jnp.where(retry & (patience < cfg.fastfail_floor), EMPTY, st)
    st = jnp.where(squat_exp, EMPTY, st)

    bucket = latency_bucket((s.t - s.arrival).astype(jnp.float32) * cfg.dt_ms)
    hist, hist_tier = dense_hists(m.lat_hist, m.lat_hist_tier, bucket, s.tier, start_now)
    m = m._replace(
        started=m.started + jnp.sum(start_now.astype(jnp.int32)),
        started_f=m.started_f + jnp.sum((start_now & ~s.contig).astype(jnp.int32)),
        started_l=m.started_l + jnp.sum((start_now & s.contig).astype(jnp.int32)),
        started_tier=m.started_tier + dense_tiers(s.tier, start_now),
        reserve_expired=m.reserve_expired + jnp.sum(prim_exp.astype(jnp.int32)),
        squat_expired=m.squat_expired + jnp.sum(squat_exp.astype(jnp.int32)),
        lat_hist=hist,
        lat_hist_tier=hist_tier,
    )
    return s._replace(
        st=st, timer=timer, start=start, patience=patience, deposit=deposit,
        free=free, alloc=alloc, alloc_node=alloc_node, alloc2=alloc2,
        node2=node2, migrating=migrating, metrics=m,
    )


def dense_completions(cfg, s):
    running = s.st == RUNNING
    service = jnp.where(running, s.service - 1, s.service)
    done = running & (service <= 0)
    m = s.metrics
    m = m._replace(
        completed=m.completed + jnp.sum(done.astype(jnp.int32)),
        completed_f=m.completed_f + jnp.sum((done & ~s.contig).astype(jnp.int32)),
        completed_l=m.completed_l + jnp.sum((done & s.contig).astype(jnp.int32)),
        completed_tier=m.completed_tier + dense_tiers(s.tier, done),
    )
    return s._replace(
        st=jnp.where(done, EMPTY, s.st),
        service=service,
        free=dense_free(s.free, s.alloc, s.alloc_node, done),
        alloc=jnp.where(done[:, None], jnp.uint32(0), s.alloc),
        alloc_node=jnp.where(done, -1, s.alloc_node),
        mem=jnp.where(done, 0.0, s.mem),
        metrics=m,
    )


def run_metrics(cfg: LaminarConfig):
    """(final state, every Metrics field after each tick) of a run."""
    engine = LaminarEngine(cfg)
    s, lam = engine.init(0)
    step = make_step(cfg, lam)

    def tick(s, _):
        s, ts = step(s, None)
        return s, (s.metrics, ts)

    final, (per_tick, ts) = jax.jit(
        lambda s: jax.lax.scan(tick, s, None, length=cfg.num_ticks))(s)
    return final, per_tick, np.asarray(ts)


@pytest.mark.parametrize("run", list(RUNS))
def test_engine_run_equals_the_dense_accumulations_tick_by_tick(run, monkeypatch):
    cfg = LaminarConfig(**SMALL, **RUNS[run])
    monkeypatch.setattr(events, "row_block", lambda cfg, num_slots: SMALL_BLOCK)
    final, per_tick, ts = run_metrics(cfg)
    # the oracle: the dense stages above, and every other release over all P
    # rows at once
    monkeypatch.setattr(arbiter, "pending_stage", dense_pending_stage)
    monkeypatch.setattr(arbiter, "completions", dense_completions)
    monkeypatch.setattr(events, "for_rows", dense_for_rows)
    want_final, want_per_tick, _ = run_metrics(cfg)

    for f in per_tick._fields:
        np.testing.assert_array_equal(np.asarray(getattr(per_tick, f)),
                                      np.asarray(getattr(want_per_tick, f)), err_msg=f)
    for path, a, b in zip(jax.tree_util.tree_flatten_with_path(final)[0],
                          jax.tree.leaves(final), jax.tree.leaves(want_final)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path[0]))

    # the events took several trips of the small block in some tick
    per_tick_done = np.diff(np.asarray(per_tick.completed), prepend=0)
    per_tick_started = np.diff(np.asarray(per_tick.started), prepend=0)
    assert max(per_tick_done.max(), per_tick_started.max()) > 2 * SMALL_BLOCK
    m = per_tick
    if run == "exp5":
        assert int(m.reclaimed[-1]) > 0 and int(m.migrated[-1]) > 0
    if run == "sec5a":
        assert int(m.reserve_expired[-1]) > 0 and int(m.timeout[-1]) > 0
    if run == "oom":
        assert int(m.oom_kill_f[-1] + m.oom_kill_l[-1]) > 0

    # conservation: every started task is accounted for, per tier too, and
    # every atom is free, held once, or was never free (painted rigid)
    out = summarize(cfg, final, ts)
    accounted = (out["completed"] + out["oom_kill_f"] + out["oom_kill_l"]
                 + out["reclaimed"] + out["evicted_killed"] + out["resident_end"])
    assert out["started"] == accounted
    for nm in TIER_NAMES:
        assert out[f"{nm}_started"] == (
            out[f"{nm}_completed"] + out[f"{nm}_oom"] + out[f"{nm}_reclaimed"]
            + out[f"{nm}_evicted_killed"] + out[f"{nm}_resident_end"])
    bits = lambda w: np.unpackbits(np.asarray(w).view(np.uint8), bitorder="little")
    free, free0 = bits(final.free).reshape(cfg.num_nodes, -1), bits(final.free0)
    held = np.zeros_like(free)
    for alloc, node in ((final.alloc, final.alloc_node), (final.alloc2, final.node2)):
        a = bits(alloc).reshape(alloc.shape[0], -1)
        for j in np.flatnonzero(np.asarray(node) >= 0):
            held[np.asarray(node)[j]] += a[j]
    assert held.max(initial=0) <= 1 and not (held & free).any()
    np.testing.assert_array_equal((free | held).ravel(), free0)
