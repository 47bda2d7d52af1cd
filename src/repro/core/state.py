"""Engine state: a fixed-shape, fully-vectorized pytree.

The Laminar engine is a *tick-synchronous* reformulation of the paper's
discrete-event simulator: every control object (probe / DA) occupies a slot in
a structure-of-arrays table and advances its own state machine each tick; all
node-level work is expressed as segmented reductions over those arrays. This is
the JAX-native adaptation — no event heap, everything `lax.scan`-able.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap
from repro.core.config import NUM_TIERS, LaminarConfig

# ---------------------------------------------------------------------------
# probe (DA) state machine codes
# ---------------------------------------------------------------------------
EMPTY = 0  # free slot
ROUTING = 1  # dispatched by TEG, in flight to launchpad
ADDRESSING = 2  # at a node, evaluating Z-HAF candidates (kinetic DA)
BOUNCING = 3  # single-hop physical redirection to j*
QUEUED = 4  # in a node's arbitration queue (counts toward Heat)
RESERVED = 5  # won arbitration; two-phase pending stage (payload pull)
RUNNING = 6  # executing; DA is a resident sentinel
SUSPENDED = 7  # Airlock glass-state (in-situ window T_susp)
LOST_WAIT = 8  # control packet lost; awaiting regeneration quiet interval
NUM_STATES = 9

LIVE_CONTROL = (ROUTING, ADDRESSING, BOUNCING, QUEUED, RESERVED, LOST_WAIT)


class Metrics(NamedTuple):
    arrived: jax.Array
    arrived_f: jax.Array
    arrived_l: jax.Array
    arrived_squat: jax.Array
    dropped_capacity: jax.Array
    started: jax.Array
    started_f: jax.Array
    started_l: jax.Array
    completed: jax.Array
    completed_f: jax.Array
    completed_l: jax.Array
    fastfail: jax.Array
    lost: jax.Array
    regen_spawned: jax.Array
    regen_exhausted: jax.Array
    timeout: jax.Array
    squat_expired: jax.Array
    reserve_expired: jax.Array
    infeasible_winner: jax.Array
    oom_kill_f: jax.Array
    oom_kill_l: jax.Array
    suspended_cnt: jax.Array
    resumed_insitu: jax.Array
    reactivated: jax.Array
    migrated: jax.Array
    reclaimed: jax.Array
    throttled_rounds: jax.Array
    # scenario disruption process (node failures / drains)
    node_failures: jax.Array
    node_recoveries: jax.Array
    # residents displaced by hard node failures: killed outright without
    # Airlock, forced into secondary re-addressing with it
    evicted: jax.Array
    # of those, the ones actually killed (non-Airlock hard failures): they
    # never come back, so they count against execution survival
    evicted_killed: jax.Array
    # control-work op counters (multiplied by ns constants at summary time)
    op_dispatch: jax.Array
    op_eval: jax.Array
    op_bounce: jax.Array
    op_arb: jax.Array
    # per-tier lifecycle counters, (NUM_TIERS,) each
    started_tier: jax.Array
    completed_tier: jax.Array
    oom_kill_tier: jax.Array
    reclaimed_tier: jax.Array
    evicted_killed_tier: jax.Array
    # arrival->start latency histograms (log buckets): global + per-tier
    lat_hist: jax.Array
    lat_hist_tier: jax.Array  # (NUM_TIERS, HIST_BUCKETS)

    @staticmethod
    def zeros(hist_buckets: int = 64) -> "Metrics":
        z = jnp.zeros((), jnp.int32)
        zt = jnp.zeros((NUM_TIERS,), jnp.int32)
        vec = dict(
            started_tier=zt,
            completed_tier=zt,
            oom_kill_tier=zt,
            reclaimed_tier=zt,
            evicted_killed_tier=zt,
            lat_hist=jnp.zeros((hist_buckets,), jnp.int32),
            lat_hist_tier=jnp.zeros((NUM_TIERS, hist_buckets), jnp.int32),
        )
        scalars = [f for f in Metrics._fields if f not in vec]
        return Metrics(**{f: z for f in scalars}, **vec)


# Metrics fields that are arrays rather than scalar counters (summarize
# reports them per-tier instead of folding them into the flat int dict).
METRIC_VECTOR_FIELDS = (
    "started_tier",
    "completed_tier",
    "oom_kill_tier",
    "reclaimed_tier",
    "evicted_killed_tier",
    "lat_hist",
    "lat_hist_tier",
)


def tier_counts(tier: jax.Array, mask: jax.Array) -> jax.Array:
    """Count masked probes per tier -> (NUM_TIERS,) i32: a compare and a
    sum per tier, no scatter."""
    hit = (tier[:, None] == jnp.arange(NUM_TIERS, dtype=tier.dtype)) & mask[:, None]
    return jnp.sum(hit.astype(jnp.int32), axis=0)


HIST_BUCKETS = 64
HIST_MIN_MS = 0.25
HIST_PER_OCTAVE = 4.0


def latency_bucket(lat_ms: jax.Array) -> jax.Array:
    b = jnp.floor(jnp.log2(jnp.maximum(lat_ms, HIST_MIN_MS) / HIST_MIN_MS) * HIST_PER_OCTAVE)
    return jnp.clip(b.astype(jnp.int32), 0, HIST_BUCKETS - 1)


def bucket_upper_ms(i: np.ndarray) -> np.ndarray:
    return HIST_MIN_MS * 2.0 ** ((i + 1) / HIST_PER_OCTAVE)


def bucket_lower_ms(i: np.ndarray) -> np.ndarray:
    return HIST_MIN_MS * 2.0 ** (np.asarray(i) / HIST_PER_OCTAVE)


def hist_quantile(hist: np.ndarray, q: float) -> float:
    """Quantile of a log-bucketed latency histogram (host-side, np).

    Linearly interpolates within the containing bucket instead of snapping to
    its upper edge; shared by ``engine.summarize`` and the baselines so the
    two report paths cannot drift. Returns 0.0 for an empty histogram.
    """
    hist = np.asarray(hist, np.float64)
    total = hist.sum()
    if total <= 0:
        return 0.0
    cum = np.cumsum(hist)
    target = q * total
    i = int(np.searchsorted(cum, target))
    i = min(i, len(hist) - 1)
    in_bucket = hist[i]
    before = cum[i] - in_bucket
    # bucket 0's nominal lower edge is HIST_MIN_MS, but sub-minimum latencies
    # clip into it, so its interpolation floor is 0
    lo = 0.0 if i == 0 else float(bucket_lower_ms(i))
    hi = float(bucket_upper_ms(np.asarray(i)))
    frac = (target - before) / in_bucket if in_bucket > 0 else 1.0
    return lo + float(np.clip(frac, 0.0, 1.0)) * (hi - lo)


class SimState(NamedTuple):
    t: jax.Array  # current tick (i32)
    key: jax.Array  # PRNG key
    sched_key: jax.Array  # per-run arrival-schedule key (constant across ticks)

    # ---- probe / DA table (P,) ------------------------------------------
    st: jax.Array  # state machine code
    zone: jax.Array  # current zone
    node: jax.Array  # current / target node
    contig: jax.Array  # L-task (strictly contiguous demand)
    squat: jax.Array  # squatter (never completes payload pull)
    tier: jax.Array  # workload class: 0 prod / 1 batch / 2 best-effort (i32)
    migrating: jax.Array  # DA in secondary-reactivation epoch
    mass: jax.Array  # atoms demanded (i32)
    ev: jax.Array  # E_v,init static routing weight (f32)
    patience: jax.Array  # remaining E_patience (f32)
    deposit: jax.Array  # frozen deposit while pending (f32)
    timer: jax.Array  # generic countdown: hop / pull / quiet (i32 ticks)
    pull_dur: jax.Array  # pre-sampled payload pull duration (i32 ticks)
    pull_deadline: jax.Array  # reservation expiry tick (i32)
    surv_deadline: jax.Array  # shared survival TTL expiry tick (i32)
    susp_tick: jax.Array  # tick at which suspension began
    arrival: jax.Array  # arrival tick
    start: jax.Array  # execution start tick (-1 before)
    service: jax.Array  # remaining service ticks while RUNNING
    regen: jax.Array  # regeneration instances used
    mem: jax.Array  # true physical memory usage while resident (f32)
    alloc: jax.Array  # (P, W) held atom words at alloc_node
    alloc_node: jax.Array  # node where atoms are held (-1 none)
    alloc2: jax.Array  # (P, W) destination reservation during migration
    node2: jax.Array  # destination node during migration (-1 none)

    # ---- node table (N,) --------------------------------------------------
    free: jax.Array  # (N, W) free-atom bitmap words
    zone_id: jax.Array
    rep_S: jax.Array  # reported (stale) slack
    rep_H: jax.Array  # reported (stale) heat
    rep_run: jax.Array  # reported (stale) max contiguous run
    rep_t: jax.Array  # tick of last successful report
    dS: jax.Array  # EMA slack derivative (atoms / ms)
    dH: jax.Array
    next_rep: jax.Array  # next report tick
    amb: jax.Array  # ambient memory perturbation (AR(1), fraction of cap)
    rigid_mem: jax.Array  # rigid-topology resident memory (fraction of cap)
    # scenario disruption process state
    node_up: jax.Array  # (N,) bool: node currently serving
    down_until: jax.Array  # (N,) i32 recovery tick while down
    free0: jax.Array  # (N, W) painted free bitmap at init (recovery restore base)

    # ---- zone table (Z,) ---------------------------------------------------
    zstart: jax.Array
    zcount: jax.Array
    zS: jax.Array  # TEG aggregate: mean reported slack
    zH: jax.Array  # TEG aggregate: total reported heat
    # densified member matrix for the zone_aggregate kernel: zones are
    # heterogeneous, so (Z, M) node indices (M = max zone size) + validity
    zmember: jax.Array  # (Z, M) node index per zone slot (0 where invalid)
    zmask: jax.Array  # (Z, M) validity (f32: 1.0 member, 0.0 padding)

    metrics: Metrics


def build_zones(cfg: LaminarConfig, rng: np.random.Generator):
    """Heterogeneous contiguous zones (target size +/- jitter)."""
    sizes = []
    left = cfg.num_nodes
    while left > 0:
        j = 1.0 + rng.uniform(-cfg.zone_size_jitter, cfg.zone_size_jitter)
        s = int(max(8, min(left, round(cfg.zone_size * j))))
        if left - s < 8:
            s = left
        sizes.append(s)
        left -= s
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
    counts = np.asarray(sizes, np.int32)
    zone_id = np.repeat(np.arange(len(sizes), dtype=np.int32), counts)
    return starts, counts, zone_id


def densify_zones(starts: np.ndarray, counts: np.ndarray):
    """(Z, M) member-index matrix + validity mask for heterogeneous zones.

    Zones are contiguous node ranges, so row z is ``starts[z] + arange(M)``
    masked at ``counts[z]``; invalid slots point at node 0 (gather-safe)."""
    M = int(counts.max())
    lane = np.arange(M, dtype=np.int32)[None, :]
    mask = lane < counts[:, None]
    member = np.where(mask, starts[:, None] + lane, 0).astype(np.int32)
    return member, mask.astype(np.float32)


# ---------------------------------------------------------------------------
# zone-blocked layout: flat node-major (N, ...) <-> padded (Z, M, ...)
# ---------------------------------------------------------------------------
# The scale-out engine (repro.parallel.engine_mesh) shards the node-plane
# computation along the zone axis. Zones are heterogeneous, so the blocked
# layout is PADDED: row z holds zone z's nodes in slots [0, zcount[z]) and
# inert fill elsewhere. ``pack_zoned`` / ``unpack_zoned`` are exact inverses
# on the valid slots; padding slots always carry ``fill`` so a round trip
# through the flat layout reproduces a canonical blocked array bit-for-bit.


def pack_zoned(
    x: jax.Array, zmember: jax.Array, zmask: jax.Array, fill=0
) -> jax.Array:
    """Flat node-major ``(N, ...)`` -> padded zone-blocked ``(Z, M, ...)``.

    Valid slots gather their node's row; padding slots are set to ``fill``
    (inert — they never re-enter the flat layout)."""
    v = x[zmember]  # (Z, M, ...)
    mask = (zmask > 0).reshape(zmask.shape + (1,) * (v.ndim - zmask.ndim))
    return jnp.where(mask, v, jnp.asarray(fill, v.dtype))


def unpack_zoned(
    xb: jax.Array, zmember: jax.Array, zmask: jax.Array, num_nodes: int
) -> jax.Array:
    """Padded zone-blocked ``(Z, M, ...)`` -> flat node-major ``(N, ...)``.

    Every node occupies exactly one valid slot, so the scatter writes each
    flat row exactly once; padding slots are dropped (scattered out of
    bounds), never clobbering node 0 despite pointing at it in ``zmember``.
    ``xb`` may carry more zone rows than ``zmember`` covers (e.g. padded to
    a device-count multiple): trailing rows are ignored."""
    Z, M = zmember.shape
    xb = xb[:Z]
    tgt = jnp.where(zmask > 0, zmember, num_nodes).reshape(-1)
    flat = xb.reshape((Z * M,) + xb.shape[2:])
    out = jnp.zeros((num_nodes,) + xb.shape[2:], xb.dtype)
    return out.at[tgt].set(flat, mode="drop")


def paint_rigid(cfg: LaminarConfig, rng: np.random.Generator):
    """Pre-occupy node bitmaps with rigid-topology chunks (post-landing ecology)."""
    A = cfg.atoms_per_node
    n = cfg.num_nodes
    bits = np.ones((n, A), dtype=bool)  # True = free
    frac = rng.uniform(cfg.rigid_frac_lo, cfg.rigid_frac_hi, size=n)
    occupied = np.zeros(n, np.int32)
    target = (frac * A).astype(np.int32)
    for _ in range(cfg.rigid_chunks):
        remaining = np.maximum(target - occupied, 0)
        chunk = np.ceil(remaining / max(1, cfg.rigid_chunks)).astype(np.int32)
        chunk = np.minimum(chunk, remaining)
        start = rng.integers(0, A, size=n)
        for i in range(n):  # init-time only; O(N * A) host work
            c = int(chunk[i])
            if c == 0:
                continue
            s = int(start[i])
            e = min(s + c, A)
            taken = int(bits[i, s:e].sum())
            bits[i, s:e] = False
            occupied[i] += taken
    rigid_atoms = A - bits.sum(axis=1)
    return bits, rigid_atoms.astype(np.float32)


def init_state(cfg: LaminarConfig, seed: int = 0) -> SimState:
    rng = np.random.default_rng(seed)
    P = cfg.probe_capacity
    N = cfg.num_nodes
    W = max(1, (cfg.atoms_per_node + 31) // 32)

    zstart, zcount, zone_id = build_zones(cfg, rng)
    zmember, zmask = densify_zones(zstart, zcount)
    Z = len(zcount)
    bits, rigid_atoms = paint_rigid(cfg, rng)
    free_words = np.asarray(bitmap.pack_bits(jnp.asarray(bits)))

    free0 = bits.sum(axis=1).astype(np.float32)
    run0 = np.zeros(N, np.float32)
    for i in range(N):
        r = best = 0
        for b in bits[i]:
            r = r + 1 if b else 0
            best = max(best, r)
        run0[i] = best

    zS0 = np.zeros(Z, np.float32)
    zH0 = np.zeros(Z, np.float32)
    for z in range(Z):
        sl = slice(zstart[z], zstart[z] + zcount[z])
        zS0[z] = free0[sl].mean()

    i32 = lambda x: jnp.asarray(x, jnp.int32)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    zero_p_i = jnp.zeros((P,), jnp.int32)
    zero_p_f = jnp.zeros((P,), jnp.float32)
    zero_p_b = jnp.zeros((P,), jnp.bool_)

    rep_interval = cfg.ticks(cfg.report_interval_ms + cfg.extra_sync_delay_ms)
    first_rep = rng.integers(0, rep_interval, size=N)

    from repro.workloads.schedule import schedule_key

    return SimState(
        t=jnp.zeros((), jnp.int32),
        key=jax.random.PRNGKey(seed),
        sched_key=schedule_key(seed),
        st=zero_p_i,
        zone=zero_p_i,
        node=jnp.full((P,), -1, jnp.int32),
        contig=zero_p_b,
        squat=zero_p_b,
        tier=zero_p_i,
        migrating=zero_p_b,
        mass=zero_p_i,
        ev=zero_p_f,
        patience=zero_p_f,
        deposit=zero_p_f,
        timer=zero_p_i,
        pull_dur=zero_p_i,
        pull_deadline=zero_p_i,
        surv_deadline=zero_p_i,
        susp_tick=zero_p_i,
        arrival=zero_p_i,
        start=jnp.full((P,), -1, jnp.int32),
        service=zero_p_i,
        regen=zero_p_i,
        mem=zero_p_f,
        alloc=jnp.zeros((P, W), jnp.uint32),
        alloc_node=jnp.full((P,), -1, jnp.int32),
        alloc2=jnp.zeros((P, W), jnp.uint32),
        node2=jnp.full((P,), -1, jnp.int32),
        free=jnp.asarray(free_words, jnp.uint32).reshape(N, W),
        zone_id=i32(zone_id),
        rep_S=f32(free0),
        rep_H=jnp.zeros((N,), jnp.float32),
        rep_run=f32(run0),
        rep_t=jnp.zeros((N,), jnp.int32),
        dS=jnp.zeros((N,), jnp.float32),
        dH=jnp.zeros((N,), jnp.float32),
        next_rep=i32(first_rep),
        amb=jnp.zeros((N,), jnp.float32),
        rigid_mem=f32(rigid_atoms / cfg.atoms_per_node),
        node_up=jnp.ones((N,), jnp.bool_),
        down_until=jnp.zeros((N,), jnp.int32),
        free0=jnp.asarray(free_words, jnp.uint32).reshape(N, W),
        zstart=i32(zstart),
        zcount=i32(zcount),
        zS=f32(zS0),
        zH=f32(zH0),
        zmember=i32(zmember),
        zmask=f32(zmask),
        metrics=Metrics.zeros(HIST_BUCKETS),
    )
