"""The Laminar engine: tick-synchronous composition of all subsystems.

One tick = one jitted transition; a run = ``lax.scan`` over ticks. The hot
path per tick mirrors the paper's control path:

    memory dynamics -> runtime control (Airlock / OOM) -> Airlock
    transitions -> completions -> node-view build -> Z-HAF reports ->
    TEG refresh -> arrivals -> probe movement (+ regeneration) ->
    TEG dispatch -> DA addressing -> node arbitration (xN rounds) ->
    pending stage -> absolute timeout

Everything is vectorized over the probe table and the node table; there is no
per-task Python control flow anywhere.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import airlock, arbiter, da, disrupt, events, hotpath, teg, workload, zhaf
from repro.core.config import LaminarConfig
from repro.workloads import schedule as wl_schedule
from repro.workloads.scenario import ScenarioConfig
from repro.core.config import TIER_NAMES
from repro.core.state import (
    EMPTY,
    METRIC_VECTOR_FIELDS,
    Metrics,
    SimState,
    hist_quantile,
    init_state,
)

TS_FIELDS = (
    "arrived",
    "started",
    "completed",
    "oom_kill_l",
    "oom_kill_f",
    "reclaimed",
    "fastfail",
    "suspended_cnt",
    "resumed_insitu",
    "migrated",
    "timeout",
    "op_eval",
)


# The stages of the tick, in tick order. ``make_step`` runs each inside
# ``jax.named_scope("laminar.<stage>")``, so every device op of the tick
# carries its stage in its ``op_name`` metadata; work outside them (the key
# split, the tick counter, the timeseries row) is unscoped. The scopes add
# metadata only, no operations.
STAGES = (
    "survival",
    "completions",
    "disruption",
    "dissemination",
    "intake",
    "addressing",
    "arbitration",
)
SCOPE_PREFIX = "laminar."


def _stage(name: str):
    if name not in STAGES:
        raise ValueError(f"unknown tick stage {name!r}")
    return jax.named_scope(SCOPE_PREFIX + name)


def _inject_arrivals(
    cfg: LaminarConfig,
    s: SimState,
    key: jax.Array,
    lam_per_tick: float | jax.Array,
    batch: workload.ArrivalBatch | None = None,
) -> Tuple[SimState, jax.Array]:
    """Sample the open-loop Poisson batch and write it into free probe slots.

    ``lam_per_tick`` may be a traced scalar (scenario schedules evaluate it
    per tick inside the scan). ``batch`` overrides the sampled batch — test
    hook for the rows-beyond-``n``-are-inert invariant.
    """
    k_batch, k_oc, k_ocv = jax.random.split(key, 3)
    if batch is None:
        batch = workload.sample_arrivals(cfg, k_batch, lam_per_tick)
    n_max = cfg.max_arrivals_per_tick

    want = jnp.arange(n_max) < batch.n
    slots = jnp.nonzero(s.st == EMPTY, size=n_max, fill_value=-1)[0]
    ok = want & (slots >= 0)
    slot = jnp.maximum(slots, 0)  # gathers only
    # scatters drop invalid rows (clamping to 0 could clobber slot 0)
    tgt = jnp.where(ok, slot, s.st.shape[0])

    mc = cfg.memory
    oc = (
        (jax.random.uniform(k_oc, (n_max,)) < mc.overclaim_prob)
        * jax.random.uniform(k_ocv, (n_max,))
        * mc.overclaim_max
    )
    mem = batch.mass.astype(jnp.float32) * (1.0 + oc) * mc.mem_per_atom
    mem = mem / cfg.atoms_per_node  # fraction of node capacity

    def put(arr, val):
        return arr.at[tgt].set(val, mode="drop")

    neg1 = jnp.full((n_max,), -1, jnp.int32)
    zero_i = jnp.zeros((n_max,), jnp.int32)
    s = s._replace(
        contig=put(s.contig, batch.contig),
        squat=put(s.squat, batch.squat),
        tier=put(s.tier, batch.tier),
        migrating=put(s.migrating, jnp.zeros((n_max,), jnp.bool_)),
        mass=put(s.mass, batch.mass),
        ev=put(s.ev, batch.ev),
        patience=put(s.patience, batch.patience),
        deposit=put(s.deposit, jnp.zeros((n_max,), jnp.float32)),
        pull_dur=put(s.pull_dur, batch.pull),
        pull_deadline=put(s.pull_deadline, zero_i),
        surv_deadline=put(s.surv_deadline, zero_i),
        arrival=put(s.arrival, jnp.full((n_max,), 1, jnp.int32) * s.t),
        start=put(s.start, neg1),
        service=put(s.service, batch.service),
        regen=put(s.regen, zero_i),
        mem=put(s.mem, mem),
        alloc=s.alloc.at[tgt].set(jnp.uint32(0), mode="drop"),
        alloc_node=put(s.alloc_node, neg1),
        alloc2=s.alloc2.at[tgt].set(jnp.uint32(0), mode="drop"),
        node2=put(s.node2, neg1),
    )

    mask = jnp.zeros_like(s.st, jnp.bool_).at[tgt].set(True, mode="drop")
    n_ok = jnp.sum(ok.astype(jnp.int32))
    n_f = jnp.sum((ok & ~batch.contig).astype(jnp.int32))
    m = s.metrics
    m = m._replace(
        arrived=m.arrived + n_ok,
        arrived_f=m.arrived_f + n_f,
        arrived_l=m.arrived_l + (n_ok - n_f),
        arrived_squat=m.arrived_squat + jnp.sum((ok & batch.squat).astype(jnp.int32)),
        dropped_capacity=m.dropped_capacity + (batch.n - n_ok),
    )
    return s._replace(metrics=m), mask


def make_step(
    cfg: LaminarConfig,
    lam_per_tick: float,
    scenario: ScenarioConfig | None = None,
    plane=None,
):
    """Build the one-tick transition (cfg, lambda and scenario closed over).

    ``scenario`` defaults to ``cfg.scenario``; a stationary, disruption-free
    scenario reproduces the pre-scenario tick bit-for-bit (same key splits,
    same arrival stream).

    ``plane`` selects the node-plane execution strategy. ``None`` (default)
    runs the flat single-device path. The zone-sharded scale-out engine
    (``repro.parallel.engine_mesh``) passes a ``MeshPlane`` so the heavy
    per-node bitmap pipeline (view build, feasibility, allocation, zone
    aggregation) runs on each device's zone block inside ``shard_map``,
    while the probe table and all O(N) float vectors stay replicated — the
    replicated math is deterministic, so every device computes identical
    probe-plane results and the two layouts agree bit for bit.
    """
    scenario = cfg.scenario if scenario is None else scenario
    sched = scenario.schedule
    disruption_on = scenario.disruption.enabled

    max_dispatch = cfg.max_arrivals_per_tick + 256
    if disruption_on and not scenario.disruption.drain:
        # eviction headroom: a failure event can force at most one resident
        # per atom on each failed node into TEG re-dispatch the same tick
        max_dispatch += 2 * scenario.disruption.fail_block * cfg.atoms_per_node

    def step(s: SimState, _) -> Tuple[SimState, jax.Array]:
        key, *ks = jax.random.split(s.key, 9 if disruption_on else 8)
        s = s._replace(key=key)

        # ---- runtime survival (Exp5) ---------------------------------------
        if cfg.memory.enabled:
            with _stage("survival"):
                s = airlock.memory_dynamics(cfg, s, ks[1])
                # one fused pass over the probe table: pressure + victim +
                # transition masks (jnp reference or Pallas survival_scan kernel)
                pressure, victim, resume, react, expire = hotpath.survival_scan(cfg, s)
                s = airlock.runtime_control(cfg, s, victim)
                s, react_mask = airlock.airlock_transitions(
                    cfg, s, resume, react, expire
                )
        else:
            pressure = jnp.zeros((cfg.num_nodes,), jnp.float32)
            react_mask = jnp.zeros_like(s.migrating)

        # ---- service progress ------------------------------------------------
        with _stage("completions"):
            s = arbiter.completions(cfg, s)

        # ---- scenario disruption: fail/drain/recover nodes --------------------
        if disruption_on:
            with _stage("disruption"):
                s, evict_mask = disrupt.apply(cfg, scenario, s, ks[7])
        else:
            evict_mask = jnp.zeros_like(s.migrating)

        with _stage("dissemination"):
            # ---- true node state, computed once per tick -----------------------
            if plane is None:
                view = zhaf.build_view(cfg, s)
                bits = view.bits
            else:
                view, bits = plane.build_view(cfg, s)

            # ---- cold path: state dissemination ---------------------------
            s = zhaf.report(cfg, s, ks[0], view)
            s = teg.refresh(cfg, s, plane)

        # ---- admissions hot path ----------------------------------------------
        with _stage("intake"):
            if sched.kind == "stationary":
                lam_t = lam_per_tick  # exact pre-scenario arrival stream
            else:
                lam_t = wl_schedule.rate_per_tick(
                    sched, lam_per_tick, s.t, s.sched_key, cfg.dt_ms
                )
            s, arrival_mask = _inject_arrivals(cfg, s, ks[2], lam_t)
            s, regen_mask = da.move(cfg, s, ks[3])
            dispatch_mask = arrival_mask | regen_mask | react_mask | evict_mask
            s = teg.dispatch(cfg, s, ks[4], dispatch_mask, max_dispatch)
        with _stage("addressing"):
            s = da.address(cfg, s, ks[5], view)

        with _stage("arbitration"):
            throttled = (
                (pressure > cfg.memory.high_watermark)
                if (cfg.memory.enabled and cfg.airlock)
                else jnp.zeros((cfg.num_nodes,), jnp.bool_)
            )
            # multiple admission rounds per tick: after each reservation the
            # node removes the winner's atoms and proceeds to the next feasible
            # candidate
            for _ in range(cfg.arb_rounds):
                s, bits = arbiter.arbitrate(cfg, s, ks[6], throttled, bits, plane)
            s = arbiter.pending_stage(cfg, s)
            s = arbiter.timeouts(cfg, s)

        s = s._replace(t=s.t + 1)
        ts = jnp.stack([getattr(s.metrics, f) for f in TS_FIELDS])
        return s, ts

    return step


class LaminarEngine:
    """Build, run, and summarize Laminar simulations."""

    def __init__(self, cfg: LaminarConfig):
        self.cfg = cfg
        self._compiled = {}

    def init(self, seed: int = 0) -> Tuple[SimState, float]:
        s = init_state(self.cfg, seed)
        free_atoms = float(np.asarray(s.rep_S).sum())
        lam = workload.lambda_per_tick(self.cfg, free_atoms)
        return s, lam

    def _runner(
        self, lam: float, num_ticks: int, scenario: ScenarioConfig | None = None
    ):
        scenario = self.cfg.scenario if scenario is None else scenario
        # the compiled scan is specialized on the FULL scenario signature —
        # keying on round(lam, 6) alone would collide two scenarios that
        # share a base rate but differ in schedule or disruption parameters
        key = (round(lam, 6), num_ticks, scenario.signature())
        if key not in self._compiled:
            step = make_step(self.cfg, lam, scenario)

            def run(s: SimState):
                return jax.lax.scan(step, s, None, length=num_ticks)

            self._compiled[key] = jax.jit(run)
        return self._compiled[key]

    def run(
        self,
        seed: int = 0,
        num_ticks: int | None = None,
        scenario: ScenarioConfig | None = None,
    ) -> Dict[str, Any]:
        s, lam = self.init(seed)
        nt = num_ticks if num_ticks is not None else self.cfg.num_ticks
        final, ts = self._runner(lam, nt, scenario)(s)
        out = summarize(self.cfg, final, np.asarray(ts))
        out["lambda_per_s"] = lam / self.cfg.dt_ms * 1e3
        return out

    # ------------------------------------------------------------------
    # batched multi-seed execution: one compiled vmap(scan) for all seeds
    # ------------------------------------------------------------------

    def init_batch(self, seeds: Sequence[int]) -> Tuple[SimState, float]:
        """Stack per-seed initial states along a leading batch axis.

        Cluster geometry (zones, rigid pre-occupancy) is built once from
        ``seeds[0]`` and shared: per-seed variation enters through the PRNG
        key, which drives every stochastic process (arrivals, loss, jitter,
        memory dynamics). Heterogeneous per-seed geometry would give each
        seed a different zone count — unstackable shapes — so batched runs
        hold the cluster fixed and vary the traffic.
        """
        seeds = [int(x) for x in seeds]
        if not seeds:
            raise ValueError("init_batch needs at least one seed")
        base = init_state(self.cfg, seeds[0])
        free_atoms = float(np.asarray(base.rep_S).sum())
        lam = workload.lambda_per_tick(self.cfg, free_atoms)
        B = len(seeds)
        batched = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (B,) + x.shape), base
        )
        keys = jnp.stack([jax.random.PRNGKey(sd) for sd in seeds])
        # the arrival schedule varies per seed too (burst placement etc.);
        # only the cluster geometry is shared from seeds[0]
        sched_keys = jnp.stack([wl_schedule.schedule_key(sd) for sd in seeds])
        return batched._replace(key=keys, sched_key=sched_keys), lam

    def _batch_runner(
        self, lam: float, num_ticks: int, scenario: ScenarioConfig | None = None
    ):
        scenario = self.cfg.scenario if scenario is None else scenario
        key = ("batch", round(lam, 6), num_ticks, scenario.signature())
        if key not in self._compiled:
            step = make_step(self.cfg, lam, scenario)

            def run_one(s: SimState):
                return jax.lax.scan(step, s, None, length=num_ticks)

            self._compiled[key] = jax.jit(jax.vmap(run_one))
        return self._compiled[key]

    def run_batch(
        self,
        seeds: Sequence[int],
        num_ticks: int | None = None,
        scenario: ScenarioConfig | None = None,
    ) -> List[Dict[str, Any]]:
        """Run all ``seeds`` through ONE compiled ``vmap``'d ``lax.scan``.

        Returns one ``summarize()`` dict per seed. There is no Python loop
        over seeds in the simulation: the batch advances in lockstep, one
        jitted program, which is how the benchmarks amortize compilation
        across replicate seeds.
        """
        seeds = [int(x) for x in seeds]
        s, lam = self.init_batch(seeds)
        nt = num_ticks if num_ticks is not None else self.cfg.num_ticks
        final, ts = self._batch_runner(lam, nt, scenario)(s)
        ts = np.asarray(ts)
        outs: List[Dict[str, Any]] = []
        for i, sd in enumerate(seeds):
            final_i = jax.tree.map(lambda x, i=i: x[i], final)
            out = summarize(self.cfg, final_i, ts[i])
            out["lambda_per_s"] = lam / self.cfg.dt_ms * 1e3
            out["seed"] = sd
            outs.append(out)
        return outs


def summarize(cfg: LaminarConfig, final: SimState, ts: np.ndarray) -> Dict[str, Any]:
    from repro.core.state import LOST_WAIT, RUNNING

    m: Metrics = jax.tree.map(np.asarray, final.metrics)
    arrived = max(int(m.arrived), 1)
    started = max(int(m.started), 1)

    # horizon censoring: control probes still in flight at the end of the run
    st = np.asarray(final.st)
    mig = np.asarray(final.migrating)
    squat = np.asarray(final.squat)
    ctl = (((st > EMPTY) & (st < RUNNING)) | (st == LOST_WAIT)) & ~mig
    in_flight = int(ctl.sum())
    in_flight_nonsquat = int((ctl & ~squat).sum())
    # started tasks still alive at the horizon: executing, in glass-state, or
    # a migrating incarnation anywhere in its secondary-reactivation epoch
    from repro.core.state import SUSPENDED

    resident_mask = ((st == RUNNING) | (st == SUSPENDED)) | (mig & (st != EMPTY))
    resident_end = int(resident_mask.sum())

    hist = np.asarray(m.lat_hist, np.float64)
    total = hist.sum()
    if total > 0:
        p50 = hist_quantile(hist, 0.50)
        p99 = hist_quantile(hist, 0.99)
    else:
        p50 = p99 = float("nan")

    k = cfg.candidate_k
    work_ns = (
        float(m.op_dispatch) * cfg.ns_utility_score
        + float(m.op_eval) * (cfg.ns_utility_score + k * cfg.ns_bitmap_check)
        + float(m.op_bounce) * cfg.ns_bitmap_check
        + float(m.op_arb) * cfg.ns_bitmap_check
        + float(m.op_dispatch) * cfg.ns_zone_aggregate * 0.0  # cold path excluded
    )

    # DAs addressed each tick, and the trips of da.address's block loop they
    # took; the run starts from init_state, whose counters are zero
    addressed = np.diff(ts[:, TS_FIELDS.index("op_eval")], prepend=0)
    addr_max = int(addressed.max()) if addressed.size else 0
    addr_block = events.row_block(cfg, st.shape[0])

    probe_drops = (
        int(m.fastfail)
        + int(m.lost)
        + int(m.regen_exhausted)
        + int(m.timeout)
        + int(m.reclaimed)
        + int(m.reserve_expired)
    )

    out: Dict[str, Any] = {
        f: int(getattr(m, f))
        for f in Metrics._fields
        if f not in METRIC_VECTOR_FIELDS
    }

    # ---- per-tier lifecycle accounting (Exp8) -----------------------------
    tier = np.asarray(final.tier)
    from repro.core.config import NUM_TIERS

    resident_tier = np.bincount(
        tier[resident_mask], minlength=NUM_TIERS
    )[:NUM_TIERS]
    for i, nm in enumerate(TIER_NAMES):
        started_i = int(m.started_tier[i])
        killed_i = (
            int(m.oom_kill_tier[i])
            + int(m.reclaimed_tier[i])
            + int(m.evicted_killed_tier[i])
        )
        th = np.asarray(m.lat_hist_tier[i], np.float64)
        out[f"{nm}_started"] = started_i
        out[f"{nm}_completed"] = int(m.completed_tier[i])
        out[f"{nm}_oom"] = int(m.oom_kill_tier[i])
        out[f"{nm}_reclaimed"] = int(m.reclaimed_tier[i])
        out[f"{nm}_evicted_killed"] = int(m.evicted_killed_tier[i])
        out[f"{nm}_resident_end"] = int(resident_tier[i])
        out[f"{nm}_survival"] = 1.0 - killed_i / max(started_i, 1)
        out[f"{nm}_p50_ms"] = (
            hist_quantile(th, 0.50) if th.sum() > 0 else float("nan")
        )
        out[f"{nm}_p99_ms"] = (
            hist_quantile(th, 0.99) if th.sum() > 0 else float("nan")
        )

    out.update(
        start_success_ratio=float(m.started) / max(arrived - in_flight, 1),
        start_success_raw=float(m.started) / arrived,
        # squatters never intend to start; Exp4's meaningful ratio excludes
        # them from the population (they are the ATTACK, not the workload)
        start_success_nonsquat=float(m.started)
        / max(arrived - int(m.arrived_squat) - in_flight_nonsquat, 1),
        in_flight_end=in_flight,
        resident_end=resident_end,
        completed_success_ratio=float(m.completed)
        / max(arrived - in_flight, 1),
        # every way a started task dies: kernel OOM, Airlock reclamation, or
        # an un-airlocked hard node failure (evicted_killed)
        exec_survival_ratio=1.0
        - (
            float(m.oom_kill_f + m.oom_kill_l)
            + float(m.reclaimed)
            + float(m.evicted_killed)
        )
        / started,
        p50_ms=p50,
        p99_ms=p99,
        control_us_per_start=work_ns / started / 1e3,
        probe_drops=probe_drops,
        addr_probes_per_tick_max=addr_max,
        addr_trips_max=-(-addr_max // addr_block),
        lat_hist=hist,
        timeseries={f: ts[:, i] for i, f in enumerate(TS_FIELDS)},
    )
    return out
