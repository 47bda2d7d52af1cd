"""One tick of the plain simulator, stage by stage.

A state is a dict of arrays: the probe table (P,), the node table (N,),
the zone table (Z,), the tick ``t``, the PRNG ``key`` and the ``metrics``
counters. The free atoms are a boolean plane ``free`` (N, A); an
allocation is held as 32-atom words (P, W), least significant bit first.
Each stage reads the state as the stage before left it:

    memory dynamics -> Airlock (suspend, resume, reactivate, reclaim) ->
    completions -> true node view -> Z-HAF reports -> zone refresh ->
    arrivals -> hops (loss, regeneration) -> gateway routing ->
    addressing -> arbitration (arb_rounds rounds) -> pending stage ->
    absolute timeout
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .codes import (
    ADDRESSING, BOUNCING, EMPTY, HIST_BUCKETS, LOST_WAIT, NUM_TIERS, QUEUED,
    RESERVED, ROUTING, RUNNING, SUSPENDED,
)

NEVER = 1 << 30  # a deadline that never comes
U32 = jnp.uint32


# ---------------------------------------------------------------- helpers


def count(mask) -> jax.Array:
    return jnp.sum(mask.astype(jnp.int32))


def per_tier(tier, mask) -> jax.Array:
    return jnp.zeros(NUM_TIERS, jnp.int32).at[jnp.where(mask, tier, NUM_TIERS)].add(
        1, mode="drop")


def to_words(plane) -> jax.Array:
    """(R, A) bool -> (R, W) uint32, atom i at bit i % 32 of word i // 32."""
    r, a = plane.shape
    w = (a + 31) // 32
    padded = jnp.zeros((r, w * 32), U32).at[:, :a].set(plane.astype(U32))
    shifts = jnp.arange(32, dtype=U32)
    return jnp.sum(padded.reshape(r, w, 32) << shifts, axis=-1, dtype=U32)


def to_plane(words, atoms: int) -> jax.Array:
    """(R, W) uint32 -> (R, A) bool, the inverse of ``to_words``."""
    shifts = jnp.arange(32, dtype=U32)
    bits = (words[:, :, None] >> shifts) & U32(1)
    return bits.reshape(words.shape[0], -1)[:, :atoms].astype(bool)


def give_back(free, words, node, mask):
    """The free plane with the atoms of each masked allocation returned.

    Allocations held on one node never share an atom, so adding their words
    is the same as or-ing them.
    """
    n = free.shape[0]
    held = jnp.zeros((n + 1, words.shape[1]), U32).at[
        jnp.where(mask & (node >= 0), node, n)].add(
        jnp.where(mask[:, None], words, U32(0)))
    return free | to_plane(held[:n], free.shape[1])


def runs(plane):
    """Per atom: (length of the free run it lies in, run length ending at it).

    Both are 0 for an occupied atom.
    """
    a = plane.shape[-1]
    pos = jnp.arange(a, dtype=jnp.int32)
    busy_before = jax.lax.cummax(jnp.where(plane, -1, pos), axis=plane.ndim - 1)
    busy_after = jax.lax.cummin(jnp.where(plane, a, pos), axis=plane.ndim - 1, reverse=True)
    whole = jnp.where(plane, busy_after - busy_before - 1, 0)
    ending = jnp.where(plane, pos - busy_before, 0)
    return whole, ending


def elect(group, eligible, *keys):
    """One row per group among the eligible: the largest by ``keys``.

    ``keys`` are compared in order (the first decides, the next breaks its
    ties); the last has to be unique, as the slot index is.
    """
    big = jnp.iinfo(jnp.int32).max
    g = jnp.where(eligible, group, big)
    order = jnp.lexsort(tuple(reversed(keys)) + (g,))
    gs = g[order]
    last = jnp.concatenate([gs[:-1] != gs[1:], jnp.ones((1,), bool)])
    won = (gs != big) & last
    return jnp.zeros_like(eligible).at[order].set(won)


def dissolve(st, migrating, mask):
    """A dying control incarnation: a migrating one falls back to suspended,
    any other frees its slot."""
    return jnp.where(mask, jnp.where(migrating, SUSPENDED, EMPTY), st)


def log2_1p(x):
    return jnp.log2(1.0 + jnp.maximum(x, 0.0))


def utility(slack, heat, gamma):
    """U = log2(1 + S) - gamma log2(1 + H)."""
    return log2_1p(slack) - gamma * log2_1p(heat)


# ------------------------------------------------------- runtime survival


def memory_dynamics(c, s, key):
    """Ambient memory per node: AR(1) noise, Bernoulli bursts, slow drift."""
    mc = c.mem
    n = c.num_nodes
    k_noise, k_hit, k_size = jax.random.split(key, 3)
    d = mc.ambient_decay
    noise = jnp.sqrt(1 - d**2) * mc.noise_sigma * jax.random.normal(k_noise, (n,))
    burst = ((jax.random.uniform(k_hit, (n,)) < mc.burst_rate)
             * jax.random.uniform(k_size, (n,)) * mc.burst_scale)
    phase = jnp.arange(n, dtype=jnp.float32) * 2.399
    seconds = s["t"].astype(jnp.float32) * c.dt_ms / 1e3
    drift = mc.drift_kappa * 0.5 * (1.0 + jnp.sin(2 * jnp.pi * seconds + phase))
    s["amb"] = jnp.clip(d * (s["amb"] - drift) + noise + burst + drift, 0.0, 0.8)


def airlock(c, s, m):
    """Airlock's ladder for one tick; returns (node pressure, reactivated).

    Pressure is the node's rigid and ambient memory plus what its residents
    use (a suspended or migrating one only its compressed residual). On a
    node above the high watermark the running resident of the worst tier,
    lowest E_v within it (highest slot on a tie), is suspended. A suspended
    task resumes where its node is under the safe watermark; one suspended
    longer than T_susp reactivates as a migrating probe with fresh patience
    and the survival deadline T_surv; a migrating task past that deadline is
    reclaimed with both its allocations.
    """
    mc = c.mem
    n = c.num_nodes
    t = s["t"]
    st, node, mig, mem = s["st"], s["alloc_node"], s["migrating"], s["mem"]
    holds = node >= 0
    at = jnp.clip(node, 0, n - 1)
    slot = jnp.arange(st.shape[0], dtype=jnp.int32)
    used = jnp.where(st == RUNNING, mem,
                     jnp.where((st == SUSPENDED) | (mig & holds),
                               mem * jnp.float32(mc.suspended_residual), 0.0))
    pressure = (s["rigid_mem"] + s["amb"]).at[jnp.where(holds, node, n)].add(
        used, mode="drop")

    over = (st == RUNNING) & holds & (pressure[at] > jnp.float32(mc.high_watermark))
    victim = elect(node, over, s["tier"], -s["ev"], slot)
    st = jnp.where(victim, SUSPENDED, st)
    mig = mig & ~victim
    susp = jnp.where(victim, t, s["susp_tick"])

    glass = (st == SUSPENDED) & ~mig
    resume = glass & holds & (pressure[at] < jnp.float32(mc.safe_watermark))
    react = glass & ~resume & (t - susp > c.ticks(c.t_susp_ms))
    expire = mig & (t > s["surv_deadline"]) & (st != EMPTY) & (st != RUNNING)

    free = give_back(s["free"], s["alloc"], node, expire)
    free = give_back(free, s["alloc2"], s["node2"], expire)
    st = jnp.where(resume, RUNNING, st)
    st = jnp.where(expire, EMPTY, st)
    s.update(
        st=st, susp_tick=susp, free=free,
        migrating=(mig | react) & ~expire,
        patience=jnp.where(react, s["ev"], s["patience"]),
        surv_deadline=jnp.where(react, t + c.ticks(c.t_surv_ms), s["surv_deadline"]),
        alloc=jnp.where(expire[:, None], U32(0), s["alloc"]),
        alloc_node=jnp.where(expire, -1, node),
        alloc2=jnp.where(expire[:, None], U32(0), s["alloc2"]),
        node2=jnp.where(expire, -1, s["node2"]),
    )
    m["suspended_cnt"] += count(victim)
    m["resumed_insitu"] += count(resume)
    m["reactivated"] += count(react)
    m["reclaimed"] += count(expire)
    m["reclaimed_tier"] += per_tier(s["tier"], expire)
    return pressure, react


# ------------------------------------------------------------ completions


def completions(c, s, m):
    """A running task's service counts down; at 0 it completes and frees its atoms."""
    running = s["st"] == RUNNING
    service = jnp.where(running, s["service"] - 1, s["service"])
    done = running & (service <= 0)
    s.update(
        service=service,
        free=give_back(s["free"], s["alloc"], s["alloc_node"], done),
        st=jnp.where(done, EMPTY, s["st"]),
        alloc=jnp.where(done[:, None], U32(0), s["alloc"]),
        alloc_node=jnp.where(done, -1, s["alloc_node"]),
        mem=jnp.where(done, 0.0, s["mem"]),
    )
    m["completed"] += count(done)
    m["completed_f"] += count(done & ~s["contig"])
    m["completed_l"] += count(done & s["contig"])
    m["completed_tier"] += per_tier(s["tier"], done)


# ------------------------------------------------ node view and Z-HAF


def true_view(c, s):
    """(free atoms, queued probes, longest free run) of every node, as floats."""
    n = c.num_nodes
    queued = s["st"] == QUEUED
    heat = jax.ops.segment_sum(queued.astype(jnp.int32), jnp.where(queued, s["node"], n),
                               num_segments=n + 1)[:n]
    whole, _ = runs(s["free"])
    return (jnp.sum(s["free"], axis=1).astype(jnp.float32), heat.astype(jnp.float32),
            jnp.max(whole, axis=1).astype(jnp.float32))


def reports(c, s, key, view):
    """Nodes whose report is due send it (lost with probability hop_loss);
    a delivered report refreshes the node's slack, heat, run and their
    derivative estimates, and every due node schedules its next report."""
    n = c.num_nodes
    t = s["t"]
    slack, heat, run = view
    k_loss, k_jitter = jax.random.split(key)
    due = t >= s["next_rep"]
    sent = due & (jax.random.uniform(k_loss, (n,)) >= c.hop_loss)
    gap_ms = jnp.maximum((t - s["rep_t"]).astype(jnp.float32) * c.dt_ms, c.dt_ms)
    a = c.deriv_ema
    d_slack = (1 - a) * s["dS"] + a * (slack - s["rep_S"]) / gap_ms
    d_heat = (1 - a) * s["dH"] + a * (heat - s["rep_H"]) / gap_ms
    every = c.ticks(c.report_interval_ms + c.extra_sync_delay_ms)
    jitter = c.report_jitter_frac * every * jax.random.normal(k_jitter, (n,))
    s.update(
        rep_S=jnp.where(sent, slack, s["rep_S"]),
        rep_H=jnp.where(sent, heat, s["rep_H"]),
        rep_run=jnp.where(sent, run, s["rep_run"]),
        rep_t=jnp.where(sent, t, s["rep_t"]),
        dS=jnp.where(sent, d_slack, s["dS"]),
        dH=jnp.where(sent, d_heat, s["dH"]),
        next_rep=jnp.where(due, t + jnp.maximum(1, every + jitter.astype(jnp.int32)),
                           s["next_rep"]),
    )


def zone_refresh(c, s):
    """Every teg_refresh_ms: each zone's mean reported slack and total heat."""
    z = s["zstart"].shape[0]
    due = s["t"] % c.ticks(c.teg_refresh_ms) == 0
    mean_slack = (jax.ops.segment_sum(s["rep_S"], s["zone_id"], num_segments=z)
                  / jnp.maximum(s["zcount"], 1).astype(jnp.float32))
    heat = jax.ops.segment_sum(s["rep_H"], s["zone_id"], num_segments=z)
    s["zS"] = jnp.where(due, mean_slack, s["zS"])
    s["zH"] = jnp.where(due, heat, s["zH"])


# ---------------------------------------------------------------- arrivals


def pick(key, values, probs, n):
    """n draws of ``values`` with probabilities ``probs``."""
    p = jnp.asarray(probs, jnp.float32)
    return jnp.asarray(values, jnp.float32)[
        jax.random.choice(key, len(values), shape=(n,), p=p / p.sum())]


def arrivals(c, s, m, key, lam):
    """Poisson(lam) new tasks, at most max_arrivals_per_tick, each taking the
    lowest free slot in turn; a task that finds no slot is dropped. Returns
    the slots filled, which the gateway routes this tick.

    A task is an F-task (dispersed atoms, exponential service) with
    probability f_share, else an L-task (contiguous atoms, lognormal
    service); mass and priority are drawn per class, the tier from the mix,
    E_v = priority x mass x tier multiplier, and patience starts at
    priority x mass. Its true memory may exceed the declared mass.
    """
    wl, mc = c.wl, c.mem
    cap = c.max_arrivals_per_tick
    k_batch, k_over, k_over_size = jax.random.split(key, 3)
    k = jax.random.split(k_batch, 11)
    n = jnp.minimum(jax.random.poisson(k[0], lam), cap).astype(jnp.int32)
    large = jax.random.uniform(k[1], (cap,)) >= wl.f_share
    squat = jax.random.uniform(k[2], (cap,)) < wl.squatter_ratio
    tp = jnp.asarray(wl.tier_probs, jnp.float32)
    tier = jax.random.choice(k[10], len(wl.tier_probs), shape=(cap,),
                             p=tp / tp.sum()).astype(jnp.int32)
    mass = jnp.where(large, pick(k[4], wl.l_masses, wl.l_mass_probs, cap),
                     pick(k[3], wl.f_masses, wl.f_mass_probs, cap)).astype(jnp.int32)
    prio = jnp.where(large, pick(k[6], wl.l_priorities, wl.l_priority_probs, cap),
                     pick(k[5], wl.f_priorities, wl.f_priority_probs, cap))
    energy = prio * mass.astype(jnp.float32)
    ev = energy * jnp.asarray(wl.tier_ev_mult, jnp.float32)[tier]
    svc_f = jax.random.exponential(k[7], (cap,)) * wl.f_service_mean_ms
    svc_l = wl.l_service_median_ms * jnp.exp(wl.l_service_sigma * jax.random.normal(k[8], (cap,)))
    service = jnp.maximum(1, jnp.round(jnp.where(large, svc_l, svc_f) / c.dt_ms)).astype(jnp.int32)
    pull_ms = jax.random.exponential(k[9], (cap,)) * jnp.where(
        large, c.l_pull_mean_ms, c.f_pull_mean_ms)
    pull = jnp.maximum(1, jnp.round(pull_ms / c.dt_ms)).astype(jnp.int32)
    over = ((jax.random.uniform(k_over, (cap,)) < mc.overclaim_prob)
            * jax.random.uniform(k_over_size, (cap,)) * mc.overclaim_max)
    mem = mass.astype(jnp.float32) * (1.0 + over) * mc.mem_per_atom
    mem = mem / c.atoms_per_node

    # the r-th free slot (in slot order) takes the r-th task
    empty = s["st"] == EMPTY
    rank = jnp.cumsum(empty.astype(jnp.int32)) - 1
    new = empty & (rank < n)
    row = jnp.clip(rank, 0, cap - 1)

    def put(field, value):
        s[field] = jnp.where(new, value, s[field])

    put("contig", large[row])
    put("squat", squat[row])
    put("tier", tier[row])
    put("migrating", False)
    put("mass", mass[row])
    put("ev", ev[row])
    put("patience", energy[row])
    put("deposit", 0.0)
    put("pull_dur", pull[row])
    put("pull_deadline", 0)
    put("surv_deadline", 0)
    put("arrival", s["t"])
    put("start", -1)
    put("service", service[row])
    put("regen", 0)
    put("mem", mem[row])
    put("alloc_node", -1)
    put("node2", -1)
    s["alloc"] = jnp.where(new[:, None], U32(0), s["alloc"])
    s["alloc2"] = jnp.where(new[:, None], U32(0), s["alloc2"])

    admitted = count(new)
    m["arrived"] += admitted
    m["arrived_f"] += count(new & ~s["contig"])
    m["arrived_l"] += count(new & s["contig"])
    m["arrived_squat"] += count(new & s["squat"])
    m["dropped_capacity"] += n - admitted
    return new


# -------------------------------------------------------------------- hops


def hops(c, s, m, key):
    """Probes in flight land after their hop. A bounce hop is lost with
    probability hop_loss: with regeneration the probe waits the quiet
    interval and is sent again through the gateway with fresh patience (up
    to regen_cap times), without it the probe dies. Returns the probes
    regenerated this tick."""
    st, timer = s["st"], s["timer"]
    flying = (st == ROUTING) | (st == BOUNCING)
    waiting = st == LOST_WAIT
    timer = jnp.where(flying | waiting, timer - 1, timer)
    landed = flying & (timer <= 0)
    lost = landed & (st == BOUNCING) & (jax.random.uniform(key, st.shape) < c.hop_loss)
    new = jnp.where(landed & ~lost & (st == ROUTING), ADDRESSING, st)
    new = jnp.where(landed & ~lost & (st == BOUNCING), QUEUED, new)
    if c.regeneration:
        new = jnp.where(lost, LOST_WAIT, new)
        timer = jnp.where(lost, c.ticks(c.regen_quiet_ms), timer)
    else:
        new = dissolve(new, s["migrating"], lost)
    quiet = waiting & (timer <= 0)
    again = quiet & (s["regen"] < c.regen_cap)
    spent = quiet & ~again
    s.update(
        st=dissolve(new, s["migrating"], spent),
        timer=timer,
        regen=jnp.where(again, s["regen"] + 1, s["regen"]),
        patience=jnp.where(again, s["ev"], s["patience"]),
    )
    m["lost"] += count(lost)
    m["regen_spawned"] += count(again)
    m["regen_exhausted"] += count(spent)
    return again


# ----------------------------------------------------------------- gateway


def route(c, s, m, key, send):
    """The gateway sends each probe in ``send`` to a zone drawn with
    probability proportional to 2^(U_z / tau), U_z from the zone's
    aggregates, and to a node drawn uniformly in that zone: one hop. Of more
    than max_arrivals_per_tick + 256 probes, those in the lowest slots go."""
    rows = c.max_arrivals_per_tick + 256
    z = s["zstart"].shape[0]
    k_zone, k_node = jax.random.split(key)
    logits = utility(s["zS"], s["zH"], c.gamma_repulsion) * (jnp.log(2.0) / c.teg_temperature)
    zone = jnp.argmax(logits[None, :] + jax.random.gumbel(k_zone, (rows, z)),
                      axis=-1).astype(jnp.int32)
    u = jax.random.uniform(k_node, (rows,))
    node = s["zstart"][zone] + jnp.floor(u * s["zcount"][zone].astype(jnp.float32)).astype(jnp.int32)
    node = jnp.clip(node, 0, c.num_nodes - 1)

    rank = jnp.cumsum(send.astype(jnp.int32)) - 1
    go = send & (rank < rows)
    row = jnp.clip(rank, 0, rows - 1)
    s.update(
        st=jnp.where(go, ROUTING, s["st"]),
        zone=jnp.where(go, zone[row], s["zone"]),
        node=jnp.where(go, node[row], s["node"]),
        timer=jnp.where(go, 1, s["timer"]),
    )
    m["op_dispatch"] += count(go)


# -------------------------------------------------------------- addressing


def address(c, s, m, key, view):
    """Every addressing probe weighs its own node and candidate_k - 1 nodes
    drawn uniformly in its zone. Its own node it sees as it is; the others
    through their last report, projected sense_delay_ms ahead along the
    derivative and, for a report older than degrade_after_ms, with slack
    and run halved and heat doubled per degrade_halflife_ms of age beyond.
    A candidate fits if its (run for an L-task, else slack) covers the mass.
    The probe takes the fitting candidate of highest U + noise, but keeps its
    own node, if that fits, unless another beats it by more than stay_margin.
    Staying queues it here; choosing another node costs a bounce hop. Each
    evaluation costs eval_cost patience, a bounce bounce_cost more; below
    fastfail_floor the probe dies.
    """
    n, k = c.num_nodes, c.candidate_k
    p = s["st"].shape[0]
    t = s["t"]
    k_cand, k_noise = jax.random.split(key)
    active = s["st"] == ADDRESSING
    here = jnp.maximum(s["node"], 0)
    size = jnp.maximum(s["zcount"][s["zone"]], 1).astype(jnp.float32)
    others = s["zstart"][s["zone"]][:, None] + jnp.floor(
        jax.random.uniform(k_cand, (p, k - 1)) * size[:, None]).astype(jnp.int32)
    others = jnp.clip(others, 0, n - 1)

    slack_seen = jnp.maximum(0.0, s["rep_S"][others] + c.sense_delay_ms * s["dS"][others])
    heat_seen = jnp.maximum(0.0, s["rep_H"][others] + c.sense_delay_ms * s["dH"][others])
    age_ms = (t - s["rep_t"][others]).astype(jnp.float32) * c.dt_ms
    fade = jnp.exp2(-jnp.maximum(0.0, age_ms - c.degrade_after_ms) / c.degrade_halflife_ms)
    slack_true, heat_true, run_true = view
    slack = jnp.concatenate([slack_true[here][:, None], slack_seen * fade], axis=1)
    heat = jnp.concatenate([heat_true[here][:, None], heat_seen / jnp.maximum(fade, 1e-6)],
                           axis=1)
    run = jnp.concatenate([run_true[here][:, None], s["rep_run"][others] * fade], axis=1)
    cand = jnp.concatenate([here[:, None], others], axis=1)

    need = s["mass"].astype(jnp.float32)[:, None]
    fits = jnp.where(s["contig"][:, None], run >= need, slack >= need)
    noise = c.addr_noise_sigma * jax.random.normal(k_noise, (p, k))
    score = jnp.where(fits, utility(slack, heat, c.gamma_repulsion) + noise, -jnp.inf)
    best = jnp.argmax(score, axis=1)
    top = jnp.max(score, axis=1)
    keep = fits[:, 0] & (top <= score[:, 0] + c.stay_margin)
    target = jnp.where(keep, here, cand[jnp.arange(p), best])
    any_fit = jnp.any(fits, axis=1)
    stay = active & any_fit & (target == s["node"])
    bounce = active & any_fit & (target != s["node"])

    patience = jnp.where(active, s["patience"] - c.eval_cost, s["patience"])
    patience = jnp.where(bounce, patience - c.bounce_cost, patience)
    st = jnp.where(stay, QUEUED, s["st"])
    st = jnp.where(bounce, BOUNCING, st)
    fail = active & (patience < c.fastfail_floor)
    s.update(
        st=dissolve(st, s["migrating"], fail),
        node=jnp.where(bounce, target, s["node"]),
        zone=jnp.where(bounce, s["zone_id"][target], s["zone"]),
        timer=jnp.where(bounce, 1, s["timer"]),
        patience=patience,
    )
    m["op_eval"] += count(active)
    m["op_bounce"] += count(bounce & ~fail)
    m["fastfail"] += count(fail)


# ------------------------------------------------------------- arbitration


def best_fit(plane, need, contiguous):
    """Per node, the atoms its winner's demand takes: (taken (N, A), fits (N,)).

    Dispersed: the ``need`` free atoms of the shortest free runs, lowest
    position first among equals. Contiguous: the first ``need`` atoms of the
    shortest free run that holds them, the earliest such run on a tie.
    """
    a = plane.shape[1]
    pos = jnp.arange(a, dtype=jnp.int32)
    whole, _ = runs(plane)
    order = jnp.where(plane, whole * a + pos, jnp.iinfo(jnp.int32).max)
    ahead = jnp.sum(order[:, None, :] < order[:, :, None], axis=-1)  # atoms before each
    fits_d = jnp.sum(plane, axis=1) >= need
    taken_d = plane & (ahead < need[:, None]) & fits_d[:, None]

    opens = plane & jnp.concatenate(
        [jnp.ones((plane.shape[0], 1), bool), ~plane[:, :-1]], axis=1)
    room = opens & (whole >= need[:, None])
    first = jnp.argmin(jnp.where(room, whole * a + pos, jnp.iinfo(jnp.int32).max), axis=1)
    fits_c = jnp.any(room, axis=1) & (need > 0)
    taken_c = ((pos >= first[:, None]) & (pos < (first + need)[:, None])) & fits_c[:, None]
    return (jnp.where(contiguous[:, None], taken_c, taken_d),
            jnp.where(contiguous, fits_c, fits_d))


def arbitrate(c, s, m, throttled, plane):
    """One admission round at every node that is not throttled: among the
    probes queued there the highest E_v wins (highest slot on a tie). If the
    node's free atoms fit the winner, they are reserved: a fresh probe pays
    its deposit and waits out its payload pull (within pull_ttl_ms under
    two-phase reservation), a migrating one its suspended-state pull into
    ``alloc2``. A winner that does not fit pays eval_cost and addresses
    again. Returns the free plane after the round."""
    n = c.num_nodes
    p = s["st"].shape[0]
    t = s["t"]
    slot = jnp.arange(p, dtype=jnp.int32)
    at = jnp.clip(s["node"], 0, n - 1)
    waiting = (s["st"] == QUEUED) & ~throttled[at]
    win = elect(s["node"], waiting, s["ev"], slot)
    winner_of = jnp.full(n + 1, -1, jnp.int32).at[jnp.where(win, s["node"], n)].set(slot)[:n]
    has = winner_of >= 0
    w = jnp.maximum(winner_of, 0)
    taken, fits = best_fit(plane, jnp.where(has, s["mass"][w], 0), s["contig"][w])
    fits = fits & has
    taken = taken & fits[:, None]
    words = to_words(taken)

    admit = win & fits[at]
    reject = win & ~admit
    fresh = admit & ~s["migrating"]
    patience = s["patience"]
    dep = (jnp.minimum(c.deposit, jnp.maximum(patience, 0.0)) if c.two_phase
           else jnp.zeros_like(patience))
    patience = jnp.where(fresh, patience - dep, patience)
    st = jnp.where(fresh, RESERVED, s["st"])
    timer = jnp.where(fresh, s["pull_dur"], s["timer"])
    deadline = jnp.where(fresh, t + c.ticks(c.pull_ttl_ms) if c.two_phase else NEVER,
                         s["pull_deadline"])
    s["deposit"] = jnp.where(fresh, dep, s["deposit"])
    s["alloc"] = jnp.where(fresh[:, None], words[at], s["alloc"])
    s["alloc_node"] = jnp.where(fresh, s["node"], s["alloc_node"])
    if c.airlock and c.mem.enabled:
        moving = admit & s["migrating"]
        st = jnp.where(moving, RESERVED, st)
        pull = jnp.ceil(s["mass"].astype(jnp.float32) * c.state_pull_ms_per_atom
                        / c.dt_ms).astype(jnp.int32) + 1
        timer = jnp.where(moving, pull, timer)
        deadline = jnp.where(moving, t + c.ticks(c.pull_ttl_ms), deadline)
        s["alloc2"] = jnp.where(moving[:, None], words[at], s["alloc2"])
        s["node2"] = jnp.where(moving, s["node"], s["node2"])
    s.update(
        st=jnp.where(reject, ADDRESSING, st),
        patience=jnp.where(reject, patience - c.eval_cost, patience),
        timer=timer, pull_deadline=deadline,
    )
    m["op_arb"] += count(win)
    m["infeasible_winner"] += count(reject)
    m["throttled_rounds"] += count(throttled)
    return plane & ~taken


# ----------------------------------------------------------- pending stage


def pending(c, s, m):
    """A reservation's pull counts down. Done within its deadline, a fresh
    task starts (its deposit returned) and a migrating one lands, its source
    freed, if the survival deadline has not passed (else both sides are
    reclaimed). Past the deadline unfinished, a migrating one is reclaimed
    and a fresh one loses its deposit and atoms and addresses again, or
    dies below fastfail_floor. Each start adds its arrival-to-start latency
    to the histograms (4 buckets per octave from 0.25 ms)."""
    t = s["t"]
    st, mig = s["st"], s["migrating"]
    reserved = st == RESERVED
    timer = jnp.where(reserved, s["timer"] - 1, s["timer"])
    done = reserved & (timer <= 0) & (t <= s["pull_deadline"])
    late = reserved & (timer > 0) & (t >= s["pull_deadline"])
    start = done & ~mig
    patience = jnp.where(start, s["patience"] + s["deposit"], s["patience"])
    st = jnp.where(start, RUNNING, st)
    free, alloc, alloc_node = s["free"], s["alloc"], s["alloc_node"]
    alloc2, node2 = s["alloc2"], s["node2"]
    migrating = mig
    if c.airlock and c.mem.enabled:
        landed = done & mig & (t <= s["surv_deadline"])
        failed = (late & mig) | (done & mig & (t > s["surv_deadline"]))
        free = give_back(free, alloc, alloc_node, landed | failed)
        free = give_back(free, alloc2, node2, failed)
        alloc = jnp.where(landed[:, None], alloc2, jnp.where(failed[:, None], U32(0), alloc))
        alloc_node = jnp.where(landed, node2, jnp.where(failed, -1, alloc_node))
        alloc2 = jnp.where((landed | failed)[:, None], U32(0), alloc2)
        node2 = jnp.where(landed | failed, -1, node2)
        st = jnp.where(landed, RUNNING, jnp.where(failed, EMPTY, st))
        migrating = mig & ~(landed | failed)
        m["migrated"] += count(landed)
        m["reclaimed"] += count(failed)
        m["reclaimed_tier"] += per_tier(s["tier"], failed)
    lapsed = late & ~mig
    free = give_back(free, alloc, alloc_node, lapsed)
    retry = lapsed & ~s["squat"]
    st = jnp.where(retry, jnp.where(patience >= c.fastfail_floor, ADDRESSING, EMPTY), st)
    st = jnp.where(lapsed & s["squat"], EMPTY, st)

    wait_ms = (t - s["arrival"]).astype(jnp.float32) * c.dt_ms
    bucket = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(wait_ms, 0.25) / 0.25) * 4.0)
                      .astype(jnp.int32), 0, HIST_BUCKETS - 1)
    m["lat_hist"] += jnp.zeros(HIST_BUCKETS, jnp.int32).at[
        jnp.where(start, bucket, HIST_BUCKETS)].add(1, mode="drop")
    m["lat_hist_tier"] += jnp.zeros((NUM_TIERS, HIST_BUCKETS), jnp.int32).at[
        jnp.where(start, s["tier"], NUM_TIERS), bucket].add(1, mode="drop")
    m["started"] += count(start)
    m["started_f"] += count(start & ~s["contig"])
    m["started_l"] += count(start & s["contig"])
    m["started_tier"] += per_tier(s["tier"], start)
    m["reserve_expired"] += count(lapsed)
    m["squat_expired"] += count(lapsed & s["squat"])
    s.update(
        st=st, timer=timer, patience=patience, free=free,
        start=jnp.where(start, t, s["start"]),
        deposit=jnp.where(start | lapsed, 0.0, s["deposit"]),
        alloc=jnp.where(lapsed[:, None], U32(0), alloc),
        alloc_node=jnp.where(lapsed, -1, alloc_node),
        alloc2=alloc2, node2=node2, migrating=migrating,
    )


def timeout(c, s, m):
    """A probe still in its control phase task_timeout_ms after arrival dies,
    returning any atoms it holds (a migrating one answers to T_surv instead)."""
    st = s["st"]
    control = (((st > EMPTY) & (st < RUNNING)) | (st == LOST_WAIT)) & ~s["migrating"]
    late = control & (s["t"] - s["arrival"] > c.ticks(c.task_timeout_ms))
    s.update(
        free=give_back(s["free"], s["alloc"], s["alloc_node"], late),
        st=jnp.where(late, EMPTY, st),
        alloc=jnp.where(late[:, None], U32(0), s["alloc"]),
        alloc_node=jnp.where(late, -1, s["alloc_node"]),
    )
    m["timeout"] += count(late)


# -------------------------------------------------------------------- tick


def step(c, lam, state):
    """The state one tick later."""
    s = dict(state)
    m = dict(state["metrics"])
    key, *k = jax.random.split(s["key"], 8)
    s["key"] = key
    n = c.num_nodes
    if c.mem.enabled:
        memory_dynamics(c, s, k[1])
        pressure, react = airlock(c, s, m)
        throttled = pressure > c.mem.high_watermark
    else:
        react = jnp.zeros_like(s["migrating"])
        throttled = jnp.zeros(n, bool)
    completions(c, s, m)
    view = true_view(c, s)
    reports(c, s, k[0], view)
    zone_refresh(c, s)
    arrived = arrivals(c, s, m, k[2], lam)
    regenerated = hops(c, s, m, k[3])
    route(c, s, m, k[4], arrived | regenerated | react)
    address(c, s, m, k[5], view)
    plane = s["free"]
    for _ in range(c.arb_rounds):
        plane = arbitrate(c, s, m, throttled, plane)
    s["free"] = plane
    pending(c, s, m)
    timeout(c, s, m)
    s["t"] = s["t"] + 1
    s["metrics"] = m
    return s
