"""Airlock: bounded node-local runtime survival (§III-G/H/I, Exp5).

Converts severe physical memory pressure into an ordered policy instead of
blind kernel OOM destruction:

  pressure > high watermark  ->  reverse-recursive suspension in *ascending*
                                 E_v order (lowest declared value first)
  pressure < safe watermark  ->  in-situ resume (before T_susp)
  suspension beyond T_susp   ->  resident DA secondary reactivation (fresh
                                 patience, shared survival TTL T_surv)
  T_surv expiry              ->  bounded reclamation of task + DA

With Airlock disabled the model reproduces kernel-OOM behavior: above the kill
watermark the largest-memory resident is destroyed outright (the linux badness
heuristic), which is precisely what indiscriminately kills L-tasks.

The per-tick *decision* — per-node pressure accumulation, extreme-victim
selection, and the resume/reactivate/expire transition masks — is one fused
op (``hotpath.survival_scan``: pure-jnp reference or the Pallas
``survival_scan`` kernel, selected by ``cfg.use_pallas``). This module owns
the *application* of that decision to the state table and the metrics.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import events
from repro.core.config import LaminarConfig
from repro.core.state import EMPTY, RUNNING, SUSPENDED, SimState, tier_counts


def memory_dynamics(cfg: LaminarConfig, s: SimState, key: jax.Array) -> SimState:
    """Exp5 dynamic perturbation: AR(1) ambient noise + Bernoulli bursts +
    slow per-node drift (neighboring rigid workloads breathing)."""
    mc = cfg.memory
    if not mc.enabled:
        return s
    k_n, k_b, k_bs = jax.random.split(key, 3)
    N = cfg.num_nodes
    decay = mc.ambient_decay
    noise = jnp.sqrt(1 - decay**2) * mc.noise_sigma * jax.random.normal(k_n, (N,))
    burst = (
        (jax.random.uniform(k_b, (N,)) < mc.burst_rate)
        * jax.random.uniform(k_bs, (N,))
        * mc.burst_scale
    )
    phase = jnp.arange(N, dtype=jnp.float32) * 2.399  # golden-angle spread
    tsec = s.t.astype(jnp.float32) * cfg.dt_ms / 1e3
    drift = mc.drift_kappa * 0.5 * (1.0 + jnp.sin(2 * jnp.pi * tsec + phase))
    amb = jnp.clip(decay * (s.amb - drift) + noise + burst + drift, 0.0, 0.8)
    return s._replace(amb=amb)


def runtime_control(
    cfg: LaminarConfig, s: SimState, victim: jax.Array
) -> SimState:
    """Apply the per-node survival action (one action/node/tick).

    ``victim`` comes from ``hotpath.survival_scan``: the largest-memory
    resident above the kill watermark (kernel OOM) or the lowest-E_v resident
    above the high watermark (Airlock).
    """
    if not cfg.memory.enabled:
        return s

    if not cfg.airlock:
        # kernel OOM: destroy outright (badness ~ memory footprint) --
        # indiscriminate, kills L-tasks.
        free = events.release(cfg, s.free, victim, [(s.alloc, s.alloc_node)])
        m = s.metrics
        m = m._replace(
            oom_kill_f=m.oom_kill_f + jnp.sum((victim & ~s.contig).astype(jnp.int32)),
            oom_kill_l=m.oom_kill_l + jnp.sum((victim & s.contig).astype(jnp.int32)),
            oom_kill_tier=m.oom_kill_tier + tier_counts(s.tier, victim),
        )
        return s._replace(
            st=jnp.where(victim, EMPTY, s.st),
            free=free,
            alloc=jnp.where(victim[:, None], jnp.uint32(0), s.alloc),
            alloc_node=jnp.where(victim, -1, s.alloc_node),
            mem=jnp.where(victim, 0.0, s.mem),
            metrics=m,
        )

    # Airlock: reverse-recursive suspension, ascending E_v (lowest value first)
    m = s.metrics
    m = m._replace(
        suspended_cnt=m.suspended_cnt + jnp.sum(victim.astype(jnp.int32))
    )
    return s._replace(
        st=jnp.where(victim, SUSPENDED, s.st),
        susp_tick=jnp.where(victim, s.t, s.susp_tick),
        migrating=jnp.where(victim, False, s.migrating),
        metrics=m,
    )


def airlock_transitions(
    cfg: LaminarConfig,
    s: SimState,
    resume: jax.Array,
    react: jax.Array,
    expire: jax.Array,
) -> Tuple[SimState, jax.Array]:
    """Apply in-situ resume / threshold-triggered reactivation / survival
    expiry masks (from ``hotpath.survival_scan``).

    Returns (state, reactivation_dispatch_mask) -- reactivated DAs re-enter the
    network through TEG exactly like fresh probes (§III-D). The masks were
    computed on the post-suspension view of the table, so they compose with
    ``runtime_control`` exactly like the sequential ladder:

      1) in-situ recovery below the safe watermark (only if no reactivation
         yet — resume has priority over reactivation for fresh glass-state);
      2) threshold-triggered secondary reactivation beyond T_susp, granting a
         fresh E_patience budget and the shared survival TTL T_surv;
      3) shared TTL expiry: bounded reclamation of task + DA, freeing both
         the primary allocation and any destination reservation. Applies to
         ANY migrating incarnation (probing, queued, reserved at a
         destination, or back in glass-state after a failed attempt).
    """
    if not (cfg.memory.enabled and cfg.airlock):
        return s, jnp.zeros_like(s.migrating)

    st = jnp.where(resume, RUNNING, s.st)
    migrating = jnp.where(react, True, s.migrating)
    patience = jnp.where(react, s.ev, s.patience)  # fresh E_patience budget
    surv_deadline = jnp.where(react, s.t + cfg.ticks(cfg.t_surv_ms), s.surv_deadline)

    # both the primary allocation and any destination reservation (node2
    # is -1 where there is none)
    free = events.release(
        cfg, s.free, expire, [(s.alloc, s.alloc_node), (s.alloc2, s.node2)]
    )
    st = jnp.where(expire, EMPTY, st)

    m = s.metrics
    m = m._replace(
        resumed_insitu=m.resumed_insitu + jnp.sum(resume.astype(jnp.int32)),
        reactivated=m.reactivated + jnp.sum(react.astype(jnp.int32)),
        reclaimed=m.reclaimed + jnp.sum(expire.astype(jnp.int32)),
        reclaimed_tier=m.reclaimed_tier + tier_counts(s.tier, expire),
    )
    s = s._replace(
        st=st,
        migrating=jnp.where(expire, False, migrating),
        patience=patience,
        surv_deadline=surv_deadline,
        free=free,
        alloc=jnp.where(expire[:, None], jnp.uint32(0), s.alloc),
        alloc_node=jnp.where(expire, -1, s.alloc_node),
        alloc2=jnp.where(expire[:, None], jnp.uint32(0), s.alloc2),
        node2=jnp.where(expire, -1, s.node2),
        metrics=m,
    )
    dispatch = react & ~expire
    return s, dispatch
