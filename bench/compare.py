"""The comparison that decides ``correct``.

The program and the plain reference (``plainref``) run the same seed from
the same cluster. What a scheduler decides is integer: who arrives where,
which node a probe weighs and queues at, who wins, which atoms it takes,
who starts, completes, is suspended or reclaimed. Those are compared exact;
the floats the decisions read (patience, memory, reported slack and heat
and their derivatives, zone aggregates) are compared within a tolerance, so
that float work done in another order still passes.

* ``counters_off``: over every chunk the window ran, the counters
  (arrivals, starts, completions, kills, suspensions, op counts, per-tier
  vectors, latency histograms) that differ from the reference's at the same
  tick. Limit 0.
* ``decisions_off``: at the window's last chunk end, the elements of the
  integer and boolean tables (probe states, nodes, zones, timers, deadlines,
  held atoms, the free-atom plane, the node report ticks, the PRNG key) that
  differ from the reference's. Limit 0.
* ``float_gap``: at the same tick, the widest gap of a float field,
  |program - reference| / max(|reference|, 1). Its limit is set between the
  program's readings and the control's (PERF.md).
* ``conservation_gap``: started tasks not accounted for as completed,
  killed, reclaimed, evicted or still resident, at that same tick. Limit 0.
"""

from __future__ import annotations

import numpy as np

# state-machine codes of a probe slot
EMPTY, RUNNING, SUSPENDED = 0, 6, 7

LIMITS = {"counters_off": 0, "decisions_off": 0, "float_gap": 1e-3,
          "conservation_gap": 0}

DECISION_FIELDS = (
    "t", "key", "st", "zone", "node", "contig", "squat", "tier", "migrating",
    "mass", "timer", "pull_dur", "pull_deadline", "surv_deadline", "susp_tick",
    "arrival", "start", "service", "regen", "alloc", "alloc_node", "alloc2",
    "node2", "zone_id", "rep_t", "next_rep", "zstart", "zcount",
)
FLOAT_FIELDS = (
    "ev", "patience", "deposit", "mem", "rep_S", "rep_H", "rep_run", "dS", "dH",
    "amb", "rigid_mem", "zS", "zH",
)


def host(tree) -> dict:
    """A state's (or a Metrics') fields as host numpy arrays, by name."""
    if isinstance(tree, dict):
        return {f: host(v) if isinstance(v, dict) else np.asarray(v) for f, v in tree.items()}
    return {
        f: host(v) if hasattr(v, "_fields") else np.asarray(v)
        for f, v in zip(tree._fields, tree)
    }


def n_differ(a: np.ndarray, b: np.ndarray) -> int:
    """Elements of ``a`` and ``b`` that differ (all, on a shape mismatch)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size)
    return int(np.count_nonzero(a != b))


def free_plane(words: np.ndarray, atoms: int) -> np.ndarray:
    """(N, W) uint32 words, atom i at bit i % 32 of word i // 32 -> (N, A) bool."""
    w = np.asarray(words, np.uint32)
    bits = (w[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(w.shape[0], -1)[:, :atoms].astype(bool)


def counters_off(prog: list, ref: list) -> int:
    """``prog``/``ref``: host counter dicts at the same ticks, pairwise.

    Every counter the program keeps is compared; one the reference lacks
    counts wholly against it.
    """
    off = 0
    for p, r in zip(prog, ref, strict=True):
        for f, v in p.items():
            off += n_differ(v, r[f]) if f in r else np.size(v)
    return off


def decisions_off(prog: dict, ref: dict) -> int:
    off = sum(n_differ(prog[f], ref[f]) for f in DECISION_FIELDS)
    return off + n_differ(free_plane(prog["free"], ref["free"].shape[1]), ref["free"])


def float_gap(prog: dict, ref: dict) -> float:
    worst = 0.0
    for f in FLOAT_FIELDS:
        p = np.asarray(prog[f], np.float64)
        r = np.asarray(ref[f], np.float64)
        if p.shape != r.shape:
            return float("inf")
        gap = np.abs(p - r) / np.maximum(np.abs(r), 1.0)
        if gap.size:
            worst = max(worst, float(np.nan_to_num(gap, nan=np.inf).max()))
    return worst


def conservation_gap(state: dict) -> int:
    metrics = state["metrics"]
    st, mig = state["st"], state["migrating"]
    resident = ((st == RUNNING) | (st == SUSPENDED) | (mig & (st != EMPTY))).sum()
    accounted = (
        metrics["completed"] + metrics["oom_kill_f"] + metrics["oom_kill_l"]
        + metrics["reclaimed"] + metrics["evicted_killed"] + resident
    )
    return abs(int(metrics["started"]) - int(accounted))


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
