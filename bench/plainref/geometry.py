"""The cluster a configuration describes, and the state before the first tick.

Built on the host from the geometry seed with NumPy, in the order the
deployment is defined: zones of ``zone_size`` with a uniform size jitter
(none smaller than 8 nodes), then each node's rigid pre-occupancy painted
in ``rigid_chunks`` contiguous chunks at random offsets, then each node's
first report tick. Every probe slot starts empty.
"""

from __future__ import annotations

import numpy as np

from .codes import EMPTY


def zones(cfg, rng: np.random.Generator):
    """(start, count) of each zone: contiguous node ranges of jittered size."""
    sizes = []
    left = cfg.num_nodes
    while left > 0:
        j = 1.0 + rng.uniform(-cfg.zone_size_jitter, cfg.zone_size_jitter)
        size = int(max(8, min(left, round(cfg.zone_size * j))))
        if left - size < 8:
            size = left
        sizes.append(size)
        left -= size
    count = np.asarray(sizes, np.int32)
    start = np.concatenate([[0], np.cumsum(count)[:-1]]).astype(np.int32)
    return start, count


def rigid_plane(cfg, rng: np.random.Generator) -> np.ndarray:
    """(N, A) bool, True where an atom is free after the rigid painting.

    Each node draws a target share in [rigid_frac_lo, rigid_frac_hi); each
    of the chunks covers ceil(remaining / chunks) atoms from a random start,
    cut at the node's last atom, and counts only atoms it newly covers.
    """
    n, a = cfg.num_nodes, cfg.atoms_per_node
    free = np.ones((n, a), bool)
    target = (rng.uniform(cfg.rigid_frac_lo, cfg.rigid_frac_hi, size=n) * a).astype(np.int32)
    covered = np.zeros(n, np.int32)
    pos = np.arange(a)
    for _ in range(cfg.rigid_chunks):
        remaining = np.maximum(target - covered, 0)
        length = np.minimum(
            np.ceil(remaining / max(1, cfg.rigid_chunks)).astype(np.int32), remaining)
        first = rng.integers(0, a, size=n)
        span = (pos >= first[:, None]) & (pos < np.minimum(first + length, a)[:, None])
        covered += (free & span).sum(axis=1).astype(np.int32)
        free &= ~span
    return free


def longest_run(free: np.ndarray) -> np.ndarray:
    """Longest stretch of consecutive free atoms in each row."""
    best = np.zeros(free.shape[0], np.int64)
    cur = np.zeros(free.shape[0], np.int64)
    for col in free.T:
        cur = np.where(col, cur + 1, 0)
        best = np.maximum(best, cur)
    return best


def initial_state(cfg, geometry_seed: int) -> tuple[dict, float]:
    """(state, free atoms in the cluster) at tick 0; the PRNG key is set by the caller."""
    rng = np.random.default_rng(geometry_seed)
    zstart, zcount = zones(cfg, rng)
    free = rigid_plane(cfg, rng)
    first_report = rng.integers(0, cfg.ticks(cfg.report_interval_ms + cfg.extra_sync_delay_ms),
                                size=cfg.num_nodes)
    n, a, p = cfg.num_nodes, cfg.atoms_per_node, cfg.probe_capacity
    words = (a + 31) // 32
    count = free.sum(axis=1).astype(np.float32)
    zone_id = np.repeat(np.arange(len(zcount), dtype=np.int32), zcount)
    zsum = np.zeros(len(zcount), np.float32)
    np.add.at(zsum, zone_id, count)

    def probes(dtype, fill=0):
        return np.full(p, fill, dtype)

    state = {
        "t": np.int32(0),
        "st": probes(np.int32, EMPTY), "zone": probes(np.int32), "node": probes(np.int32, -1),
        "contig": probes(bool), "squat": probes(bool), "tier": probes(np.int32),
        "migrating": probes(bool), "mass": probes(np.int32), "ev": probes(np.float32),
        "patience": probes(np.float32), "deposit": probes(np.float32),
        "timer": probes(np.int32), "pull_dur": probes(np.int32),
        "pull_deadline": probes(np.int32), "surv_deadline": probes(np.int32),
        "susp_tick": probes(np.int32), "arrival": probes(np.int32),
        "start": probes(np.int32, -1), "service": probes(np.int32), "regen": probes(np.int32),
        "mem": probes(np.float32),
        "alloc": np.zeros((p, words), np.uint32), "alloc_node": probes(np.int32, -1),
        "alloc2": np.zeros((p, words), np.uint32), "node2": probes(np.int32, -1),
        "free": free,
        "zone_id": zone_id,
        "rep_S": count, "rep_H": np.zeros(n, np.float32),
        "rep_run": longest_run(free).astype(np.float32),
        "rep_t": np.zeros(n, np.int32),
        "dS": np.zeros(n, np.float32), "dH": np.zeros(n, np.float32),
        "next_rep": first_report.astype(np.int32),
        "amb": np.zeros(n, np.float32),
        "rigid_mem": (a - count) / np.float32(a),
        "zstart": zstart, "zcount": zcount,
        "zS": zsum / zcount.astype(np.float32), "zH": np.zeros(len(zcount), np.float32),
    }
    return state, float(count.sum())
