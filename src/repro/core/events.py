"""Per-slot events accumulated from their compacted rows.

A tick's events (completions, starts, expiries, reclamations, timeouts)
each touch a few percent of the probe table's P slots at most, and each is
added into a small table: freed atom words per node, counts per tier, a
latency histogram. On the TPU an XLA scatter costs about the same for each
of its update rows, however small its target and however few rows its mask
lets through. So these accumulations do not scatter over all P slots:
``for_rows`` ranks the rows of a mask by a cumulative count and hands them
to a body ``row_block`` at a time, as many trips as the count needs, and the
body scatters only those rows. ``da.address`` decides its addressing slots
with the same loop.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import hotpath
from repro.core.config import LaminarConfig
from repro.core.state import latency_bucket

# Rows per group of ``for_rows``' two-level lookup: a trip scatters one row
# per group to find each wanted rank's group, then gathers one row of
# LOOKUP_GROUP counts for each of its ``block`` ranks.
LOOKUP_GROUP = 256


def row_block(cfg: LaminarConfig, num_slots: int) -> int:
    """Rows per trip of a ``for_rows`` loop over a table of ``num_slots``.

    The smallest multiple of the utility_topk kernel's probe block that
    holds twice a tick's arrival cap, capped at the table: a steady tick's
    addressing DAs, and each of its events, fit one trip, and a busier
    tick takes more trips.
    """
    want = max(2 * cfg.max_arrivals_per_tick, 1)
    return min(-(-want // hotpath.TOPK_BLOCK) * hotpath.TOPK_BLOCK, num_slots)


def for_rows(
    mask: jax.Array,
    block: int,
    body: Callable[[jax.Array, jax.Array, Any], Any],
    init: Any,
) -> Any:
    """Fold ``body(row, on, carry) -> carry`` over the set rows of ``mask``.

    Each trip hands ``body`` the next ``block`` set rows in increasing
    order: ``row`` their indices, and ``on`` which lanes hold one (lanes
    past the last set row read row P - 1 and are off). A mask of n set rows
    takes ceil(n / block) trips, none when n is 0. The rows are found from a
    cumulative count, not by ``jnp.nonzero``, which lowers to a scatter
    over every row.

    The row of the r-th set row is the first whose count reaches r. It is
    looked up in two levels: the group of ``LOOKUP_GROUP`` rows whose last
    count first reaches r, then the place inside that group, by one gather
    of the group's counts. A trip's wanted ranks are consecutive, so the
    groups before each are counted at once: the groups that end before the
    trip, plus a running sum over the trip's lanes of the groups that end at
    each, one scatter of P / LOOKUP_GROUP rows. A binary search
    (``jnp.searchsorted``) would gather from the P-row count once per
    halving, about log2(P) dependent gathers of ``block`` rows.
    """
    P = mask.shape[0]
    groups = -(-P // LOOKUP_GROUP)
    # padding rows are never set, so they keep the last count
    rank = jnp.cumsum(
        jnp.pad(mask.astype(jnp.int32), (0, groups * LOOKUP_GROUP - P))
    ).reshape(groups, LOOKUP_GROUP)
    last = rank[:, -1]
    lane_rank = jnp.arange(1, block + 1, dtype=jnp.int32)

    def more(c):
        return c[0] * block < last[-1]

    def trip(c):
        b, carry = c
        # each group's last count, as a lane of this trip; groups that end
        # past the trip are dropped
        ends = last - b * block
        ended = jnp.sum((ends <= 0).astype(jnp.int32))
        lane = jnp.where(ends > 0, ends, block)
        ending = jnp.zeros((block,), jnp.int32).at[lane].add(1, mode="drop")
        # groups wholly before each wanted row; ``groups`` past the last set row
        g = ended + jnp.cumsum(ending)
        want = b * block + lane_rank
        inside = rank[jnp.minimum(g, groups - 1)] < want[:, None]
        slot = jnp.where(
            g < groups, g * LOOKUP_GROUP + jnp.sum(inside.astype(jnp.int32), axis=1), P
        )
        return b + 1, body(jnp.minimum(slot, P - 1), slot < P, carry)

    zero = jnp.zeros((), jnp.int32)
    return jax.lax.while_loop(more, trip, (zero, init))[1]


def accumulate(cfg: LaminarConfig, mask: jax.Array, body, init):
    """``for_rows`` over an event mask of the probe table, in the tick's
    ``events`` scope (nested under the stage that calls it)."""
    with jax.named_scope("events"):
        return for_rows(mask, row_block(cfg, mask.shape[0]), body, init)


def no_freed(free: jax.Array) -> jax.Array:
    """An empty accumulator of freed words for ``free``'s ``(N, W)`` nodes:
    ``N + 1`` rows, the last one taking the rows that free nothing."""
    return jnp.zeros((free.shape[0] + 1, free.shape[1]), free.dtype)


def add_freed(
    acc: jax.Array, alloc: jax.Array, node: jax.Array, on: jax.Array
) -> jax.Array:
    """Each ``on`` row's held words ``alloc`` added into ``acc`` at ``node``.

    Held allocations are disjoint, so the sum of a node's freed words is
    their bitwise or: ``free | acc[:-1]`` returns them all at once, in any
    order and from any number of calls. A row at node -1 holds nothing; it
    lands in the last row, as the rows that are off do.
    """
    tgt = jnp.where(on, node, acc.shape[0] - 1)
    return acc.at[tgt].add(jnp.where(on[:, None], alloc, jnp.uint32(0)))


def count(table: jax.Array, index: tuple, on: jax.Array) -> jax.Array:
    """One added into ``table`` at each ``on`` row's ``index`` (one array per
    axis of the table); rows that are off are dropped."""
    first = jnp.where(on, index[0], table.shape[0])
    return table.at[(first,) + tuple(index[1:])].add(
        on.astype(table.dtype), mode="drop"
    )


def count_latency(
    cfg: LaminarConfig,
    t: jax.Array,
    arrival: jax.Array,
    tier: jax.Array,
    on: jax.Array,
    hists: tuple,
) -> tuple:
    """``hists`` = (latency histogram, per-tier latency histogram) plus the
    ``on`` rows, which start at tick ``t``."""
    hist, hist_tier = hists
    bucket = latency_bucket((t - arrival).astype(jnp.float32) * cfg.dt_ms)
    return count(hist, (bucket,), on), count(hist_tier, (tier, bucket), on)


def release(cfg: LaminarConfig, free: jax.Array, mask: jax.Array, held: list):
    """``free`` with the atoms of the ``mask`` rows returned.

    ``held`` lists the ``(alloc, node)`` pairs of the table that those rows
    give back: each row's ``alloc`` words at its ``node``.
    """

    def add(row, on, acc):
        for alloc, node in held:
            acc = add_freed(acc, alloc[row], node[row], on)
        return acc

    return free | accumulate(cfg, mask, add, no_freed(free))[:-1]
