"""The plain reference's own rules, on cases worked out by hand.

Run by hand: ``python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

import tiny  # noqa: F401  (puts bench/ on the path)
from plainref import tick


def plane(*rows: str):
    return jnp.asarray([[ch == "1" for ch in r] for r in rows])


def test_runs_measure_each_free_stretch():
    whole, ending = tick.runs(plane("11011100"))
    assert whole.tolist() == [[2, 2, 0, 3, 3, 3, 0, 0]]
    assert ending.tolist() == [[1, 2, 0, 1, 2, 3, 0, 0]]


def test_dispersed_demand_takes_the_shortest_runs_first():
    taken, fits = tick.best_fit(plane("11101011"), jnp.asarray([2]), jnp.asarray([False]))
    # runs: [0..2] of 3, [4] of 1, [6..7] of 2 -> atom 4, then atom 6
    assert bool(fits[0])
    assert np.flatnonzero(np.asarray(taken[0])).tolist() == [4, 6]


def test_contiguous_demand_takes_the_smallest_run_that_holds_it():
    rows = plane("11101100", "11100111", "10000000")
    taken, fits = tick.best_fit(rows, jnp.asarray([2, 3, 2]), jnp.asarray([True, True, True]))
    assert np.asarray(fits).tolist() == [True, True, False]
    assert np.flatnonzero(np.asarray(taken[0])).tolist() == [4, 5]  # run of 2 over run of 3
    assert np.flatnonzero(np.asarray(taken[1])).tolist() == [0, 1, 2]  # equal runs: earliest
    assert not np.asarray(taken[2]).any()


def test_election_is_lexicographic_per_group():
    group = jnp.asarray([0, 0, 0, 1, 1, 2])
    eligible = jnp.asarray([True, True, True, True, False, False])
    ev = jnp.asarray([5.0, 7.0, 7.0, 1.0, 9.0, 3.0])
    slot = jnp.arange(6)
    won = tick.elect(group, eligible, ev, slot)
    assert np.flatnonzero(np.asarray(won)).tolist() == [2, 3]  # tie on 7: higher slot


def test_words_and_plane_round_trip_least_significant_bit_first():
    rows = np.zeros((1, 64), bool)
    rows[0, [0, 5, 33]] = True
    words = tick.to_words(jnp.asarray(rows))
    assert np.asarray(words).tolist() == [[1 | 1 << 5, 1 << 1]]
    assert (np.asarray(tick.to_plane(words, 64)) == rows).all()
