"""The control, the reference in bfloat16 in the program's place, is not correct.

Run by hand: ``python -m pytest bench/tests -q``. On the chip, at the cells'
own sizes, ``bench/readings.py --side control`` reads the same numbers.
"""

from __future__ import annotations

import tiny  # noqa: F401  (puts bench/ on the path)
from test_faults import drive


def test_control_is_not_correct():
    r = drive("--side", "control")
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["counters_off"]["value"] > 0


def test_bf16_rounding_matches_a_bfloat16_round_trip():
    import numpy as np
    import jax.numpy as jnp

    import reference

    x = jnp.asarray(np.random.default_rng(0).normal(size=4096).astype(np.float32) * 1e3)
    lowered = reference.to_bf16(x)
    assert bool(jnp.all(lowered == x.astype(jnp.bfloat16).astype(jnp.float32)))
    assert bool(jnp.any(lowered != x))
