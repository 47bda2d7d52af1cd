"""The plain reference run, and the control that must fail against it.

The reference is ``plainref``: a plain simulator written from the
scheduler's semantics, which imports nothing of the program. It builds its
own cluster from the configuration's geometry seed and takes only the run
seed's PRNG key.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from spec import HERE, seed_keys

if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import plainref  # noqa: E402


def to_bf16(x: jax.Array) -> jax.Array:
    """float32 rounded to bfloat16's precision (nearest, ties to even).

    Done in integer arithmetic: XLA on TPU, allowed excess precision by
    default, removes an f32 -> bf16 -> f32 convert pair, and the control
    then reads exactly like the reference.
    """
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def bf16_state(s):
    """The control's precision: every float32 field of the state kept in bfloat16."""
    return jax.tree.map(lambda x: to_bf16(x) if x.dtype == jnp.float32 else x, s)


def initial(kw: dict, geometry_seed: int, seed: int, device=None):
    """(config, state on ``device``, arrivals per tick) for one run seed."""
    key, _ = seed_keys(seed)
    cfg, s, lam = plainref.initial(kw, geometry_seed, key)
    return cfg, plainref.to_device(s, device), lam


def chunk(cfg, lam: float, ticks: int, lowp: bool = False):
    """``state -> state`` over ``ticks`` ticks; ``lowp`` runs the control,
    whose state is rounded to bfloat16 after every tick (and on entry, by
    ``run.control_side``)."""
    return plainref.runner(cfg, lam, ticks, after_tick=bf16_state if lowp else None)


def chunk_ends(kw: dict, geometry_seed: int, seed: int, warmup: int, segment: int,
               k: int, device=None) -> list:
    """Reference states at the end of each chunk of the segment, on the host.

    Entry ``i`` is the state at tick ``warmup + (i + 1) * k``.
    """
    cfg, s, lam = initial(kw, geometry_seed, seed, device)
    run = chunk(cfg, lam, k)
    for _ in range(warmup // k):
        s = run(s)
    ends = []
    for _ in range(segment // k):
        s = run(s)
        ends.append(jax.device_get(s))
    return ends
