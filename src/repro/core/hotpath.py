"""Hot-path kernel dispatch: route the measured ops through Pallas.

The paper micro-optimizes three control-plane operations (§V-A): bitmap
feasibility (4.02 ns), DA utility scoring (13.7 ns) and zone aggregation
(29.3 ns); the fourth op is Airlock's per-tick survival ladder (§III-G/H/I
— pressure accumulation, extreme-victim selection, transition masks), whose
victim election is the kernel. This module is the single switch point
between the pure-jnp reference implementations (`repro.kernels.*.ref`) and
their Pallas kernels (`repro.kernels.*.kernel`):

  * ``cfg.use_pallas = False`` (default) — pure-jnp references, the
    portable CPU path.
  * ``cfg.use_pallas = True`` — Pallas kernels: native on TPU,
    ``interpret=True`` on any other backend (identical semantics, used on
    the CPU as the correctness harness).

``cfg.use_pallas`` is a *static* config field, so the branch is resolved at
trace time and the jitted tick function specializes to exactly one path —
there is no runtime dispatch cost. Engine call sites (``arbiter``, ``da``,
``teg``, ``airlock``/``engine`` for the survival scan) go through this
module only; a kernel optimization is therefore a one-file change that the
parity tests and ``bench_hotpath`` pick up automatically.

The node-indexed ops (``bitmap_fit``, ``zone_aggregate``) serve both
layouts with the same kernels: they grid over rows, so the zone-sharded
engine's blocked node plane (``repro.parallel.engine_mesh.MeshPlane``)
passes its local zone-block rows through these exact entry points and gets
bit-identical per-row results. The probe-indexed ops (``utility_topk``,
``survival_scan``) run replicated under the mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitmap as _bitmap
from repro.core import state as _state
from repro.core.config import LaminarConfig
from repro.kernels.bitmap_fit import ops as _bitmap_ops
from repro.kernels.survival_scan import ops as _surv_ops
from repro.kernels.survival_scan import ref as _surv_ref
from repro.kernels.utility_topk import ops as _topk_ops
from repro.kernels.utility_topk.kernel import BLOCK_P as TOPK_BLOCK
from repro.kernels.zone_aggregate import ops as _agg_ops

__all__ = [
    "TOPK_BLOCK",
    "bitmap_fit",
    "bitmap_fit_blocked",
    "survival_scan",
    "utility_topk",
    "zone_aggregate",
]

# the survival_scan kernel package hardcodes the state-machine codes to stay
# importable without repro.core; fail loudly here if they ever drift
assert (_surv_ref.EMPTY, _surv_ref.RUNNING, _surv_ref.SUSPENDED) == (
    _state.EMPTY,
    _state.RUNNING,
    _state.SUSPENDED,
), "survival_scan state codes out of sync with repro.core.state"


def bitmap_fit(
    cfg: LaminarConfig,
    words: jax.Array,
    mass: jax.Array,
    contig: jax.Array,
    bits: jax.Array | None = None,
) -> jax.Array:
    """Per-node feasibility (int32 0/1) of each node's demand vs its bitmap.

    The Pallas kernel operates on the packed word representation (the
    system's native form). When the caller already holds the unpacked
    (N, A) bit plane — the arbiter threads one across admission rounds —
    passing it as ``bits`` lets the jnp path skip re-unpacking ``words``;
    the feasibility semantics are identical either way.
    """
    if cfg.use_pallas:
        return _bitmap_ops.bitmap_fit(words, mass, contig)
    if bits is None:
        return _bitmap_ops.bitmap_fit_ref(words, mass, contig)
    m = mass.astype(jnp.int32)
    ok = _bitmap.feasible_for_class(
        jnp.sum(bits, axis=-1), _bitmap.max_run(bits), m, contig.astype(bool)
    )
    return (ok | (m == 0)).astype(jnp.int32)


def bitmap_fit_blocked(
    cfg: LaminarConfig,
    words: jax.Array | None,
    mass: jax.Array,
    contig: jax.Array,
    bits: jax.Array | None = None,
) -> jax.Array:
    """Zone-blocked feasibility: ``(Z, M)`` inputs, ``(Z, M)`` int32 out.

    The zone-sharded engine's production path for its local zone block.
    The pallas route is the SAME kernel gridded over block rows
    (``ops.bitmap_fit_blocked``); the jnp route reuses :func:`bitmap_fit`
    on the flattened rows, so per-row results are bit-identical to the
    flat layout in both modes. ``bits`` is the flattened ``(Z*M, A)`` bit
    plane (jnp path); ``words`` the ``(Z, M, W)`` word plane (pallas path).
    """
    if cfg.use_pallas:
        return _bitmap_ops.bitmap_fit_blocked(words, mass, contig)
    Z, M = mass.shape
    return bitmap_fit(
        cfg, None, mass.reshape(-1), contig.reshape(-1), bits=bits
    ).reshape(Z, M)


def utility_topk(
    cfg: LaminarConfig,
    s_pred: jax.Array,
    h_pred: jax.Array,
    eps: jax.Array,
    feasible: jax.Array,
    gamma: jax.Array,
):
    """Best candidate per probe: (best_idx (P,), best_score (P,))."""
    if cfg.use_pallas:
        return _topk_ops.utility_topk(s_pred, h_pred, eps, feasible, gamma)
    return _topk_ops.utility_topk_ref(s_pred, h_pred, eps, feasible, gamma)


def zone_aggregate(
    cfg: LaminarConfig, s_gather: jax.Array, h_gather: jax.Array, mask: jax.Array
):
    """Per-zone (mean slack, total heat) from densified node gathers."""
    if cfg.use_pallas:
        return _agg_ops.zone_aggregate(s_gather, h_gather, mask)
    return _agg_ops.zone_aggregate_ref(s_gather, h_gather, mask)


def survival_scan(cfg: LaminarConfig, s):
    """Fused per-tick survival decision over the probe table (§III-G/H/I).

    Takes the full ``SimState`` (the op consumes nine of its columns) and
    returns ``(pressure (N,), victim, resume, react, expire)``. The victim is
    the per-node extreme — largest memory under kernel OOM, lowest E_v within
    the node's worst workload class under Airlock (strict tier precedence) —
    and the transition masks are empty when ``cfg.airlock`` is off.
    """
    mc = cfg.memory
    args = (
        s.st,
        s.alloc_node,
        s.mem,
        s.ev,
        s.tier,
        s.migrating,
        s.susp_tick,
        s.surv_deadline,
        s.rigid_mem + s.amb,
        s.t,
    )
    kw = dict(
        airlock=cfg.airlock,
        residual=mc.suspended_residual,
        watermark=mc.high_watermark if cfg.airlock else mc.kill_watermark,
        safe=mc.safe_watermark,
        t_susp=cfg.ticks(cfg.t_susp_ms),
        t_surv=cfg.ticks(cfg.t_surv_ms),
    )
    if cfg.use_pallas:
        return _surv_ops.survival_scan(*args, **kw)
    return _surv_ops.survival_scan_ref(*args, **kw)
