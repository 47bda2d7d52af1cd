"""Zone-sharded scale-out engine: the tick under ``shard_map``.

The flat engine simulates every zone on one device, so the regime where the
paper's decentralization claim bites (tens of thousands of nodes) is out of
reach. Zones are Laminar's natural independence boundary — TEG splits flow
over *zone aggregates* only, probing and arbitration are in-zone/node-local —
so the zone axis is the shard dimension.

Execution model
---------------
One 1-D device mesh with axis ``"zones"``. ``SimState`` enters the
``shard_map`` fully replicated (its flat node-major layout is the
interchange format; ``state.pack_zoned`` / ``state.unpack_zoned`` convert to
the padded ``(Z, M, ...)`` zone-blocked layout per tick). Work splits as:

  sharded     the O(N * A) node-bitmap pipeline: bit-plane unpack, max-run
              scans, per-winner feasibility + atom allocation, word packing
              — each device computes only its block of ``ceil(Z / D)`` zone
              rows (``MeshPlane``), through the SAME four hot-path kernels
              as the flat engine (they grid over rows, so a zone block is
              just a shorter row batch).

  replicated  the probe table and every O(N) float vector (reports,
              derivatives, ambient memory, PRNG). Replicated math is
              deterministic, so all devices hold identical copies and the
              probe plane never needs to migrate between shards even though
              probes hop zones every tick.

  exchanged   per tick, two kinds of ``all_gather``:
                * the (Z,) zone-aggregate table (zS, zH) on TEG refresh
                  ticks — this IS the paper's control-plane cost model
                  (O(num_zones) floats), now measurable (`traffic_model`);
                * per-node *results* of the sharded bitmap pipeline
                  (s_true/run_true and per-round allocation words) — a
                  simulator-fidelity artifact of keeping the probe plane
                  replicated: in the modeled system these values are read
                  node-locally by in-zone probes and never cross the
                  network. Reported separately as ``sim_sync_bytes``.

Contract (enforced by ``tests/test_shard_engine.py``): with mesh size 1 the
sharded engine reproduces the flat engine bit-for-bit; with mesh size > 1
metrics are still bit-for-bit identical because every cross-shard value is
an exact gather/scatter of deterministically computed rows — no reduction
ever crosses the shard boundary.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bitmap, hotpath, zhaf
from repro.core.config import LaminarConfig
from repro.core.engine import LaminarEngine, make_step
from repro.core.state import SimState, unpack_zoned
from repro.workloads.scenario import ScenarioConfig

AXIS = "zones"
# every cross-shard exchange of the tick runs inside this scope, nested in the
# stage (``laminar.<stage>``) that asks for it, so a trace reads it apart
EXCHANGE_SCOPE = "zone_exchange"

__all__ = [
    "AXIS",
    "EXCHANGE_SCOPE",
    "MeshPlane",
    "ZoneShardedEngine",
    "traffic_model",
    "zone_mesh",
]


def zone_mesh(num_devices: int | None = None, devices=None) -> Mesh:
    """1-D device mesh over the zone axis (defaults to every local device)."""
    devices = list(devices if devices is not None else jax.devices())
    d = int(num_devices) if num_devices is not None else len(devices)
    if not 1 <= d <= len(devices):
        raise ValueError(
            f"requested {d} devices, {len(devices)} available "
            "(on CPU, force more with XLA_FLAGS=--xla_force_host_platform_device_count=D)"
        )
    return Mesh(np.asarray(devices[:d]), (AXIS,))


class MeshPlane:
    """Node-plane strategy: zone-blocked shards of the bitmap pipeline.

    All methods run *inside* ``shard_map``. Inputs arrive replicated; the
    plane slices its own block of zone rows (``lax.axis_index``), computes
    on the blocked layout, and ``all_gather``s the per-node results back to
    the replicated flat layout that the probe plane consumes.
    """

    def __init__(self, cfg: LaminarConfig, num_devices: int, axis: str = AXIS):
        self.cfg = cfg
        self.D = int(num_devices)
        self.axis = axis

    # ---- blocked-layout plumbing ---------------------------------------

    def _local_rows(self, s: SimState):
        """This device's (Zb, M) slice of the padded member/mask matrices."""
        Z, M = s.zmember.shape
        Zb = -(-Z // self.D)
        pad = Zb * self.D - Z
        zmember = jnp.pad(s.zmember, ((0, pad), (0, 0)))
        zmask = jnp.pad(s.zmask, ((0, pad), (0, 0)))
        d = jax.lax.axis_index(self.axis)
        mem_l = jax.lax.dynamic_slice_in_dim(zmember, d * Zb, Zb, axis=0)
        msk_l = jax.lax.dynamic_slice_in_dim(zmask, d * Zb, Zb, axis=0)
        return mem_l, msk_l

    def _to_flat(self, x_l: jax.Array, s: SimState) -> jax.Array:
        """all_gather local (Zb, M, ...) blocks -> flat replicated (N, ...)."""
        with jax.named_scope(EXCHANGE_SCOPE):
            xb = jax.lax.all_gather(x_l, self.axis, axis=0, tiled=True)  # (Zp, M, ...)
            return unpack_zoned(xb, s.zmember, s.zmask, self.cfg.num_nodes)

    def _local_words(self, s: SimState, mem_l, msk_l) -> jax.Array:
        """(R, W) free bitmap words of the local rows; padding slots zeroed."""
        words = jnp.where(
            (msk_l > 0)[..., None], s.free[mem_l], jnp.uint32(0)
        )  # (Zb, M, W)
        return words.reshape(-1, s.free.shape[-1])

    # ---- the three node-plane hooks ------------------------------------

    def build_view(self, cfg: LaminarConfig, s: SimState):
        """Blocked view build; returns (NodeView, threaded local bit plane).

        s_true / run_true are computed row-wise on the local block — the
        exact per-node rows the flat engine computes — then gathered back.
        Heat is a probe-table scatter (replicated). ``NodeView.bits`` is
        None in mesh mode: the threaded plane is block-local.
        """
        mem_l, msk_l = self._local_rows(s)
        words_l = self._local_words(s, mem_l, msk_l)
        bits_l = bitmap.unpack_bits(words_l, cfg.atoms_per_node)  # (R, A)
        Zb, M = mem_l.shape
        s_true_l = jnp.sum(bits_l, axis=-1).astype(jnp.float32)
        run_l = bitmap.max_run(bits_l).astype(jnp.float32)
        s_true = self._to_flat(s_true_l.reshape(Zb, M), s)
        run_true = self._to_flat(run_l.reshape(Zb, M), s)
        h_true = zhaf.node_heat(cfg, s).astype(jnp.float32)
        return zhaf.NodeView(None, s_true, h_true, run_true), bits_l

    def alloc_round(self, cfg: LaminarConfig, s: SimState, bits_l, ws, has_w):
        """One admission round's bitmap math on the local zone block.

        Same op sequence as the flat inline path in ``arbiter.arbitrate``
        (hot-path feasibility kernel, class allocation, word packing), per
        local row; only the packed result words and the feasibility flags
        are exchanged. Padding rows are forced infeasible so they can never
        contribute atoms.
        """
        mem_l, msk_l = self._local_rows(s)
        Zb, M = mem_l.shape
        valid = (msk_l > 0).reshape(-1)
        ws_l = ws[mem_l].reshape(-1)
        mass_l = s.mass[ws_l]
        contig_l = s.contig[ws_l]
        words_b = (
            self._local_words(s, mem_l, msk_l).reshape(Zb, M, -1)
            if cfg.use_pallas
            else None
        )
        feas_hot = (
            hotpath.bitmap_fit_blocked(
                cfg, words_b, mass_l.reshape(Zb, M), contig_l.reshape(Zb, M),
                bits=bits_l,
            ).reshape(-1)
            != 0
        )
        alloc_bits_l, feas_l = bitmap.alloc_for_class(
            bits_l, mass_l, contig_l, policy=cfg.alloc_policy
        )
        feas_l = feas_l & feas_hot & has_w[mem_l].reshape(-1) & valid
        taken = alloc_bits_l & feas_l[:, None]
        alloc_words_l = bitmap.pack_bits(taken)
        bits_l = bits_l & ~taken

        alloc_words = self._to_flat(
            alloc_words_l.reshape(Zb, M, -1), s
        )  # (N, W) replicated
        feas_n = self._to_flat(feas_l.reshape(Zb, M), s)  # (N,) bool
        return alloc_words, feas_n, bits_l

    def zone_aggregates(self, cfg: LaminarConfig, s: SimState):
        """Local zone rows through the zone_aggregate kernel, then the O(Z)
        aggregate-table ``all_gather`` — the modeled control-plane exchange."""
        mem_l, msk_l = self._local_rows(s)
        zS_l, zH_l = hotpath.zone_aggregate(cfg, s.rep_S[mem_l], s.rep_H[mem_l], msk_l)
        Z = s.zmember.shape[0]
        with jax.named_scope(EXCHANGE_SCOPE):
            zS = jax.lax.all_gather(zS_l, self.axis, axis=0, tiled=True)[:Z]
            zH = jax.lax.all_gather(zH_l, self.axis, axis=0, tiled=True)[:Z]
        return zS, zH


def traffic_model(
    cfg: LaminarConfig, num_zones: int, num_devices: int, max_zone: int | None = None
) -> Dict[str, float]:
    """Per-tick cross-shard bytes of the sharded tick, by category.

    ``control_plane_bytes_per_tick`` is the modeled Laminar control plane:
    the (zS, zH) zone-aggregate table broadcast on TEG refresh ticks —
    O(num_zones) floats, *independent of num_nodes* for a fixed zone count.
    ``sim_sync_bytes_per_tick`` is the simulator-fidelity exchange (per-node
    results of the sharded bitmap pipeline feeding the replicated probe
    plane) — O(num_nodes), but *not* part of the modeled system: on real
    hardware those values are node-local reads by in-zone probes.

    An ``all_gather`` of a sharded X-byte array moves each device's X/D
    shard to D-1 peers: X * (D - 1) / D * D = X * (D - 1) bytes per tick of
    fabric traffic in a flat topology.
    """
    D = int(num_devices)
    peers = max(D - 1, 0)
    refresh_every = cfg.ticks(cfg.teg_refresh_ms)
    # (zS, zH): 2 float32 per zone, once per refresh interval
    ctrl = 2 * 4 * num_zones * peers / refresh_every

    M = int(max_zone) if max_zone else cfg.zone_size
    Zb = -(-num_zones // D)
    slots = Zb * D * M  # padded blocked slots actually transferred
    W = max(1, (cfg.atoms_per_node + 31) // 32)
    view_bytes = 2 * 4 * slots  # s_true + run_true, float32
    round_bytes = (4 * W + 1) * slots  # alloc words (uint32) + feas (bool)
    sync = (view_bytes + cfg.arb_rounds * round_bytes) * peers
    return {
        "num_zones": int(num_zones),
        "num_devices": D,
        "control_plane_bytes_per_tick": float(ctrl),
        "sim_sync_bytes_per_tick": float(sync),
    }


class ZoneShardedEngine(LaminarEngine):
    """`LaminarEngine` whose compiled scan runs under a zone-axis mesh.

    Drop-in for :class:`LaminarEngine`: same ``run`` / ``summarize``
    surface, same compiled-runner cache discipline (keys additionally carry
    the mesh size). ``run_batch`` falls back to sequential per-seed runs —
    ``vmap`` over ``shard_map`` is not part of this engine's contract.
    """

    def __init__(
        self,
        cfg: LaminarConfig,
        num_devices: int | None = None,
        devices=None,
    ):
        super().__init__(cfg)
        self.mesh = zone_mesh(num_devices, devices)
        self.num_devices = self.mesh.devices.size

    def _runner(
        self, lam: float, num_ticks: int, scenario: ScenarioConfig | None = None
    ):
        scenario = self.cfg.scenario if scenario is None else scenario
        key = (
            "mesh",
            self.num_devices,
            round(lam, 6),
            num_ticks,
            scenario.signature(),
        )
        if key not in self._compiled:
            plane = MeshPlane(self.cfg, self.num_devices)
            step = make_step(self.cfg, lam, scenario, plane=plane)

            def run(s: SimState):
                return jax.lax.scan(step, s, None, length=num_ticks)

            # the whole state is replicated (P()); sharding is internal to
            # the plane (axis_index slicing + all_gather), so the varying-
            # manual-axes check is off — the parity tests are the
            # replication proof.
            sharded = jax.shard_map(
                run,
                mesh=self.mesh,
                in_specs=(P(),),
                out_specs=(P(), P()),
                check_vma=False,
            )
            self._compiled[key] = jax.jit(sharded)
        return self._compiled[key]

    def _batch_runner(self, *a, **kw):  # pragma: no cover - guarded path
        raise NotImplementedError(
            "ZoneShardedEngine.run_batch loops one compiled sharded scan"
        )

    def run_batch(
        self,
        seeds: Sequence[int],
        num_ticks: int | None = None,
        scenario: ScenarioConfig | None = None,
    ) -> List[Dict[str, Any]]:
        """Sequential per-seed runs of ONE compiled sharded scan.

        Same batch semantics as ``LaminarEngine.run_batch``: cluster
        geometry and lambda come from ``seeds[0]`` and are shared; per-seed
        variation enters only through the PRNG and schedule keys. (``vmap``
        over ``shard_map`` is out of contract, so the seeds advance
        sequentially rather than in lockstep — the compiled program is
        still built exactly once.)
        """
        from repro.core.engine import summarize
        from repro.workloads import schedule as wl_schedule

        seeds = [int(x) for x in seeds]
        if not seeds:
            raise ValueError("run_batch needs at least one seed")
        base, lam = self.init(seeds[0])
        nt = num_ticks if num_ticks is not None else self.cfg.num_ticks
        runner = self._runner(lam, nt, scenario)
        outs: List[Dict[str, Any]] = []
        for sd in seeds:
            s = base._replace(
                key=jax.random.PRNGKey(sd),
                sched_key=wl_schedule.schedule_key(sd),
            )
            final, ts = runner(s)
            out = summarize(self.cfg, final, np.asarray(ts))
            out["lambda_per_s"] = lam / self.cfg.dt_ms * 1e3
            out["seed"] = sd
            outs.append(out)
        return outs

    def traffic(self, seed: int = 0) -> Dict[str, float]:
        """Traffic model for this engine's actual zone geometry."""
        s, _ = self.init(seed)
        Z, M = s.zmember.shape
        return traffic_model(self.cfg, Z, self.num_devices, max_zone=M)
