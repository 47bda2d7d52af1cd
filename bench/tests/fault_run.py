"""Drive one tiny run on the CPU with a fault planted under the timed path.

    python bench/tests/fault_run.py --fault <name> --devices <1|4> [--side control]

Prints the run's result line. Used by ``test_faults.py`` and
``test_control.py``; with ``--devices 4`` it sets up four virtual CPU devices
before JAX starts, for the zone-sharded engine.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--fault", default="none",
                choices=("none", "unchanged", "half_batch", "no_exchange", "altered"))
ap.add_argument("--devices", type=int, default=1)
ap.add_argument("--side", choices=("program", "control"), default="program")
ap.add_argument("--seed", type=int, default=2**31 + 11)
args = ap.parse_args()
os.environ["JAX_PLATFORMS"] = "cpu"
if args.devices > 1:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.devices}"
    )

import tiny  # noqa: E402
import run  # noqa: E402


def plant(fault: str):
    """Break the program for ``fault``; returns a wrapper for the chunk fn."""
    import jax.numpy as jnp

    if fault == "unchanged":
        return lambda chunk: (lambda s: s)
    if fault == "altered":
        def altered(chunk):
            def f(s):
                s = chunk(s)
                return s._replace(metrics=s.metrics._replace(
                    completed=s.metrics.completed + 1))
            return f
        return altered
    if fault == "half_batch":
        from repro.core import workload

        sample = workload.sample_arrivals

        def halved(cfg, key, lam):
            b = sample(cfg, key, lam)
            return b._replace(n=b.n // 2)

        workload.sample_arrivals = halved
    if fault == "no_exchange":
        from repro.parallel import engine_mesh
        from repro.core.state import unpack_zoned

        def local_only(self, x_l, s):
            xb = jnp.concatenate([x_l] * self.D, axis=0)  # own block, never sent
            return unpack_zoned(xb, s.zmember, s.zmask, self.cfg.num_nodes)

        engine_mesh.MeshPlane._to_flat = local_only
    return None


wrap = plant(args.fault)
side = run.program_side if args.side == "program" else run.control_side
r = run.run_cell(tiny.tiny_spec(devices=args.devices), args.seed, 1.0, False,
                 side=side, require_tpu=False, wrap=wrap)
print(json.dumps(r))
