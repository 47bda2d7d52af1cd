"""Device time of each tick stage, split by the program's ``laminar.<stage>`` scopes.

    python3 bench/stages.py --workload <cell> --seed <n>

A v5e trace's ``XLA Ops`` events carry the HLO instruction but no
``op_name``, so the scope of each op comes from the compiled chunk program's
optimized HLO text (``op_scopes``), keyed by instruction name. ``stages``
gives each stage the union of its leaf ops' intervals inside
``bench.window``, mean over devices, as ``devtrace.reduce`` computes
``busy_s``; leaf ops under no ``laminar.*`` scope go to ``unscoped``.

The command sets a cell up as ``run.py`` does, profiles a window of the
cell's ``trace_chunks`` chunks from the warm-up state, and prints one JSON
line: each stage's device milliseconds per tick, ``device_ms_per_tick`` and
the ticks per second of the same window. It is a tool beside the benchmark;
``run.py``'s result line does not carry these numbers.
"""

from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent)]

import devtrace  # noqa: E402

# the program's ``engine.SCOPE_PREFIX``, not imported: the tool also reads a
# program built before the scopes
SCOPE_PREFIX = "laminar."
UNSCOPED = "unscoped"
HLO_OP_NAME = re.compile(r'^\s*(?:ROOT )?%?([^\s=]+) = .*?op_name="([^"]*)"', re.M)


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of an optimized HLO module's text."""
    return dict(HLO_OP_NAME.findall(hlo_text))


def stage_of(op_name: str | None) -> str:
    """The outermost ``laminar.<stage>`` component of an op_name, else unscoped."""
    for part in (op_name or "").split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part[len(SCOPE_PREFIX):]
    return UNSCOPED


def stages(planes: list, scopes: dict) -> dict | None:
    """Device seconds of each tick stage in the traced window.

    For each stage, the union of its leaf ops' intervals inside the window,
    mean over devices; leaf ops whose instruction carries no ``laminar.*``
    scope go to ``unscoped``. None where no op carries a stage, as in a
    program built without the scopes.
    """
    windows = [(s, e) for n, s, e in devtrace.host_spans(planes)
               if n == devtrace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {devtrace.WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]
    per_dev = [devtrace.clip(evs, lo, hi) for evs in devtrace.device_ops(planes).values()]
    per_dev = [evs for evs in per_dev if evs]
    total: dict = {}
    for evs in per_dev:
        by_stage: dict = {}
        for n, s, e in devtrace.leaves(evs):
            name = n.partition(" = ")[0].lstrip("%")
            by_stage.setdefault(stage_of(scopes.get(name)), []).append((s, e))
        for st, iv in by_stage.items():
            total[st] = total.get(st, 0.0) + sum(e - s for s, e in devtrace.union(iv))
    if set(total) <= {UNSCOPED}:
        return None
    return {st: t / len(per_dev) / 1e9 for st, t in total.items()}


def ms_per_tick(seconds: dict, ticks: int) -> dict:
    """Each stage's device milliseconds per tick; stages with no ops left out."""
    return {st: t * 1e3 / ticks for st, t in seconds.items() if t > 0}


def measure(spec: dict, seed: int, require_tpu: bool = True) -> dict:
    """Set up ``spec``'s cell, profile ``trace_chunks`` chunks from the
    warm-up state, and split the window's device time by stage."""
    import run

    run.configure_jax()
    import jax

    import entry
    import spec as bspec
    from clock import Spans
    from repro.core import config as prog_config

    run.devices_for(spec["chips"], require_tpu)
    traffic = spec["traffic"]
    W, K = int(traffic["warmup_ticks"]), int(traffic["chunk_ticks"])
    n = int(spec["cell"]["trace_chunks"])
    kw = bspec.laminar_kwargs(spec)
    engine = entry.build(bspec.build_config(prog_config, kw), spec["cell"], spec["chips"])
    s, lam = engine.init(int(spec["config"]["geometry_seed"]))
    key, sched_key = bspec.seed_keys(seed)
    s = entry.place(engine, s._replace(key=key, sched_key=sched_key))
    step = entry.chunk(engine, lam, K)
    for _ in range(W // K):
        s = step(s)
    s_w = jax.block_until_ready(s)

    spans = Spans()
    tdir = run.TRACE_DIR / f"{spec['name']}.stages"
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tdir), profiler_options=opts):
        with spans.span(devtrace.WINDOW_SPAN):
            for _ in range(n):
                s = jax.block_until_ready(step(s))
    try:
        planes = devtrace.load(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    # the same compiled program the window ran, its instruction names included
    text = engine._runner(lam, K).lower(s_w).compile().as_text()
    red = devtrace.reduce(planes)
    split = stages(planes, op_scopes(text))
    ticks = n * K
    return {
        "workload": spec["name"],
        "seed": seed,
        "ticks": ticks,
        "window_ticks_per_s": ticks / red["window_s"],
        "device_ms_per_tick": red["busy_s"] * 1e3 / ticks,
        "stage_ms_per_tick": None if split is None else ms_per_tick(split, ticks),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    import spec as bspec

    t0 = time.perf_counter()
    out = measure(bspec.cell(args.workload), args.seed)
    out["seconds"] = time.perf_counter() - t0
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
