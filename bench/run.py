"""One benchmark run of one cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's cluster at its configuration's geometry seed (so
every seed runs one compiled program), gives the state the run seed's PRNG
keys, compiles the engine's K-tick program and advances W warm-up ticks to a
state S_W. The window then replays the segment [W, W + L) from S_W in
K-tick chunks until ``--seconds`` have passed, so every PR measures the same
simulated regime. ``--trace 1`` replaces the timed window by a profiled one
of a few chunks and reports the per-layer metrics instead.

After the window the plain reference (``plainref``) runs the same seed from
its own cluster, and every chunk end the window produced is compared with
it (``compare.py``). The last stdout line is one JSON object;
the numbers compared, with their limits, close both stdout's line and
stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
CACHE_DIR = REPO / ".bench_cache" / "jax"
TRACE_DIR = REPO / ".bench_cache" / "trace"
sys.path[:0] = [str(HERE), str(REPO / "src")]

import spec as bspec  # noqa: E402


class NoChip(RuntimeError):
    pass


def configure_jax() -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def program_side(spec: dict, kw: dict, seed: int, spans):
    """The program under test: (state, lambda per tick, chunk fn)."""
    import entry
    from repro.core import config as prog_config

    cfg = bspec.build_config(prog_config, kw)
    engine = entry.build(cfg, spec["cell"], spec["chips"])
    with spans.span("bench.init_state"):
        s, lam = engine.init(int(spec["config"]["geometry_seed"]))
    key, sched_key = bspec.seed_keys(seed)
    s = entry.place(engine, s._replace(key=key, sched_key=sched_key))
    return s, lam, entry.chunk(engine, lam, int(spec["traffic"]["chunk_ticks"]))


def control_side(spec: dict, kw: dict, seed: int, spans):
    """The control, the reference in the program's place with its state in
    bfloat16: (state, lambda per tick, chunk fn)."""
    import jax

    import reference

    with spans.span("bench.init_state"):
        cfg, s, lam = reference.initial(kw, int(spec["config"]["geometry_seed"]), seed,
                                        jax.devices()[0])
    s = reference.bf16_state(s)
    return s, lam, reference.chunk(cfg, lam, int(spec["traffic"]["chunk_ticks"]), lowp=True)


def counters(state):
    """The counters of a state, the program's (a NamedTuple) or the reference's."""
    return state["metrics"] if isinstance(state, dict) else state.metrics


def traced(spec: dict, chunks, spans) -> dict:
    """Run ``chunks()`` under the profiler; the reduced trace (``devtrace``)."""
    import jax

    import devtrace

    tdir = TRACE_DIR / spec["name"]
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tdir), profiler_options=opts):
        with spans.span(devtrace.WINDOW_SPAN):
            chunks()
    try:
        return devtrace.reduce(devtrace.load(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def judge(spec: dict, kw: dict, seed: int, seen: list, last_state: dict, device) -> dict:
    """The numbers compared: the window's chunk ends against the reference's."""
    import compare
    import reference

    t = spec["traffic"]
    ends = reference.chunk_ends(kw, int(spec["config"]["geometry_seed"]), seed,
                                int(t["warmup_ticks"]), int(t["segment_ticks"]),
                                int(t["chunk_ticks"]), device=device)
    ends = [compare.host(e) for e in ends]
    last_ref = ends[seen[-1][0]]
    return {
        "counters_off": compare.counters_off([m for _, m in seen],
                                             [ends[p]["metrics"] for p, _ in seen]),
        "decisions_off": compare.decisions_off(last_state, last_ref),
        "float_gap": compare.float_gap(last_state, last_ref),
        "conservation_gap": compare.conservation_gap(last_state),
    }


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             side=program_side, require_tpu: bool = True, wrap=None) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    configure_jax()
    import jax

    import compare
    from clock import CompileClock, Spans

    devs = devices_for(spec["chips"], require_tpu)
    clock, spans = CompileClock(), Spans()
    traffic = spec["traffic"]
    W, L, K = (int(traffic[k]) for k in ("warmup_ticks", "segment_ticks", "chunk_ticks"))
    if W % K or L % K:
        raise ValueError("warm-up and segment must be whole chunks")
    kw = bspec.laminar_kwargs(spec)

    s, lam, run = side(spec, kw, seed, spans)
    bspec.check_cap(kw["max_arrivals_per_tick"], lam)
    if wrap is not None:
        run = wrap(run)
    c0 = clock.seconds
    with spans.span("bench.warmup"):
        for _ in range(W // K):
            s = run(s)
        jax.block_until_ready(s)
    compile_s = clock.seconds - c0
    s_w, seg = s, L // K
    seen = []  # (segment position, metrics) of every chunk the window ran
    state = {"s": s_w, "last": None}

    def chunk() -> None:
        pos = len(seen) % seg
        with spans.span("bench.dispatch"):
            s = run(state["s"])
        with spans.span("bench.readback"):
            jax.block_until_ready(s)
        seen.append((pos, counters(s)))
        state["last"] = s
        state["s"] = s_w if pos == seg - 1 else s

    compiles_before = clock.compiles
    setup_s = time.perf_counter() - T_START
    result: dict = {}
    if not trace:
        t0 = time.perf_counter()
        while not seen or time.perf_counter() - t0 < seconds:
            chunk()
        window_s = time.perf_counter() - t0
        metrics = {"sim_ticks_per_s": len(seen) * K / window_s, "setup_s": setup_s}
    else:
        n = int(spec["cell"]["trace_chunks"])
        red = traced(spec, lambda: [chunk() for _ in range(n)], spans)
        ctx = {"init_state_s": spans.seconds["bench.init_state"],
               "compile_s": compile_s, "trace": red, "ticks": n * K}
        metrics = {m["name"]: bspec.reader(m["name"])(ctx) for m in spec["per_layer"]}
        metrics = {k: v for k, v in metrics.items() if v is not None}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    window_compiles = clock.compiles - compiles_before
    if window_compiles:
        raise RuntimeError(f"{window_compiles} programs compiled inside the window")
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak_bytes(devs),
    }
    if trace:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])

    # the window's counters to the host, then the program's state is let go
    at_w = compare.host(counters(s_w))
    seen = [(p, compare.host(m)) for p, m in seen]
    attempted = failed = 0
    prev = at_w
    for pos, m in seen:
        a, f = bspec.window_counts(at_w if pos == 0 else prev, m)
        attempted, failed, prev = attempted + a, failed + f, m
    last_state = compare.host(state["last"])
    del s, s_w, state

    t_ref = time.perf_counter()
    numbers = judge(spec, kw, seed, seen, last_state, devs[0])
    print(f"bench: setup_s={setup_s} compile_s={compile_s} "
          f"programs={clock.compiles} cache_hits={clock.cache_hits} "
          f"chunks={len(seen)} reference_s={time.perf_counter() - t_ref}",
          file=sys.stderr)
    result.update(
        correct=compare.verdict(numbers),
        attempted=attempted,
        failed=failed,
        metrics={k: {"value": v, "unit": units[k]} for k, v in metrics.items()},
        device=device,
    )
    result["checks"] = {k: {"value": v, "limit": compare.LIMITS[k]}
                        for k, v in numbers.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = bspec.cell(args.workload)
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in result["checks"].items():
        print(f"{k}={v['value']} limit={v['limit']}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
