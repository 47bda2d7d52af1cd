"""From a profiler trace to device busy time, collective time and a breakdown.

``load`` keeps from the ``.xplane.pb`` file only what the reduction reads:
the device planes' ``XLA Ops`` lines and the harness's own host spans
(``bench.*``). ``reduce`` works on that plain structure, so a small recorded
trace checks it (``tests/test_trace.py``).

A plane is ``{"name": str, "lines": [{"name": str, "events": [[name,
start_ns, duration_ns], ...]}]}``.
"""

from __future__ import annotations

from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE_MARKS = ("all-gather", "all-reduce", "all-to-all", "collective-permute",
                    "reduce-scatter")


def load(trace_dir: Path) -> list:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for pl in ProfileData.from_file(str(files[-1])).planes:
        lines = []
        for ln in pl.lines:
            device = pl.name.startswith(DEVICE_PREFIX) and ln.name == OPS_LINE
            evs = [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in ln.events
                if device or e.name.startswith(SPAN_PREFIX)
            ]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return planes


def device_ops(planes: list) -> dict:
    """``{device plane name: [(op, start_ns, end_ns), ...]}`` sorted by start."""
    out = {}
    for pl in planes:
        if not pl["name"].startswith(DEVICE_PREFIX):
            continue
        evs = [
            (n, s, s + d)
            for ln in pl["lines"] if ln["name"] == OPS_LINE
            for n, s, d in ln["events"]
        ]
        out[pl["name"]] = sorted(evs, key=lambda e: (e[1], -e[2]))
    return out


def host_spans(planes: list) -> list:
    """``[(name, start_ns, end_ns)]`` of every ``bench.*`` span on a host plane."""
    return [
        (n, s, s + d)
        for pl in planes if not pl["name"].startswith(DEVICE_PREFIX)
        for ln in pl["lines"]
        for n, s, d in ln["events"] if n.startswith(SPAN_PREFIX)
    ]


def clip(evs, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]


def union(intervals) -> list:
    """Merged ``[start, end]`` cover of ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def leaves(evs: list) -> list:
    """Ops that enclose no other op of the line (a loop's ops, not the loop)."""
    out = []
    for i, (n, s, e) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < e and nxt[2] <= e:
            continue
        out.append((n, s, e))
    return out


def short(name: str) -> str:
    """``%fusion.9 = f32[64]{0:T(1024)} fusion(...)`` -> ``fusion.9 f32[64]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    shape = rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{head.lstrip('%')} {shape}"


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def innermost(spans: list, t: float) -> str:
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "no bench span"


def reduce(planes: list, top: int = 10) -> dict:
    """Busy, collective and idle time of the traced window.

    ``busy_s``: union of op intervals inside the window, mean over devices.
    ``collective_s``: summed collective op time on the device with the most.
    ``device_ops``: the ops that took most time, mean over devices.
    ``idle_gaps``: the longest gaps between busy intervals on the busiest
    device, each named by the innermost host span at its middle.
    """
    spans = host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]
    per_dev = {d: clip(evs, lo, hi) for d, evs in device_ops(planes).items()}
    per_dev = {d: evs for d, evs in per_dev.items() if evs}
    if not per_dev:
        return {"busy_s": 0.0, "window_s": (hi - lo) / 1e9, "devices": 0,
                "collective_s": 0.0, "collectives": 0,
                "device_ops": [], "idle_gaps": []}
    busy = {d: union((s, e) for _, s, e in evs) for d, evs in per_dev.items()}
    busy_ns = {d: sum(e - s for s, e in iv) for d, iv in busy.items()}
    coll = {
        d: [(s, e) for n, s, e in leaves(evs) if is_collective(n)]
        for d, evs in per_dev.items()
    }
    coll_ns = {d: sum(e - s for s, e in iv) for d, iv in coll.items()}
    by_op: dict = {}
    for evs in per_dev.values():
        for n, s, e in leaves(evs):
            by_op[short(n)] = by_op.get(short(n), 0.0) + (e - s)
    nd = len(per_dev)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    busiest = max(busy_ns, key=busy_ns.get)
    iv = busy[busiest]
    edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy_ns.values()) / nd / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": nd,
        "collective_s": max(coll_ns.values()) / 1e9,
        "collectives": max(len(v) for v in coll.values()),
        "device_ops": [[n, t / nd / 1e9] for n, t in ops],
        "idle_gaps": [[innermost(spans, (a + b) / 2), (b - a) / 1e9] for a, b in gaps],
    }
