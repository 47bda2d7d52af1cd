"""Readings of the numbers that decide ``correct``, over many seeds in one process.

    python3 bench/readings.py --workload <cell> --side program --seeds 1,2,3 --seconds 5
    python3 bench/readings.py --workload <cell> --side control --seeds 1,2,3 --seconds 5

``program`` gives the lower readings (sound runs of the program), ``control``
the upper ones: the reference put in the program's place with its state held
in bfloat16, a step below the float32 the configuration states. Each seed is a
whole run (set-up, a short window at the cell's load, the comparison); one
JSON line per seed. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--side", choices=("program", "control"), required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    spec = run.bspec.cell(args.workload)
    side = run.program_side if args.side == "program" else run.control_side
    for seed in (int(x) for x in args.seeds.split(",")):
        r = run.run_cell(spec, seed, args.seconds, False, side=side)
        print(json.dumps({"workload": args.workload, "side": args.side, "seed": seed,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "failed": r["failed"], "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
