"""Readings for the output check's limits: the program's widest gap and
the control's, on many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 5 [--controls fp8,int8]

For each seed it makes one benchmark run (``bench/run.py``'s
``measure``) at the cell's own load, and prints one JSON line with the
program's numbers and verdict and, for each control, the numbers read
from the tokens the lower-precision replay ranks first at the same
positions, held to the same limits, with the control's own verdict
(``correct`` has to come out false). Benchmark runs never compute the
controls.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--controls", default="fp8,int8")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    controls = tuple(c for c in args.controls.split(",") if c)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.measure(cell, seed, args.seconds, False, args.cpu_rehearsal,
                          controls=controls)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"],
                          "controls": out.get("controls", {})}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
