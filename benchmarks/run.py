"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes JSON into
experiments/bench/. ``--quick`` shrinks agent counts for CI-speed runs.

  PYTHONPATH=src python -m benchmarks.run [--only fig11] [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import Reporter
from repro.launch.compile_cache import enable_compile_cache

SUITES = [
    ("fig2_scaling_gap", "benchmarks.scaling_gap"),
    ("fig3_similarity", "benchmarks.similarity"),
    ("fig10_capacity", "benchmarks.capacity"),
    ("fig11_collective_speedup", "benchmarks.collective_speedup"),
    ("fig12_compression", "benchmarks.compression"),
    ("fig13_restore", "benchmarks.restore"),
    ("fig14_accuracy", "benchmarks.accuracy"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter, e.g. fig11")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for name, modpath in SUITES:
        if args.only and args.only not in name:
            continue
        rep = Reporter()
        t0 = time.time()
        try:
            mod = __import__(modpath, fromlist=["run"])
            mod.run(rep, quick=args.quick)
            rep.save(name)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:
            failures.append((name, e))
            import traceback
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {[n for n, _ in failures]}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
