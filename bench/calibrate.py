#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's widest
logit gap, and the float8 control's, over many seeds in one process.

    python3 bench/calibrate.py --workload qwen3-1.7b.chat --seconds 51 \
        --seeds 11 12 13 [--out FILE.jsonl]

Each seed is one benchmark run (``harness.run_cell``) with the control
put in the program's place for the check: the window serves the cell's
traffic at its own rate, and the sampled greedy requests are run through
the float32 reference twice, once to score the tokens the program served
and once to score the tokens that the same reference computed with
float8 matmul operands puts first (``bench.check``). The control's gap is
the one judged, so a sound limit reads ``correct`` false here. Benchmark
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seeds, seconds: float, chip_check: bool = True):
    """Yield one dict of readings per seed."""
    from bench import harness
    for seed in seeds:
        t0 = time.perf_counter()
        r = harness.run_cell(cell, seed, seconds, False, t0,
                             chip_check=chip_check, control=True)
        yield {"seed": seed, "correct": r["correct"], **r["readings"],
               "limit": r["checks"]["widest_logit_gap"]["limit"],
               "run_s": round(time.perf_counter() - t0, 2)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from bench import spec
    cell = spec.load_cell(args.workload)
    for r in readings(cell, args.seeds, args.seconds):
        line = json.dumps({"workload": args.workload, **r})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
