#!/usr/bin/env python3
"""Knee sweep: one cell's traffic served at a ladder of fixed rates.

    python3 bench/sweep.py --workload qwen3-1.7b.chat --seconds 60 \
        --rates 0.7 0.8 0.9 1.0 [--seed 5] [--out FILE.jsonl]

Made once, when a cell is defined, to find the highest rate the system
sustains; the cell then fixes its rate from it (a fraction below the
knee for a latency cell, above it for a throughput cell). Each rate is
one window of the benchmark's own path (``harness.serve_window``: set-up,
warm-up, the window, its drain), with no check. For each rate it prints
the tails, the tokens per second emitted in the window, and how far the
queue fell behind: the mean wait for a slot of the last quarter of the
window's requests against the first, and the requests due before the
window closed that still waited for a slot when it closed (a backlog
that grows: past the knee). Past the knee, the requests and tokens
completed per second in the window's second half read the capacity. The window has to outlast the time a slot
holds a request, or the slots never fill.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def row(data, rate: float) -> dict:
    """The sweep's reading of one window."""
    import numpy as np
    from bench.measure import percentile
    waits = data.queue_wait_ms()
    q = max(len(waits) // 4, 1)
    admitted = {}
    for rid, t in data.admissions:
        admitted.setdefault(rid, t)
    backlog = sum(1 for r in data.requests
                  if admitted.get(r.rid, np.inf) >= data.seconds)
    half = data.seconds / 2
    done_late = sum(1 for r in data.requests if r.completed
                    and half <= r.t_done < data.seconds)
    toks_late = sum(1 for ts in data.emissions().values() for t in ts
                    if half <= t < data.seconds)
    return {"rate_per_s": rate, "requests": len(data.requests),
            "ttft_p50_ms": percentile(data.ttft_ms(), 50),
            "ttft_p95_ms": percentile(data.ttft_ms(), 95),
            "itl_p50_ms": percentile(data.itl_ms(), 50),
            "itl_p95_ms": percentile(data.itl_ms(), 95),
            "output_tokens_per_s": data.window_tokens() / data.seconds,
            "queue_wait_first_quarter_ms": float(np.mean(waits[:q])),
            "queue_wait_last_quarter_ms": float(np.mean(waits[-q:])),
            "backlog_at_close": backlog,
            "completed_per_s_second_half": done_late / half,
            "output_tokens_per_s_second_half": toks_late / half,
            "preemptions": data.window_preemptions(),
            "step_ms": data.mean_step_ms(),
            **data.step_summary()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    for rate in args.rates:
        t0 = time.perf_counter()
        w = harness.serve_window(cell, args.seed, args.seconds, False, t0,
                                 rate=rate)
        line = json.dumps({"workload": args.workload, **row(w.data, rate),
                           "setup_s": w.data.setup_s,
                           "wall_s": round(time.perf_counter() - t0, 2)})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
