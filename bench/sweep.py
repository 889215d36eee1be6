#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate the system
keeps up with.  One process serves the cell's traffic at each rate in
turn (set-up once), and prints one JSON line per rate:

    python3 bench/sweep.py --workload minicpm-2b.chat --seed 7 \
        --seconds 20 --rates 1,1.5,2,2.5,3

A rate is kept up with when times to first token do not grow through
the window (the second half's p95 against the first half's).  Where
answers outlast the window, the tokens served per second fall short of
what the due requests ask for at any rate, so that line is only shown.
The rate a cell runs at is written into its traffic file by hand, once.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec, stats, traffic
    harness.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    setup = harness.prepare(cell, args.seed, trace=False, t_start=T_START)
    base = copy.deepcopy(cell.traffic)
    for rate in [float(r) for r in args.rates.split(",")]:
        setup.cell.traffic = dict(base, rate_rps=rate)
        out = harness.serve(setup, args.seed, args.seconds,
                            t_start=time.perf_counter())
        t0, t1 = out["t0"], out["t1"]
        mid = (t0 + t1) / 2
        recs = out["records"]
        due = [r for r in recs if t0 <= r.due < t1]
        halves = [[r for r in due if r.due < mid],
                  [r for r in due if r.due >= mid]]
        ttft_halves = [stats.percentile(
            stats.ttft_ms(h, t0, t1, out["gave_up"]), 95) for h in halves]
        tt = stats.ttft_ms(recs, t0, t1, out["gave_up"])
        asked = sum(r.max_new for r in due) / args.seconds
        print(json.dumps({
            "rate_rps": rate, "due_in_window": len(due),
            "failed": sum(1 for r in recs if r.failed),
            "tokens_per_s": stats.tokens_in(recs, t0, t1) / args.seconds,
            "tokens_per_s_asked": asked,
            "ttft_p50_ms": stats.percentile(tt, 50),
            "ttft_p95_ms": stats.percentile(tt, 95),
            "itl_p95_ms": stats.percentile(stats.itl_ms(recs, t0, t1), 95),
            "ttft_p95_ms_halves": ttft_halves,
            "mean_request_tokens": traffic.mean_request_tokens(
                setup.cell.traffic)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
