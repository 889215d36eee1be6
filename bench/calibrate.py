#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

One process builds the cell once, then for each seed makes that seed's
weights, serves the cell's traffic for ``--seconds`` (the longest
requests finish in the grace period after it), and scores a sample of
what was served, as a benchmark run does:

* ``program``: the widest float32 logit gap of a served token
  (``correct.served_gaps``) -- the lower reading;
* ``control`` (seeds in ``--control-seeds``): the same positions scored
  with the fp8 reference put in the program's place
  (``correct.control_gaps``) -- the upper reading.

    python3 bench/calibrate.py --workload minicpm-2b.chat \
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 10

Prints one JSON line per seed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec
    harness.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    setup = harness.prepare(cell, seeds[0], trace=False, t_start=T_START)
    for seed in seeds:
        if setup.params is None:        # scoring freed the last seed's
            setup.params = harness.program_weights(cell, setup.abstract,
                                                   seed)
            setup.engine.params = setup.params
        out = harness.serve(setup, seed, args.seconds,
                            t_start=time.perf_counter())
        modes = ("f32", "fp8") if seed in controls else ("f32",)
        t = time.perf_counter()
        picked, gaps = harness.score(setup, out["records"], seed, modes)
        line = {"seed": seed, "requests": len(out["records"]),
                "failed": sum(1 for r in out["records"] if r.failed),
                "sampled": len(picked),
                "tokens": int(sum(len(g) for g in gaps["f32"])),
                "program_gap": max(float(g.max()) for g in gaps["f32"]),
                "memory_peak_bytes": out["memory_peak"],
                "score_s": time.perf_counter() - t}
        if "fp8" in gaps:
            line["control_gap"] = max(float(g.max()) for g in gaps["fp8"])
            line["control_positions_over_program"] = int(sum(
                (g > line["program_gap"]).sum() for g in gaps["fp8"]))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
