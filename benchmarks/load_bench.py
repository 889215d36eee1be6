#!/usr/bin/env python
"""Open-loop Poisson load generator for the async serving front door.

Drives :class:`GraphServer` through :class:`AsyncFrontend` with Poisson
arrivals at fixed offered QPS.  The loop is OPEN: the arrival schedule
is drawn up front (seeded exponential inter-arrivals) and honoured
regardless of completions, so a slow server shows up as queueing delay
in the latency percentiles instead of silently throttling the load —
the methodology the serving literature insists on for tail latency
(closed-loop clients self-pace and hide the queue).

Per offered-QPS point the bench reports:

* **TTFT** — time from ``submit`` to first streamed token (p50/p95/p99),
  which includes flow-limiter queueing and chunked-prefill time;
* **inter-token latency** — gaps between consecutive streamed tokens of
  the same request (p50/p95/p99);
* **goodput** — achieved request rate and generated tok/s over the
  point's wall clock;
* **registry percentiles** — the same TTFT / ITL read back from the
  server's ``serve.ttft_ms`` / ``serve.itl_ms`` metrics histograms
  (scheduler-side stamps, bucket-derived quantiles), cross-checked
  against the client-side measurement (docs/OBSERVABILITY.md).

A ``--cancel-frac`` slice of clients disconnects mid-stream (the async
generator is closed after a few tokens), exercising disconnect →
cancellation under real concurrency; the leak gate below then proves
the cancellations cleaned up after themselves.

Results merge into the ``load`` section of ``BENCH_serve.json``
(``--out``) — the serve_bench sections are preserved — stamped with the
same provenance block (git SHA, seed, argv, versions) so the cross-PR
trajectory is comparable.  ``--smoke`` shrinks everything for CI.

    PYTHONPATH=src python benchmarks/load_bench.py \
        --qps 2,4,8 --requests 16 --max-new-tokens 16

Exits non-zero unless (a) every request reached a terminal state, (b)
every non-cancelled request's tokens are bit-identical to the
sequential ``engine.generate`` reference, (c) the block arena drains to
baseline (zero in use, zero reserved, empty prefix index) after every
point despite the mid-stream disconnects, (d) when
``--gate-p95-ttft-ms`` is given, p95 TTFT at the LOWEST offered QPS is
under the gate (the sanity bound CI enforces on the smoke run), and
(e) the registry's TTFT/ITL percentiles agree with the client-side
measurement within tolerance.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import platform
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, "src")

import repro.calculators  # noqa: F401,E402
from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import (make_serving_mesh,  # noqa: E402
                               simulated_devices_env)
from repro.serving import (AsyncFrontend, GraphServer, LLMEngine,  # noqa: E402
                           Policy)


def percentile(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * q))]


def pctiles_ms(xs):
    if not xs:
        return {"p50": None, "p95": None, "p99": None}
    return {k: round(percentile(xs, q) * 1e3, 2)
            for k, q in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))}


def provenance(args) -> dict:
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    import jax
    return {
        "git_sha": sha,
        "seed": args.seed,
        "backends": ["paged"],
        "argv": sys.argv[1:],
        "jax": jax.__version__,
        "python": platform.python_version(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def sched_of(srv):
    for node in srv.graph.nodes:
        if node.name == "engine":
            return node.calculator.sched
    raise RuntimeError("no engine node in serving graph")


def registry_crosscheck(reg, ttft, gaps):
    """Compare client-side TTFT / inter-token percentiles against the
    scheduler-side ``serve.ttft_ms`` / ``serve.itl_ms`` histograms from
    the server's metrics registry (docs/OBSERVABILITY.md).

    The two views measure different spans of the same events — the
    registry stamps inside the scheduler, the client stamps after the
    dispatcher and event-loop hop — and histogram quantiles are
    bucket-edge-quantized, so agreement means the client percentile
    falls inside a generous envelope around the registry's bucket
    bounds (factor 2 plus 25 ms absolute slack), not equality."""
    out = {}
    ok = True
    for name, key, samples in (("serve.ttft_ms", "ttft_ms", ttft),
                               ("serve.itl_ms", "itl_ms", gaps)):
        hist = reg.get(name)
        rec = {}
        for q in (0.50, 0.95):
            est = hist.quantile(q) if hist is not None else None
            rec[f"p{int(q * 100)}"] = round(est, 2) \
                if est is not None else None
            bounds = hist.quantile_bounds(q) if hist is not None else None
            if bounds is None or not samples:
                continue
            client = percentile(samples, q) * 1e3
            lo = bounds[0] / 2 - 25.0
            # the +Inf bucket's upper edge is the clamped estimate
            hi_edge = bounds[1] if np.isfinite(bounds[1]) else est
            hi = hi_edge * 2 + 25.0
            if not (lo <= client <= hi):
                ok = False
                print(f"registry disagreement: {name} p{int(q * 100)} "
                      f"client={client:.2f}ms outside "
                      f"[{lo:.2f}, {hi:.2f}]ms (registry bucket "
                      f"{bounds[0]:g}..{bounds[1]:g})")
        out[key] = rec
    return ok, out


_ref_cache = {}


def reference(engine, prompt, max_new):
    key = (prompt.tobytes(), max_new)
    if key not in _ref_cache:
        _ref_cache[key] = engine.generate(prompt[None],
                                          max_new_tokens=max_new)[0]
    return _ref_cache[key]


async def drive(front, prompts, arrivals, max_new, cancel_after):
    """Submit every request at its scheduled arrival time and stream it
    to completion (or to its scripted disconnect point).  Returns one
    record per request with monotonic-clock stamps."""
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    recs = [None] * len(prompts)

    async def one(i):
        await asyncio.sleep(max(0.0, t0 + arrivals[i] - loop.time()))
        rec = {"submit": loop.time(), "stamps": [], "tokens": [],
               "cancelled": False}
        agen = front.stream(prompts[i], request_id=f"load-{i}",
                            max_new_tokens=max_new)
        try:
            async for tok in agen:
                rec["stamps"].append(loop.time())
                rec["tokens"].append(tok)
                if cancel_after[i] is not None \
                        and len(rec["tokens"]) >= cancel_after[i]:
                    rec["cancelled"] = True
                    break              # aclose() below fires the cancel
        finally:
            await agen.aclose()
        rec["done"] = loop.time()
        recs[i] = rec

    await asyncio.gather(*(one(i) for i in range(len(prompts))))
    return t0, recs


def run_point(engine, args, qps, rng):
    n = args.requests
    lengths = [int(rng.choice([6, 10, 14])) for _ in range(n)]
    prompts = [rng.randint(0, 512, size=L).astype(np.int32)
               for L in lengths]
    # open-loop Poisson schedule: exponential inter-arrivals at the
    # offered rate, fixed before the run starts
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=n)).tolist()
    cancel_after = [1 + i % 3 if rng.rand() < args.cancel_frac else None
                    for i in range(n)]

    srv = GraphServer(engine, num_slots=args.num_slots,
                      max_new_tokens=args.max_new_tokens,
                      paged=True, block_size=args.block_size,
                      speculate_k=args.speculate_k)
    front = AsyncFrontend(srv, policy=Policy(timeout_ms=args.timeout_ms))
    t0, recs = asyncio.run(
        drive(front, prompts, arrivals, args.max_new_tokens,
              cancel_after))
    srv.close()                        # drains in-flight cancellations
    reg = srv.metrics_registry()
    sched = sched_of(srv)
    pool = sched.pool
    pool.check_invariants()
    leak_free = (pool.blocks_in_use == 0 and pool.reserved_blocks == 0
                 and len(sched.prefix) == 0
                 and sorted(sched.free) == list(range(sched.num_slots)))

    ttft = [r["stamps"][0] - r["submit"] for r in recs if r["stamps"]]
    gaps = [b - a for r in recs
            for a, b in zip(r["stamps"], r["stamps"][1:])]
    survivors = [(i, r) for i, r in enumerate(recs) if not r["cancelled"]]
    exact = all(
        np.array_equal(np.asarray(r["tokens"], np.int32),
                       reference(engine, prompts[i],
                                 args.max_new_tokens))
        for i, r in survivors)
    wall = max(r["done"] for r in recs) - t0
    toks = sum(len(r["tokens"]) for r in recs)
    reg_ok, reg_pct = registry_crosscheck(reg, ttft, gaps)
    point = {
        "offered_qps": qps,
        "achieved_qps": round(n / wall, 2),
        "requests": n,
        "cancelled": sum(r["cancelled"] for r in recs),
        "ttft_ms": pctiles_ms(ttft),
        "intertoken_ms": pctiles_ms(gaps),
        "tok_per_s": round(toks / wall, 1),
        "wall_s": round(wall, 2),
        "outputs_identical": exact,
        "leak_free": leak_free,
        "registry": {**reg_pct, "agrees_with_client": reg_ok},
    }
    print(f"qps={qps:>5.1f}  achieved={point['achieved_qps']:>5.1f}  "
          f"ttft p50={point['ttft_ms']['p50']}ms "
          f"p95={point['ttft_ms']['p95']}ms "
          f"p99={point['ttft_ms']['p99']}ms  "
          f"itl p50={point['intertoken_ms']['p50']}ms "
          f"p95={point['intertoken_ms']['p95']}ms  "
          f"cancelled={point['cancelled']}/{n}  "
          f"exact={exact}  leak_free={leak_free}")
    print(f"        registry: ttft p50={reg_pct['ttft_ms']['p50']}ms "
          f"p95={reg_pct['ttft_ms']['p95']}ms  "
          f"itl p50={reg_pct['itl_ms']['p50']}ms "
          f"p95={reg_pct['itl_ms']['p95']}ms  "
          f"agrees={reg_ok}")
    return point


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm_2b")
    ap.add_argument("--qps", default="2,4,8",
                    help="comma-separated offered QPS points (open loop)")
    ap.add_argument("--requests", type=int, default=16,
                    help="requests per QPS point")
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--speculate-k", type=int, default=0)
    ap.add_argument("--cancel-frac", type=float, default=0.25,
                    help="fraction of clients that disconnect mid-stream")
    ap.add_argument("--timeout-ms", type=float, default=300_000.0,
                    help="frontend policy timeout per request")
    ap.add_argument("--gate-p95-ttft-ms", type=float, default=None,
                    help="fail unless p95 TTFT at the lowest offered "
                         "QPS is under this bound")
    ap.add_argument("--mesh", type=int, default=0,
                    help="serve over an N-way tensor-parallel mesh "
                         "(docs/SHARDING.md); a CPU run with fewer than "
                         "N devices re-execs with forced host devices, "
                         "an accelerator run with fewer fails")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for the CI smoke job")
    args = ap.parse_args(argv)

    import jax
    enable_compile_cache()
    if args.mesh > 1 and jax.device_count() < args.mesh:
        # a CPU run re-execs with N simulated host devices (the flag
        # must precede backend start-up); an accelerator run fails here
        env = simulated_devices_env(args.mesh)
        cmd = [sys.executable, os.path.abspath(__file__)] + \
            list(sys.argv[1:] if argv is None else argv)
        return subprocess.run(cmd, env=env).returncode
    if args.smoke:
        args.requests = min(args.requests, 6)
        args.max_new_tokens = min(args.max_new_tokens, 8)
        args.num_layers = 1
        args.d_model = 64
        if args.qps == "2,4,8":
            args.qps = "3,9"
    qps_points = [float(q) for q in args.qps.split(",") if q]
    if not qps_points:
        ap.error("--qps must name at least one rate")

    cfg = get_config(args.arch).reduced()
    cfg = dataclasses.replace(cfg, num_layers=args.num_layers,
                              d_model=args.d_model, vocab_size=512)
    max_len = -(-(args.max_new_tokens + 16) // args.block_size) \
        * args.block_size
    mesh = make_serving_mesh(args.mesh,
                             devices=jax.devices()[:args.mesh]) \
        if args.mesh >= 1 else None
    engine = LLMEngine(cfg, max_len=max_len, seed=args.seed, mesh=mesh)

    # warm-up: run the whole workload once untimed so every prefill /
    # decode shape either mode can hit is compiled before measurement
    warm_rng = np.random.RandomState(args.seed)
    run_point(engine, args, max(qps_points) * 4, warm_rng)
    print("-- warm-up above; measured points below --")

    rng = np.random.RandomState(args.seed)
    points = [run_point(engine, args, q, rng)
              for q in sorted(qps_points)]

    data = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            data = json.load(f)
    data["load"] = {
        "provenance": provenance(args),
        "config": {
            "arch": cfg.name, "requests_per_point": args.requests,
            "num_slots": args.num_slots,
            "max_new_tokens": args.max_new_tokens,
            "max_len": max_len, "block_size": args.block_size,
            "speculate_k": args.speculate_k,
            "cancel_frac": args.cancel_frac, "smoke": args.smoke,
            "mesh": engine.mesh_desc,
        },
        "points": points,
    }
    with open(args.out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"load_bench -> {args.out} ({len(points)} points)")

    ok = True
    if not all(p["outputs_identical"] for p in points):
        print("FAIL: a completed request diverged from the sequential "
              "reference under load")
        ok = False
    if not all(p["leak_free"] for p in points):
        print("FAIL: arena not at baseline after drain (cancellation "
              "leaked blocks / refs / slots)")
        ok = False
    if not all(p["registry"]["agrees_with_client"] for p in points):
        print("FAIL: registry TTFT/ITL percentiles disagree with the "
              "client-side measurement beyond tolerance")
        ok = False
    if args.gate_p95_ttft_ms is not None:
        p95 = points[0]["ttft_ms"]["p95"]
        if p95 is None or p95 > args.gate_p95_ttft_ms:
            print(f"FAIL: p95 TTFT {p95}ms at {points[0]['offered_qps']} "
                  f"QPS exceeds the {args.gate_p95_ttft_ms:g}ms gate")
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
