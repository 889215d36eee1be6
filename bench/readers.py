"""What the metric readers under ``bench/metrics/`` share.

A reader is ``read(run) -> float | None`` over a :class:`harness.Run`;
None means the run held nothing to read, and the metric is left out of
the result line.  Shares of a roofline or of a peak are never made up:
a trace with no priced call of the kind gives None, not 0.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from . import shapes, stats


# -- client side (host clock) ---------------------------------------------
def itl(run) -> List[float]:
    return stats.itl_ms(run.records, run.t0, run.t1)


def tokens_per_s(run) -> float:
    return stats.tokens_in(run.records, run.t0, run.t1) / run.seconds


# -- the program's own spans and counters ---------------------------------
def _series(reg, name):
    m = reg.get("metrics", {}).get(name)
    if not m or not m.get("values"):
        return 0, 0.0
    return (sum(v["count"] for v in m["values"]),
            sum(v["sum"] for v in m["values"]))


def window_hist(run, name) -> Tuple[int, float]:
    """(count, sum) a histogram of the server's registry gained in the
    window."""
    c0, s0 = _series(run.reg0, name)
    c1, s1 = _series(run.reg1, name)
    return c1 - c0, s1 - s0


def window_mean(run, name) -> Optional[float]:
    n, total = window_hist(run, name)
    return total / n if n else None


def window_stat(run, key) -> float:
    return (run.reg1["stats"]["scheduler"][key]
            - run.reg0["stats"]["scheduler"][key])


def prefill_ms_per_ktok(run) -> Optional[float]:
    """Host milliseconds of ``serve.prefill_ms`` per thousand prompt
    tokens prefilled in the window."""
    _, ms = window_hist(run, "serve.prefill_ms")
    tokens = window_stat(run, "prefill_tokens")
    return 1e3 * ms / tokens if tokens else None


def kv_blocks_peak_pct(run) -> Optional[float]:
    pool = run.stats.get("block_pool")
    if not pool:
        return None
    return 100.0 * pool["peak_in_use"] / run.usable_blocks


# -- the device trace ------------------------------------------------------
#: the jitted step program each kind of engine call runs (its module
#: name in the trace); smaller programs under the same annotation (a
#: slice of the returned tokens) are not priced
PROGRAMS = {"prefill": "jit_prefill_step",
            "decode": "jit_paged_decode_step"}


def priced(run, kind: str) -> List[Tuple[float, str]]:
    """(device seconds, annotation) of every traced execution of the
    ``kind`` step program that ran under a ``bench.<kind>`` annotation."""
    if run.trace is None:
        return []
    tag, prog = f"bench.{kind}:", PROGRAMS[kind]
    return [(secs, ann) for name, secs, ann in run.trace.calls
            if name == prog and ann is not None and ann.startswith(tag)]


def cost(run, annotation: str) -> Tuple[float, float]:
    """(flops, bytes) a call needs, from the shapes its annotation
    names."""
    kind, *args = annotation.split(":")
    if kind == "bench.prefill":
        return run.dims.prefill(int(args[0]))
    if kind == "bench.decode":
        ctx, rows = int(args[0]), int(args[1])
        # the sum of live contexts prices attention and K/V exactly; the
        # per-row split does not matter to either count
        flops, nbytes = run.dims.decode([0] * rows)
        return (flops + run.dims.attention_flops(ctx),
                nbytes + ctx * run.dims.kv_bytes_per_token)
    raise ValueError(f"unknown annotation {annotation!r}")


def roofline_pct(run, kind: str) -> Optional[float]:
    """Least time over device time, summed over the traced executions of
    one kind of call."""
    calls = priced(run, kind)
    device = sum(s for s, _ in calls)
    if not calls or device <= 0:
        return None
    least = sum(shapes.least_seconds(*cost(run, a), run.peaks)
                for _, a in calls)
    return 100.0 * least / device


def flops_of(run, kinds) -> Tuple[float, float]:
    """(model flops, device seconds) of the traced executions of the
    given kinds of call."""
    flops = device = 0.0
    for kind in kinds:
        for secs, ann in priced(run, kind):
            flops += cost(run, ann)[0]
            device += secs
    return flops, device

