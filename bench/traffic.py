"""One generator for every traffic mix: requests from a mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) gives parameters only: the
loop (``open`` at a fixed rate, or ``closed`` with a fixed number of
clients per slot), the distribution of prompt and output lengths, the
context the mix needs (``max_len``), and the public source each length
distribution is taken from (``source``).

Every seed gets the same work.  Prompt lengths come from a fixed pool
(so each pool length is one compiled prefill, warmed in set-up) in
equal counts; output lengths are fixed quantiles of their
distribution; open-loop gaps are fixed quantiles of the exponential
distribution.  In an open loop the seed shuffles each list; a closed
loop keeps one order (see :func:`closed_loop`).  The seed always draws
the prompt tokens.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

#: requests the closed loop can draw before its sequence repeats
CLOSED_LOOP_REQUESTS = 1 << 16
#: quantiles used to state a mix's mean request size (independent of
#: the run's length)
MEAN_QUANTILES = 4096


@dataclasses.dataclass
class Request:
    index: int
    prompt_len: int
    max_new: int
    due: Optional[float]        # seconds after traffic start (open loop)
    seed: int

    def tokens(self, vocab: int) -> np.ndarray:
        """Uniform token ids, from the run seed and the request index:
        distinct prompts share no prefix block."""
        rng = np.random.default_rng([self.seed, 1, self.index])
        return rng.integers(0, vocab, self.prompt_len, dtype=np.int32)


def _clip(x: float, lo: int, hi: int) -> int:
    return int(min(hi, max(lo, round(x))))


def _lognormal(median: float, sigma: float, q: float) -> float:
    return median * math.exp(sigma * NormalDist().inv_cdf(q))


def prompt_pool(spec: Dict[str, Any]) -> List[int]:
    """The fixed prompt lengths of a mix, the same for every seed."""
    if spec["kind"] == "list":
        return [int(n) for n in spec["lengths"]]
    n = int(spec["pool"])
    if spec["kind"] == "lognormal_quantile_pool":
        return [_clip(_lognormal(spec["median"], spec["sigma"],
                                 (2 * i + 1) / (2 * n)),
                      spec["min"], spec["max"]) for i in range(n)]
    if spec["kind"] == "logspace_pool":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        return [_clip(math.exp(lo + (hi - lo) * i / (n - 1)),
                      spec["min"], spec["max"]) for i in range(n)]
    raise ValueError(f"unknown prompt distribution {spec['kind']!r}")


def output_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` output lengths: the distribution's quantiles at
    (i + 1/2) / n, in ascending order."""
    if spec["kind"] == "lognormal":
        return [_clip(_lognormal(spec["median"], spec["sigma"],
                                 (i + 0.5) / n), spec["min"], spec["max"])
                for i in range(n)]
    if spec["kind"] == "uniform":
        vals = np.arange(spec["min"], spec["max"] + 1)
        return [int(vals[int(i * len(vals) / n)]) for i in range(n)]
    raise ValueError(f"unknown output distribution {spec['kind']!r}")


def mean_request_tokens(mix: Dict[str, Any]) -> float:
    pool = prompt_pool(mix["prompt"])
    outs = output_lengths(mix["output"], MEAN_QUANTILES)
    return float(np.mean(pool) + np.mean(outs))


def max_request_tokens(mix: Dict[str, Any]) -> int:
    return max(prompt_pool(mix["prompt"])) + int(mix["output"]["max"])


def open_loop(mix: Dict[str, Any], seed: int,
              seconds: float) -> List[Request]:
    """Poisson-like arrivals at ``rate_rps`` over the ramp and the
    window: gaps are exponential quantiles in seeded order."""
    rate = float(mix["rate_rps"])
    n = max(1, math.ceil(rate * (float(mix["ramp_s"]) + seconds)))
    rng = np.random.default_rng([seed, 0])
    gaps = np.array([-math.log(1 - (i + 0.5) / n) / rate
                     for i in range(n)])
    gaps = gaps[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    pool = prompt_pool(mix["prompt"])
    lens = np.array([pool[i % len(pool)] for i in range(n)])
    lens = lens[rng.permutation(n)]
    outs = np.array(output_lengths(mix["output"], n))[rng.permutation(n)]
    return [Request(i, int(lens[i]), int(outs[i]), float(due[i]), seed)
            for i in range(n)]


def closed_loop(mix: Dict[str, Any], seed: int) -> Iterator[Request]:
    """The request sequence the closed loop's clients draw from, in
    order: each run of ``len(pool)`` consecutive requests holds every
    pool length once, each run of the output distribution's values
    every value once.  The order is the same for every seed (the seed
    draws the token ids): a closed loop serves as many requests as the
    window allows, so an order that varied with the seed would change
    which lengths fall in the window, and so the work."""
    rng = np.random.default_rng(0)
    pool = prompt_pool(mix["prompt"])
    outs = output_lengths(mix["output"],
                          int(mix["output"]["max"]) -
                          int(mix["output"]["min"]) + 1)
    for i in range(CLOSED_LOOP_REQUESTS):
        if i % len(pool) == 0:
            lens = [pool[j] for j in rng.permutation(len(pool))]
        if i % len(outs) == 0:
            news = [outs[j] for j in rng.permutation(len(outs))]
        yield Request(i, lens[i % len(pool)], news[i % len(outs)],
                      None, seed)
