"""Tail arithmetic on host-clock stamps of streamed requests.

Every latency is taken from the request's *due* time (when the load
generator was meant to send it), not from when it was sent, so a late
generator or a stall shows as latency.  A request that never produced
its first token counts as a miss: its time to first token is the time
waited until the run gave up on it, which is at least the grace period
and so the worst of the run.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence


@dataclasses.dataclass
class Record:
    """What the client saw of one request (host-clock seconds)."""
    index: int
    prompt_len: int
    max_new: int
    due: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False                 # streamed all its tokens
    cut: bool = False                  # still streaming when the run ended
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        """Finished, with every token it asked for."""
        return (self.done and self.error is None
                and len(self.tokens) == self.max_new)

    @property
    def failed(self) -> bool:
        """Raised, ended short, or never produced a first token."""
        return (self.error is not None or not self.stamps
                or (self.done and len(self.tokens) != self.max_new))


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it.  None for no values."""
    if not values:
        return None
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def ttft_ms(records: Sequence[Record], t0: float, t1: float,
            gave_up: float) -> List[float]:
    """First streamed token minus due time, for every request due in
    [t0, t1); a request with no token by ``gave_up`` reads
    ``gave_up - due``."""
    out = []
    for r in records:
        if not t0 <= r.due < t1:
            continue
        first = r.stamps[0] if r.stamps else gave_up
        out.append((first - r.due) * 1e3)
    return out


def itl_ms(records: Sequence[Record], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive streamed tokens of one request
    whose later token arrived in [t0, t1)."""
    out = []
    for r in records:
        s = r.stamps
        out.extend((b - a) * 1e3 for a, b in zip(s, s[1:]) if t0 <= b < t1)
    return out


def tokens_in(records: Sequence[Record], t0: float, t1: float) -> int:
    return sum(1 for r in records for s in r.stamps if t0 <= s < t1)


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median (the bound rule's
    spread), with ``statistics.quantiles(values, n=4)``."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
