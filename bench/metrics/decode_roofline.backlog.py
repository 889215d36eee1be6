"""Roofline share of the decode step programs: over the traced decode
executions, the least time each needs (the larger of its bytes over HBM
bandwidth and its flops over bf16 peak, from ``shapes.py``: weights
plus each row's live K/V) over the device time they took."""

from bench import readers


def read(run):
    return readers.roofline_pct(run, "decode")
