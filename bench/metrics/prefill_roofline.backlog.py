"""Roofline share of the prefill programs: over the traced prefill
executions, the least time each needs (from ``shapes.py``: causal
attention, the head at the last position) over the device time they
took."""

from bench import readers


def read(run):
    return readers.roofline_pct(run, "prefill")
