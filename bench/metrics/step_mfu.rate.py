"""Model flops of the traced prefill and decode executions over their
device time times the bf16 peak."""

from bench import readers


def _step_mfu(run):
    flops, device = readers.flops_of(run, ("prefill", "decode"))
    if device <= 0:
        return None
    return 100.0 * flops / (device * run.peaks["bf16_flops_per_s"])


def read(run):
    return _step_mfu(run)
