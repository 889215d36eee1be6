"""Model flops of every prefill and decode execution in the traced window
over the window's length times the bf16 peak."""

from bench import readers


def _window_mfu(run):
    flops, device = readers.flops_of(run, ("prefill", "decode"))
    if device <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s
                            * run.peaks["bf16_flops_per_s"])


def read(run):
    return _window_mfu(run)
