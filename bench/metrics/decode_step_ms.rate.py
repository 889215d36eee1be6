"""Mean host time of one batched decode step in the window, from the
scheduler's ``serve.decode_step_ms`` span (it ends at the step's
device sync)."""

from bench import readers


def read(run):
    return readers.window_mean(run, "serve.decode_step_ms")
