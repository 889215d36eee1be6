"""Mean active decode rows per decode step in the window, from the
scheduler's ``serve.batch_occupancy`` histogram."""

from bench import readers


def read(run):
    return readers.window_mean(run, "serve.batch_occupancy")
