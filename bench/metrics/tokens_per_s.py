"""Generated tokens streamed to clients in the window, over the window's
seconds (host clock, client side)."""

from bench import readers


def read(run):
    return readers.tokens_per_s(run)
