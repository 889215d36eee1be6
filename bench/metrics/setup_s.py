"""Seconds from process start to the first request of the traffic:
imports, device start, weights, server, and the warm-up that compiles
or loads every program the window runs."""


def read(run):
    return run.setup_s
