"""Host milliseconds of prefill (with its insert into the arena) per
thousand prompt tokens in the window: the scheduler's
``serve.prefill_ms`` span over its ``prefill_tokens`` counter."""

from bench import readers


def read(run):
    return readers.prefill_ms_per_ktok(run)
