"""Host milliseconds of prefill (with its insert into the arena) per
thousand prompt tokens in the window, in the open-loop cell: each
prefill stalls every active row's next token, so a slower prefill
lengthens the token gaps."""

from bench import readers


def read(run):
    return readers.prefill_ms_per_ktok(run)
