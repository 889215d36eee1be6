"""90th percentile of every gap between consecutive streamed tokens of
one request, over the gaps that end in the window (host clock, client
side): the token cadence a chat user sees.  The 90th and not the 95th:
a prefill that lands between two decode steps lengthens one gap of each
active row, about 4-6% of the gaps at this cell's rate, so a 95th
percentile sits on the edge between the two kinds of gap and swings
from run to run."""

from bench import readers
from bench.stats import percentile


def read(run):
    return percentile(readers.itl(run), 90)
