"""Tail arithmetic: latencies run from the due time, and a request that
never produced a token counts as a miss at the worst time of the run."""
import pytest

from bench import stats
from bench.stats import Record


def rec(i, due, stamps):
    return Record(i, 10, len(stamps), due, stamps=list(stamps),
                  tokens=[1] * len(stamps), done=bool(stamps))


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None


def test_ttft_runs_from_the_due_time_not_the_send():
    # due at 10.0; a starved generator sends it at 10.4, the first token
    # comes 0.1 s after sending: the latency is 0.5 s
    r = rec(0, due=10.0, stamps=[10.5])
    assert stats.ttft_ms([r], 0.0, 20.0, gave_up=30.0) == \
        [pytest.approx(500.0)]


def test_an_unserved_request_is_a_miss():
    served = [rec(i, due=1.0 + i, stamps=[1.1 + i]) for i in range(19)]
    lost = rec(99, due=5.0, stamps=[])
    ttft = stats.ttft_ms(served + [lost], 0.0, 100.0, gave_up=70.0)
    assert max(ttft) == pytest.approx(65_000.0)
    assert stats.percentile(ttft, 95) == pytest.approx(100.0)
    assert stats.percentile(ttft, 100) == pytest.approx(65_000.0)
    assert not lost.ok


def test_only_requests_due_in_the_window_count():
    rs = [rec(0, 0.5, [0.6]), rec(1, 1.5, [1.9]), rec(2, 3.0, [3.1])]
    assert stats.ttft_ms(rs, 1.0, 3.0, gave_up=9.0) == \
        [pytest.approx(400.0)]


def test_itl_counts_gaps_ending_in_the_window():
    r = rec(0, 0.0, [0.5, 1.0, 1.2, 3.5])
    assert stats.itl_ms([r], 1.0, 3.0) == [pytest.approx(500.0),
                                           pytest.approx(200.0)]
    assert stats.tokens_in([r], 1.0, 3.0) == 2


def test_spread_is_the_interquartile_share_of_the_median():
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(
        (6 - 2) / 4)
