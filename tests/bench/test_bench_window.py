"""The window arithmetic of TTFT, inter-token gaps, queue waits and
tokens per second, on hand-made stamps."""
import pytest

from bench import stats
from bench.stats import RequestStamps as R

T0, T1 = 10.0, 20.0


def reqs():
    return [
        R(0, due=9.0, refill=9.1, tokens=[9.5, 10.5, 11.0]),   # before
        R(1, due=10.0, refill=10.2, tokens=[10.4, 10.6, 10.9]),
        R(2, due=12.0, refill=13.0, tokens=[13.5]),
        R(3, due=19.0, refill=19.5, tokens=[20.5]),          # token late
        R(4, due=19.8),                                      # never served
        R(5, due=20.0, refill=20.1, tokens=[20.2]),          # after
    ]


def test_ttft_counts_unserved_requests_at_their_age():
    samples, attempted, failed = stats.ttft_samples(reqs(), T0, T1)
    assert attempted == 4 and failed == 2
    assert samples == pytest.approx([0.4, 1.5, 1.0, 0.2])


def test_queue_wait():
    got = stats.queue_wait_samples(reqs(), T0, T1)
    assert got == pytest.approx([0.2, 1.0, 0.5, 0.2])


def test_itl_takes_gaps_ending_inside_the_window():
    got = stats.itl_samples(reqs(), T0, T1)
    assert sorted(got) == pytest.approx(sorted([1.0, 0.5, 0.2, 0.3]))


def test_tokens_in_window():
    assert stats.tokens_in(reqs(), T0, T1) == 6
    assert stats.tokens_in(reqs(), T0, T1) / (T1 - T0) == 0.6


def test_percentile_matches_numpy_linear():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95.0
    assert stats.percentile([], 95) is None
