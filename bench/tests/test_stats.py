"""Percentile and rate arithmetic: over all requests, failures counted."""
import pytest

from bench.harness import stats
from bench.harness.client import Served
from bench.harness.traffic import Turn


def served(due, times, n_answer=None, error=None):
    t = Turn(0.0, 0, 1, n_answer if n_answer is not None else len(times),
             0, 1)
    s = Served(t, due=due, times=list(times), tokens=[1] * len(times),
               status=200, error=error)
    return s


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 99) == 99
    assert stats.percentile([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_failures_count_at_the_cap():
    ok = [served(0.0, [0.1 * (i + 1), 1.0]) for i in range(9)]
    failed = served(0.0, [], n_answer=4, error="refused")
    short = served(0.0, [0.05], n_answer=3)      # answer cut short
    v = stats.ttft_values(ok + [failed, short], cap_s=30.0)
    assert len(v) == 11
    assert v.count(30.0) == 2
    assert stats.percentile(v, 90) == 30.0


def test_ttft_from_due_time():
    s = served(10.0, [12.5, 12.7])
    assert stats.ttft_values([s], 30.0) == [2.5]


def test_gaps_use_every_request():
    a = served(0.0, [1.0, 1.5, 2.5])
    b = served(0.0, [3.0, 3.25])
    assert sorted(stats.gaps([a, b])) == [0.25, 0.5, 1.0]


def test_tokens_in_window():
    a = served(0.0, [0.5, 1.5, 2.5])
    b = served(0.0, [1.9, 2.1])
    assert stats.tokens_in([a, b], 1.0, 2.0) == 2
