"""Percentiles and the due-time arithmetic on a synthetic schedule with a
stall in it."""
import math

from benchmark import stats


def test_percentile_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 50) == 50
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4


def test_missing_requests_push_the_tail_out():
    ttft = stats.ttfts_ms([0.0] * 20, [0.1] * 18 + [None, None])
    assert stats.percentile(ttft, 95) == math.inf
    assert stats.percentile(ttft, 90) == 100.0


def test_due_time_counts_the_wait_a_stall_imposes():
    # ten requests due every 100 ms; the server stalls from 0.25 s to
    # 0.75 s, answers each request 10 ms after it can take it
    due = [0.1 * i for i in range(10)]
    first = []
    for d in due:
        start = d if not 0.25 <= d < 0.75 else 0.75
        first.append(start + 0.010)
    ttft = stats.ttfts_ms(due, first)
    # requests due at 0.3..0.7 wait for the stall's end
    want = [10.0, 10.0, 10.0, 460.0, 360.0, 260.0, 160.0, 60.0, 10.0, 10.0]
    assert [round(t, 6) for t in ttft] == want
    assert round(stats.percentile(ttft, 95), 6) == 460.0
    assert round(stats.median(ttft), 6) == 35.0


def test_gaps_are_pooled_over_requests():
    gaps = stats.gaps_ms([[0.0, 0.01, 0.03], [1.0], [2.0, 2.5]])
    assert [round(g, 6) for g in gaps] == [10.0, 20.0, 500.0]
