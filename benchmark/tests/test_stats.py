"""Percentiles and the due-time arithmetic on a synthetic schedule with a
stall in it."""
import math

import pytest

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


def test_gap_modes_count_the_prefills_in_the_steps_a_gap_spans():
    from benchmark.runners import serve
    # five engine steps ending at 1..5 (x 10 ms); steps 2 and 5 held one
    # prefill, step 4 two.  Stream A got a token in every step, stream B
    # (admitted in step 2) from step 2 on but none in step 4 (preempted);
    # a ramp request's first token (stamped -1) opens no gap.
    ends = [0.01, 0.02, 0.03, 0.04, 0.05]
    served = {"queue": [(t, 0) for t in ends],
              "step_prefills": [0, 1, 0, 2, 1],
              "token_times": [[-1.0] + ends, [0.02, 0.03, 0.05], []]}
    modes = serve.gap_modes(served)
    assert modes[0] == pytest.approx([10.0, 10.0])         # A: 2->3; B: 2->3
    assert modes[1] == pytest.approx([10.0, 10.0])         # A: 1->2, 4->5
    assert modes[2] == pytest.approx([10.0, 20.0])         # A: 3->4; B: 3->5
    assert sum(map(len, modes.values())) == len(
        stats.gaps_ms([[t for t in ts if t >= 0] for ts in
                       served["token_times"]]))
