"""The gap tail over all gaps, and tokens/s over the window, on
hand-built timestamps."""

import pytest

from harness.stats import percentile, token_gaps, tokens_in_window


def test_gap_tail_is_over_every_gap_not_per_request_means():
    # one request stalls once for 1 s among 99 gaps of 10 ms; another
    # streams evenly.  A per-request mean hides the stall; the tail of
    # all gaps keeps it.
    a = [0.01 * i for i in range(100)]
    a = a[:50] + [t + 1.0 for t in a[50:]]
    b = [0.02 * i for i in range(10)]
    gaps = token_gaps([a, b], end=10.0)
    assert len(gaps) == 99 + 9
    assert max(gaps) == pytest.approx(1.01)
    assert percentile(gaps, 100) == pytest.approx(1.01)
    mean_a = (a[-1] - a[0]) / 99
    assert mean_a < 0.03          # what tpot would report for request a


def test_gaps_after_the_close_are_left_out():
    gaps = token_gaps([[1.0, 2.0, 3.5, 6.0]], end=3.5)
    assert gaps == [1.0, 1.5]


def test_tokens_delivered_together_give_a_zero_gap():
    assert token_gaps([[1.0, 1.0, 1.2]], end=5) == [0.0, pytest.approx(0.2)]


def test_tokens_per_window():
    stamps = [[0.5, 1.0, 9.9, 10.1], [2.0, 3.0]]
    assert tokens_in_window(stamps, 1.0, 10.0) == 4


def test_percentile_matches_linear_interpolation():
    v = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert percentile(v, 50) == 3.0
    assert percentile(v, 95) == pytest.approx(4.8)
    with pytest.raises(ValueError):
        percentile([], 50)


def test_unanswered_requests_count_in_the_ttft_tail():
    from types import SimpleNamespace as NS

    from harness.view import RunView

    answered = [NS(due=0.0, stamps=[0.1, 0.2]) for _ in range(18)]
    unanswered = [NS(due=1.0, stamps=[]), NS(due=2.0, stamps=[])]
    win = NS(recs=answered + unanswered, drain_end=50.0)
    run = RunView(cell_name="c", seconds=10, shapes=None, slots=4,
                  peaks=None, window=win, setup_s=0.0, engine_metrics=None, spans=[])
    ttfts = run.ttfts_s()
    assert len(ttfts) == 20
    assert sorted(ttfts)[-2:] == [48.0, 49.0]
    assert percentile(ttfts, 95) > 40.0
