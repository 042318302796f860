"""Window arithmetic: rates over the whole tick-aligned window, tails
over every request of the window, drained ones included."""
import pytest

from bench import window


def test_rate_divides_by_the_whole_window():
    # 3 ticks of 1,000 tokens that ended at 3.2 s: the rate counts the
    # elapsed time of the last tick too
    assert window.rate(3000, 0.0, 3.2) == pytest.approx(937.5)
    with pytest.raises(ValueError):
        window.rate(1, 2.0, 2.0)


def test_nearest_rank():
    xs = list(range(1, 21))
    assert window.nearest_rank(xs, 0.95) == 19
    assert window.nearest_rank(xs, 0.90) == 18
    assert window.nearest_rank(xs, 1.0) == 20
    assert window.nearest_rank([5.0], 0.9) == 5.0
    with pytest.raises(ValueError):
        window.nearest_rank([], 0.5)
    assert window.median([3, 1, 2, 10]) == 2.5


def test_tails_cover_requests_that_drain_after_the_window():
    # window [0, 10): the last request is due at 9.5 and finishes at 14
    reqs = [{"due": 0.0, "tokens": [0.2, 0.3, 0.4]},
            {"due": 5.0, "tokens": [5.5, 5.6]},
            {"due": 9.5, "tokens": [12.0, 13.0, 14.0]}]
    ttfts, gaps = window.ttfts_and_gaps(reqs)
    assert ttfts == pytest.approx([0.2, 0.5, 2.5])
    assert sorted(gaps) == pytest.approx([0.1, 0.1, 0.1, 1.0, 1.0])
    assert window.nearest_rank(ttfts, 0.9) == pytest.approx(2.5)
    with pytest.raises(ValueError):
        window.ttfts_and_gaps([{"due": 1.0, "tokens": []}])
