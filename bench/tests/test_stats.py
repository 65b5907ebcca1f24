"""Percentiles, rates and attainment over a window, unfinished requests
counted as misses."""
import pytest

from benchlib import stats
from benchlib.stats import Timeline

LIMITS = {"ttft_ms": 500.0, "itl_ms": 100.0}


def tl(arrival, tokens, done=True, error=None):
    return Timeline(arrival, 10, len(tokens), list(tokens), done, error)


def test_open_loop_window_and_misses():
    lines = {
        0: tl(-1.0, [-0.5, -0.4]),                 # before the window: out
        1: tl(0.0, [0.2, 0.25, 0.30]),             # meets both limits
        2: tl(1.0, [1.9, 1.95]),                   # TTFT 900 ms: misses
        3: tl(2.0, [2.1, 2.4]),                    # gap 300 ms: misses
        4: tl(3.0, [3.1], done=False),             # unfinished: misses
        5: tl(4.0, [], done=False),                # no token by the end
        6: tl(11.0, [11.1, 11.2]),                 # after the window: out
    }
    out = stats.open_loop(lines, 0.0, 10.0, 20.0, LIMITS)
    assert out["attempted"] == 5 and out["failed"] == 2
    assert out["slo_attainment"] == pytest.approx(20.0)
    ttft = [0.2, 0.9, 0.1, 0.1, 16.0]
    assert out["ttft_p90_ms"] == pytest.approx(
        stats.percentile(ttft, 90) * 1e3)
    itl = [0.05, 0.05, 0.05, 0.3]
    assert out["itl_p95_ms"] == pytest.approx(stats.percentile(itl, 95) * 1e3)


def test_closed_loop_rate_counts_tokens_in_window():
    lines = {0: tl(-5.0, [-1.0, 0.5, 1.0, 1.5]),
             1: tl(0.0, [0.2, 0.4, 10.5])}
    out = stats.closed_loop(lines, 0.0, 10.0)
    assert out["output_tok_per_s"] == pytest.approx(5 / 10.0)
    # gaps whose later token lies in the window: 1.5, .5, .5, .2
    assert out["itl_p95_ms"] == pytest.approx(
        stats.percentile([1.5, 0.5, 0.5, 0.2], 95) * 1e3)
    assert out["attempted"] == 2 and out["failed"] == 0


def test_percentile_is_linear():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([0, 10], 90) == pytest.approx(9.0)
