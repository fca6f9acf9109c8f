"""Metric arithmetic: percentiles and censoring over every request,
tokens per second over the window."""
import statistics

import pytest

from bench import stats
from bench.cells import Metric
from bench.harness import end_to_end
from bench.record import Record, Req


def test_percentile_matches_linear_interpolation():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    assert stats.percentile(xs, 100) == 5.0
    assert stats.percentile([], 90) is None
    # inclusive quartiles, as statistics.quantiles' inclusive method
    q = statistics.quantiles(xs, n=4, method="inclusive")
    assert [stats.percentile(xs, p) for p in (25, 50, 75)] == q


def test_censored_waits_keep_stalled_requests_in_the_tail():
    starts = [0.0, 1.0, 2.0, 3.0]
    ends = [0.5, None, 2.2, 12.0]      # one never served, one after close
    waits = stats.censored_waits(starts, ends, close=10.0)
    assert waits == pytest.approx([0.5, 9.0, 0.2, 7.0])


def test_gaps_and_counts_only_inside_the_window():
    stamps = [0.5, 1.0, 1.5, 2.5, 3.5]
    assert stats.gaps_within(stamps, 1.0, 3.0) == pytest.approx([0.5, 1.0])
    assert stats.count_within(stamps, 1.0, 3.0) == 3


def test_union_length_merges_overlaps():
    assert stats.union_length([(0, 1), (0.5, 2), (3, 4), (3.2, 3.5)]) == 3.0
    assert stats.union_length([]) == 0.0


class _Cell:
    end_to_end = [Metric("itl_p99_ms", "ms", "lower", "host_clock"),
                  Metric("output_tokens_per_s", "tokens/s", "higher",
                         "host_clock"),
                  Metric("setup_s", "s", "lower", "host_clock"),
                  Metric("ttft_p50_s", "s", "lower", "host_clock"),
                  Metric("itl_p95_ms", "ms", "lower", "host_clock")]


def test_end_to_end_over_all_requests_and_the_whole_window():
    reqs = {}
    # ten requests due at 0..9 s; each gets its first token 1 s later
    # and then a token every 0.1 s; the one due at 9 s gets none
    for i in range(10):
        stamps = [] if i == 9 else [i + 1.0 + 0.1 * k for k in range(5)]
        reqs[i] = Req(i, 8, 16, 5, due=float(i), sent=float(i),
                      stamps=stamps, tokens=[1] * len(stamps))
    reqs[99] = Req(99, 8, 16, 5, due=-1.0, stamps=[0.2, 0.3])  # before
    rec = Record(0.0, 10.0, reqs, 4, {}, {})
    out = end_to_end(_Cell(), rec, setup_s=12.5)
    # waits: 1 s for nine, 1 s censored for the last (due 9, close 10)
    waits = stats.censored_waits([r.due for r in rec.attempted()],
                                 [r.first for r in rec.attempted()], 10.0)
    assert len(waits) == 10 and stats.percentile(waits, 90) == 1.0
    # tokens stamped in [0, 10]: 9 full requests minus what falls past
    # 10 s (request 8: 9.0 .. 9.4 is inside) and request 99's two
    inside = sum(1 for r in reqs.values() for t in r.stamps if t <= 10)
    assert out["output_tokens_per_s"]["value"] == pytest.approx(inside / 10)
    assert out["itl_p99_ms"]["value"] == pytest.approx(100.0)
    assert out["setup_s"]["value"] == 12.5
    assert out["ttft_p50_s"]["value"] == pytest.approx(1.0)
    assert out["itl_p95_ms"]["value"] == pytest.approx(100.0)


def test_median_time_to_first_token_counts_the_unserved():
    """Six requests due at 0..5 s; the first two are served 0.5 s after
    they were due, the other four never before the close at 10 s, so
    they enter with waits of 10, 9, 8 and 7 s."""
    reqs = {i: Req(i, 8, 16, 5, due=float(i), sent=float(i),
                   stamps=[i + 0.5] if i < 2 else [])
            for i in range(6)}
    out = end_to_end(_Cell(), Record(0.0, 10.0, reqs, 4, {}, {}), 1.0)
    # waits 0.5, 0.5, 8, 7, 6, 5: the median is 5.5, where the served
    # requests alone would read 0.5
    assert out["ttft_p50_s"]["value"] == pytest.approx(5.5)
