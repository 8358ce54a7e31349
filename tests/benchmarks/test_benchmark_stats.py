"""Percentile and window arithmetic on hand-made request stats."""

import pytest

from benchmarks.lib import stats


@pytest.mark.parametrize("values,q,want", [([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5), ([10], 95, 10.0),
                                           (list(range(101)), 95, 95.0), ([0, 10], 95, 9.5)])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None


def _req(due, admitted, first, done, n_new, want=None):
    return {"due": due, "admitted": admitted, "first_token": first, "done": done, "n_new": n_new,
            "want": n_new if want is None else want}


def test_ttft_runs_from_the_due_time_not_from_admission():
    s = stats.serve_summary([_req(1.0, 1.4, 1.5, 2.5, 11)], [(2.5, 11)], seconds=10, drain_s=5)
    assert s["ttft_p95_ms"] == pytest.approx(500.0)       # 1.5 - 1.0, though admitted at 1.4
    assert s["gen_late_p95_ms"] == pytest.approx(400.0)   # the generator's own lateness, apart
    assert s["tpot_p95_ms"] == pytest.approx(100.0)       # (2.5 - 1.5) / (11 - 1)
    assert (s["attempted"], s["failed"]) == (1, 0)


def test_an_unfinished_request_is_failed_and_sits_in_the_tail():
    reqs = [_req(0.0, 0.0, 0.1, 1.0, 10) for _ in range(9)] + [_req(9.0, 9.0, None, None, 0, want=10)]
    s = stats.serve_summary(reqs, [(1.0, 90)], seconds=10, drain_s=5)
    assert s["failed"] == 1 and s["attempted"] == 10
    assert s["ttft_p95_ms"] > 100.0 and stats.percentile([6000.0] + [100.0] * 9, 95) == pytest.approx(s["ttft_p95_ms"])


@pytest.mark.parametrize("done,n_new,failed", [(14.9, 10, 0), (15.1, 10, 1), (12.0, 9, 1)])
def test_the_drain_limit_and_the_token_count_decide_failure(done, n_new, failed):
    s = stats.serve_summary([_req(9.0, 9.0, 9.5, done, n_new, want=10)], [], seconds=10, drain_s=5)
    assert s["failed"] == failed


def test_tokens_count_only_inside_the_window_and_over_the_whole_window():
    s = stats.serve_summary([_req(0, 0, 1, 12, 30)], [(5.0, 10), (10.0, 10), (12.0, 10)], seconds=10, drain_s=5)
    assert s["tokens_in_window"] == 20 and s["tokens_total"] == 30
    assert s["serve_tokens_per_s"] == pytest.approx(2.0)


def test_completion_rate_reads_the_middle_stretch():
    done = [i / 4 for i in range(1, 401)]  # 4 a second for 100 s
    assert stats.completion_rate(done) == pytest.approx(4.0, rel=0.02)
    assert stats.completion_rate([1.0, 2.0]) is None
