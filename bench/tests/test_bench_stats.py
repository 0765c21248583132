"""End-to-end metric arithmetic on hand-worked windows."""
import pytest

from bench.serving_stats import (RequestRecord, percentile, summarize,
                                 tpot_s, ttft_s)


def test_percentile_is_nearest_rank():
    v = list(range(1, 21))              # 1..20
    assert percentile(v, 95) == 19      # ceil(0.95 * 20) = 19th
    assert percentile(v, 50) == 10
    assert percentile([7.0], 95) == 7.0


def test_ttft_counts_requests_still_waiting_at_the_end():
    done = RequestRecord(due_s=1.0, max_new=2, token_s=[1.5, 1.6],
                         finished=True)
    waiting = RequestRecord(due_s=2.0, max_new=4)
    assert ttft_s(done, 10.0) == pytest.approx(0.5)
    assert ttft_s(waiting, 10.0) == pytest.approx(8.0)
    s = summarize([done, waiting], 10.0, 1.0, 1.0)
    assert s["ttft_p95_ms"] == pytest.approx(8000.0)
    assert s["requests"] == 2


def test_tpot_and_slo_share_hand_worked():
    recs = [
        # ttft 0.2, tpot (1.2 - 0.2) / 4 = 0.25: meets 0.5 / 0.3
        RequestRecord(0.0, 5, 0.1, [0.2, 0.5, 0.7, 1.0, 1.2], True),
        # ttft 0.7 misses the 0.5 limit; tpot (1.9 - 1.7) / 2 = 0.1
        RequestRecord(1.0, 3, 1.1, [1.7, 1.8, 1.9], True),
        # tpot (3.2 - 2.1) / 1 = 1.1 misses 0.3
        RequestRecord(2.0, 2, 2.0, [2.1, 3.2], True),
        # not finished: misses both, although its first token was quick
        RequestRecord(3.0, 9, 3.0, [3.1, 3.2], False),
    ]
    assert tpot_s(recs[0]) == pytest.approx(0.25)
    assert tpot_s(RequestRecord(0.0, 1, None, [0.3], True)) is None
    s = summarize(recs, 4.0, 0.5, 0.3)
    assert s["slo_share"] == pytest.approx(25.0)
    # tpots 0.25, 0.1, 1.1, 0.1 -> nearest-rank p95 of four is the largest
    assert s["tpot_p95_ms"] == pytest.approx(1100.0)
    assert s["output_tok_s"] == pytest.approx(12 / 4.0)
    # queue waits 0.1, 0.1, 0.0, 0.0 -> p95 0.1 s
    assert s["queue_wait_p95_ms"] == pytest.approx(100.0)
