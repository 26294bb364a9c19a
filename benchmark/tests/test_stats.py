"""The metric arithmetic: counts and arithmetic only, never a time."""

import random

import pytest

from benchmark import stats


def _window(n=336, window_s=48.0, base_ttft=0.120, seed=0):
    """A synthetic steady window: n requests due evenly, each answered
    ``base_ttft`` (+ jitter) after its due time, 64 tokens at 30 ms."""
    rng = random.Random(seed)
    recs = []
    for i in range(n):
        due = (i + 0.5) * window_s / n
        first = due + base_ttft + rng.uniform(0, 0.040)
        recs.append({"due": due, "sent": due, "first": first,
                     "last": first + 63 * 0.030, "n_out": 64, "want_out": 64,
                     "ok": True})
    return recs


def _stall(recs, at, length):
    """No first token leaves between ``at`` and ``at + length``: requests
    due inside wait for the stall's end, then the queue drains at 4x."""
    out, backlog_until = [], at + length
    for r in recs:
        r = dict(r)
        if at <= r["due"] < at + length * 1.33:
            first = max(r["first"], backlog_until)
            backlog_until = first + 0.035
            shift = first - r["first"]
            r["first"] += shift
            r["last"] += shift
        out.append(r)
    return out


LIMITS = {"ttft_ms": 500.0, "tpot_ms": 60.0}


def test_one_stall_shows_in_the_end_to_end_tail_and_not_in_the_parts_median():
    # PR 22's situation: ~7 requests/s for 30 s, one stall of a few seconds
    # touches just over a tenth of the requests.
    calm = _window(n=213, window_s=30.0)
    stalled = _stall(calm, at=12.0, length=3.0)
    # the end-to-end tail is over all requests of the window: it sees it
    whole_a = stats.whole_window_percentile(calm, "ttft_ms", 90, 50.0)
    whole_b = stats.whole_window_percentile(stalled, "ttft_ms", 90, 50.0)
    assert whole_a["count"] == whole_b["count"] == 213
    assert whole_a["beyond"] == 21
    assert whole_b["value"] > 1.5 * whole_a["value"]
    # and so does the share that met the limits
    assert stats.slo_met_pct(calm, LIMITS, 50.0) == 100.0
    assert stats.slo_met_pct(stalled, LIMITS, 50.0) < 97.0
    # the per-layer diagnostic (median over parts) names the part and stays
    a = stats.parts_percentile(calm, "ttft_ms", 90, 30.0, 3, 50.0)
    b = stats.parts_percentile(stalled, "ttft_ms", 90, 30.0, 3, 50.0)
    assert a["counts"] == b["counts"] == [71, 71, 71]
    assert b["parts"][1] > 3 * a["parts"][1]
    assert b["parts"][0] == a["parts"][0]
    assert b["parts"][2] == a["parts"][2]
    assert b["value"] == pytest.approx(a["value"], rel=0.02)


def test_due_time_latency_counts_a_late_generator():
    recs = _window(n=30, window_s=3.0)
    for r in recs[10:20]:       # the generator ran 300 ms late on these
        r["sent"] = r["due"] + 0.300
        r["first"] += 0.300
        r["last"] += 0.300
    from_due = [v for _, v in stats.field_values(recs, "ttft_ms", 4.0)]
    from_send = [v for _, v in stats.field_values(
        recs, "ttft_from_send_ms", 4.0)]
    assert max(from_send) < 170.0            # the send clock hides it
    assert sum(1 for v in from_due if v > 400.0) == 10  # the due clock not


def test_failed_and_unfinished_requests_miss_and_stay_in_the_tail():
    recs = _window(n=300)
    recs[5].update(ok=False, first=None, last=None, n_out=0)      # refused
    recs[6].update(ok=False, n_out=10)                            # cut short
    assert stats.slo_met_pct(recs, LIMITS, 60.0) == pytest.approx(
        100.0 * 298 / 300)
    vals = dict(stats.field_values(recs, "ttft_ms", 60.0))
    assert vals[recs[5]["due"]] == pytest.approx(
        (60.0 - recs[5]["due"]) * 1000.0)


def test_parts_must_be_odd_at_least_three_and_non_empty():
    recs = _window(n=30)
    for bad in (1, 2, 4):
        with pytest.raises(ValueError):
            stats.parts_percentile(recs, "ttft_ms", 90, 48.0, bad, 50.0)
    with pytest.raises(ValueError):
        stats.parts_percentile(recs[:5], "ttft_ms", 90, 48.0, 3, 50.0)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (90, 4.6), (100, 5.0)])
def test_percentile_interpolates_like_numpy(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_tpot_is_the_mean_gap_after_the_first_token():
    r = {"due": 0.0, "sent": 0.0, "first": 1.0, "last": 1.9, "n_out": 10,
         "want_out": 10, "ok": True}
    assert stats.tpot_ms(r, 5.0) == pytest.approx(100.0)
    assert stats.tpot_ms(dict(r, want_out=1, n_out=1), 5.0) is None


def test_window_rate_counts_only_stamps_inside_the_window():
    assert stats.window_rate([-0.1, 0.0, 1.0, 9.99, 10.0, 11.0], 10.0) == 0.3


def test_quartile_spread_is_the_contracts():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    from statistics import median, quantiles
    q = quantiles(vals, n=4)
    assert stats.quartile_spread(vals) == (q[2] - q[0]) / median(vals)
