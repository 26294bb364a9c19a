"""Traffic generation and the load loops against a fake system."""

import json
import threading
import time
from pathlib import Path

import pytest

from benchmark import loadgen, stats

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def _traffic(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", sorted(p.stem for p in TRAFFIC.glob("*.json")))
def test_every_seed_offers_the_same_work_in_its_own_order(mix):
    t = _traffic(mix)
    a = loadgen.build_requests(t, 1000, 1, 48.0)
    b = loadgen.build_requests(t, 1000, 2 ** 31 + 77, 48.0)
    again = loadgen.build_requests(t, 1000, 1, 48.0)
    assert a == again                       # the same seed, the same inputs
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]
    prompts = lambda rs: [len(r["tokens"]) for r in rs]
    answers = lambda rs: [r["max_new_tokens"] for r in rs]
    assert len(a) == len(b)
    assert sorted(prompts(a)) == sorted(prompts(b))     # one multiset
    assert sorted(answers(a)) == sorted(answers(b))
    for spec, vals in ((t["prompt_len"], prompts(a)),
                       (t["output_len"], answers(a))):
        assert spec["lo"] <= min(vals) and max(vals) <= spec["hi"]
        mid = (spec["lo"] * spec["hi"]) ** 0.5 if spec["dist"] == "loguniform" \
            else (spec["lo"] + spec["hi"]) / 2.0
        below = sum(1 for v in vals if v < mid)     # half on either side
        # (flooring to whole tokens shifts a few tenths of a percent down)
        assert abs(below - len(vals) / 2.0) <= max(2, 0.01 * len(vals))
    if t["loop"] == "open":
        assert len(a) == round(t["arrivals"]["rate_rps"] * 48.0)
        assert prompts(a) != prompts(b)     # the order is the seed's
        for rs in (a, b):
            due = [r["due"] for r in rs]
            assert due == sorted(due) and 0 <= due[0] and due[-1] < 48.0
        assert [r["due"] for r in a] != [r["due"] for r in b]
    else:
        assert len(a) == t["set_size"]
        assert prompts(a) == prompts(b)     # one fixed set, one order:
        assert answers(a) == answers(b)     # the file's base_seed's, not --seed's
        other = loadgen.build_requests(
            dict(t, base_seed=int(t["base_seed"]) + 1), 1000, 1, 48.0)
        assert prompts(other) != prompts(a)
        assert sorted(prompts(other)) == sorted(prompts(a))


def test_quantile_lengths_are_the_distribution_itself():
    spec = {"dist": "loguniform", "lo": 16, "hi": 256}
    v = loadgen._quantile_lengths(spec, 200)
    assert list(v) == sorted(v) and v[0] == 16 and v[-1] >= 250
    # log-uniform: as many lengths in 16-64 as in 64-256
    assert abs(sum(1 for x in v if x < 64) - 100) <= 2
    u = loadgen._quantile_lengths({"dist": "uniform", "lo": 128, "hi": 256}, 64)
    assert abs(float(u.mean()) - 192.0) < 1.0
    assert set(loadgen._quantile_lengths(
        {"dist": "fixed", "lo": 7, "hi": 7}, 5)) == {7}


class _FakeStream:
    def __init__(self):
        self._cb = None

    def subscribe(self, on_chunk, on_close):
        self._cb = (on_chunk, on_close)

    def emit(self, n, gap_s, err=None):
        for _ in range(n):
            time.sleep(gap_s)
            self._cb[0](0)
        self._cb[1](err)


def _fake_system(delay_s=0.01, gap_s=0.001, block_first_s=0.0, fail_every=0):
    """submit() answers each request from a thread of its own; optionally
    the FIRST submit blocks the caller (a late generator)."""
    state = {"n": 0}

    def submit(payload):
        state["n"] += 1
        if state["n"] == 1 and block_first_s:
            time.sleep(block_first_s)
        s = _FakeStream()
        n = payload["max_new_tokens"]
        err = None
        if fail_every and state["n"] % fail_every == 0:
            n, err = 1, RuntimeError("refused")

        def answer():
            time.sleep(delay_s)
            s.emit(n, gap_s, err)

        threading.Thread(target=answer, daemon=True).start()
        return s, None

    return submit


def _requests(n, spacing):
    return [{"due": (i + 1) * spacing, "tokens": [1, 2, 3],
             "max_new_tokens": 4} for i in range(n)]


def test_open_loop_times_from_the_due_time_and_reports_lateness():
    reqs = _requests(10, 0.02)
    run = loadgen.run_open_loop(_fake_system(block_first_s=0.15), reqs,
                                seconds=0.4, drain_timeout_s=5.0)
    recs = run["records"]
    assert len(recs) == 10 and all(r["ok"] and r["n_out"] == 4 for r in recs)
    late = [r["sent"] - r["due"] for r in recs]
    assert max(late[1:6]) > 0.03          # the blocked send delayed the next
    from_due = [stats.ttft_ms(r, 9.0) for r in recs]
    from_send = [stats.ttft_from_send_ms(r, 9.0) for r in recs]
    assert all(d >= s for d, s in zip(from_due, from_send))
    assert max(d - s for d, s in zip(from_due, from_send)) > 30.0


def test_failed_requests_are_counted_not_dropped():
    run = loadgen.run_open_loop(_fake_system(fail_every=3), _requests(9, 0.01),
                                seconds=0.2, drain_timeout_s=5.0)
    recs = run["records"]
    assert sum(1 for r in recs if not r["ok"]) == 3
    assert all(r["error"] for r in recs if not r["ok"])


def test_closed_loop_keeps_exactly_clients_in_flight():
    run = loadgen.run_closed_loop(
        _fake_system(delay_s=0.02), _requests(5, 0.0), seconds=0.3,
        clients=3, drain_timeout_s=5.0)
    recs = run["records"]
    assert len(recs) > 6 and all(r["ok"] for r in recs)
    for r in recs:      # never more than 3 open at any send
        open_then = sum(1 for o in recs
                        if o["sent"] <= r["sent"] < o["closed"])
        assert open_then <= 3
    # round and round through the fixed set, in order
    assert [r["prompt_len"] for r in recs] == [3] * len(recs)


def test_stall_watchers_record_and_stop():
    beat, gcw = loadgen.Heartbeat(period_s=0.005), loadgen.GcWatch()
    beat.start(); gcw.start()
    import gc
    gc.collect()
    time.sleep(0.05)
    beat.stop(); gcw.stop()
    assert len(beat.overshoots) >= 3 and gcw.pauses
    assert not beat._thread.is_alive()
