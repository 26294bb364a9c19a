"""Engine-loop phase spans on the profiler's clock, and the turn ring
(ISSUE 24; tier-1). Counts, order and containment only — never a time.

- ``tracer().phase`` is a ``jax.profiler.TraceAnnotation`` and nothing
  else: no ``Span``, no exporter call, no switch but a profiler session.
- Under ``jax.profiler.start_trace`` (CPU) a tiny paged engine's
  ``rdb.engine.*`` spans arrive in ``ProfileData`` with their attributes,
  children nest inside parents, and the phases tile the engine thread.
- ``DecodeEngine.turns`` holds one ``Turn`` per device dispatch; the
  flight recorder's ``decode.turn`` span and ``snapshot()["turns"]`` are
  computed from it.
"""

import collections
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine, Turn
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.utils.tracing import tracer

TOP = {"rdb.engine.fabric", "rdb.engine.admit", "rdb.engine.prefill",
       "rdb.engine.turn", "rdb.engine.publish", "rdb.engine.idle_wait"}
CHILDREN = {
    "rdb.engine.turn": {"rdb.engine.turn.prepare", "rdb.engine.turn.dispatch",
                        "rdb.engine.turn.fetch", "rdb.engine.turn.harvest"},
    "rdb.engine.prefill": {"rdb.engine.prefill.prepare",
                           "rdb.engine.prefill.dispatch",
                           "rdb.engine.prefill.fetch",
                           "rdb.engine.prefill.finish"},
}
# What ``summarize_turns`` says of the engine thread's time (ISSUE 55).
SHARES = ("thread_blocked_share", "thread_idle_share", "thread_host_share")
TILING_KEYS = ("thread_ms", *SHARES, "fetch_found_ready_share",
               "longest_records")
# The share of the engine thread's time, between its first and its last
# phase, that no top-level phase may leave uncovered: what lies between two
# phases is the loop's own `while`, one `any()` and the heartbeat stamp.
UNCOVERED_SHARE = 0.10


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(lm, **kw):
    model, params = lm
    queue = RequestQueue(model.name, max_len=256)
    opts = dict(num_slots=4, max_len=96, prompt_buckets=[8, 16],
                eos_token_id=None, default_max_new_tokens=8,
                decode_horizon=4, paged=True, page_size=128)
    opts.update(kw)
    return DecodeEngine(model, params, queue, **opts), queue


def _submit(queue, model_name, lens=(5, 12, 40, 9, 30, 14), new=6, seed=1):
    rng = np.random.default_rng(seed)
    reqs = []
    for n in lens:
        r = Request(model=model_name, payload={
            "tokens": rng.integers(1, 500, n).tolist(),
            "max_new_tokens": new}, slo_ms=60_000.0)
        queue.add_request(r)
        reqs.append(r)
    return reqs


# --- phase(): a profiler annotation and nothing else ------------------------
@pytest.mark.parametrize("recorder_on", [False, True])
def test_phase_touches_nothing_of_the_flight_recorder(recorder_on):
    t = tracer()
    t.reset()
    exported = []
    if recorder_on:
        t.set_exporter(exported.append)
    try:
        with t.phase("rdb.test.outer", n=1) as ph:
            with t.phase("rdb.test.inner"):
                pass
            ph.set_metadata(late=2)
        assert t.enabled is recorder_on
        assert t.finished_spans() == [] and exported == []
        assert t.current_span() is None
    finally:
        t.reset()


# --- the spans, as a profiler session records them ----------------------------
@pytest.fixture(scope="module")
def traced(lm, tmp_path_factory):
    """One profiler session (Python tracer off) over a started engine that
    serves a burst and then idles: the spans of its thread, by line."""
    import jax.profiler as jp

    engine, queue = _engine(lm)
    engine.warmup()
    trace_dir = str(tmp_path_factory.mktemp("engine_trace"))
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    engine.start()
    try:
        jp.start_trace(trace_dir, profiler_options=opts)
        try:
            for r in _submit(queue, engine.model.name):
                r.future.result(timeout=120)
            time.sleep(0.05)      # a few idle waits
        finally:
            jp.stop_trace()
    finally:
        engine.stop()
    (path,) = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in jp.ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for e in line.events if e.name.startswith("rdb.engine.")]
            if evs:
                lines.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return engine, lines


def test_every_phase_is_recorded_on_the_engine_thread_with_its_attributes(
        traced):
    engine, lines = traced
    (spans,) = lines          # one engine, one thread, one line
    names = {s[0] for s in spans}
    assert TOP <= names
    assert CHILDREN["rdb.engine.turn"] | CHILDREN["rdb.engine.prefill"] <= names
    # the engine's own tag: its model, numbered among the process's engines
    assert engine._phase_tag.startswith(engine.model.name + ":")
    assert all(s[3].get("replica") == engine._phase_tag for s in spans)
    by = collections.defaultdict(list)
    for s in spans:
        by[s[0]].append(s[3])
    assert all({"admitted", "queue_len"} <= set(a) for a in by["rdb.engine.admit"])
    assert sum(a["admitted"] for a in by["rdb.engine.admit"]) == 6
    assert all({"trains", "tokens"} <= set(a) for a in by["rdb.engine.prefill"])
    assert all(a["tokens"] <= engine.prefill_token_budget and a["trains"] >= 1
               for a in by["rdb.engine.prefill"])
    turns = by["rdb.engine.turn"]
    assert all({"horizon", "active", "spec"} <= set(a) for a in turns)
    assert all(a["spec"] == 0 and 1 <= a["active"] <= engine.num_slots
               and a["horizon"] in (1, engine.ttft_horizon, engine.decode_horizon)
               for a in turns)
    # the ring and the trace count the same scans (the trace may have
    # started inside one); the loop's scan opens the phase twice: once
    # round its preparation and dispatch, once round its fetch and harvest
    scans = sum(1 for t in engine.turns if t.kind == "turn")
    assert 2 * scans - 2 <= len(turns) <= 2 * scans


def test_children_nest_inside_their_parents(traced):
    _engine_, (spans,) = traced
    parents = [s for s in spans if s[0] in CHILDREN]
    for name, start, end, _ in spans:
        if name in TOP:
            continue
        parent = name.rsplit(".", 1)[0]
        assert name in CHILDREN[parent]
        assert any(p[0] == parent and p[1] <= start and end <= p[2]
                   for p in parents), name
    for p in parents:      # and no two children of one parent overlap
        kids = [s for s in spans if s[0] in CHILDREN[p[0]]
                and p[1] <= s[1] and s[2] <= p[2]]
        assert kids
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1]
    halves = [[s[0].rsplit(".", 1)[1] for s in spans
               if s[0] in CHILDREN[p[0]] and p[1] <= s[1] and s[2] <= p[2]]
              for p in parents if p[0] == "rdb.engine.turn"]
    # every scan of the loop: prepare and dispatch, then (behind the
    # admission and the chunk group issued in between) fetch and harvest
    assert all(h in (["prepare", "dispatch"], ["fetch", "harvest"])
               for h in halves)
    if halves[0] == ["fetch", "harvest"]:      # the trace began inside one
        halves = halves[1:]
    assert halves[0::2] == [["prepare", "dispatch"]] * len(halves[0::2])
    assert halves[1::2] == [["fetch", "harvest"]] * len(halves[1::2])
    # a chunk group's two halves likewise: dispatched under one prefill
    # phase; where it ended a prompt, fetched and finished under another
    # when it was issued behind a scan
    for p in parents:
        if p[0] != "rdb.engine.prefill":
            continue
        kids = [s[0].rsplit(".", 1)[1] for s in spans
                if s[0] in CHILDREN[p[0]] and p[1] <= s[1] and s[2] <= p[2]]
        assert kids[0] in ("prepare", "fetch", "finish"), kids


def test_the_phases_tile_the_engine_threads_time(traced):
    _engine_, (spans,) = traced
    tops = [s for s in spans if s[0] in TOP]
    for a, b in zip(tops, tops[1:]):
        assert a[2] <= b[1], (a, b)       # top-level phases never overlap
    covered = sum(e - s for _, s, e, _ in tops)
    extent = tops[-1][2] - tops[0][1]
    assert (extent - covered) / extent < UNCOVERED_SHARE
    # one loop iteration with work: fabric, admit, [prefill], turn (issued),
    # admit, [prefill: the next group issued behind the scan], turn
    # (fetched, harvested), [prefill: that group completed], publish
    i = next(i for i, s in enumerate(tops) if s[0] == "rdb.engine.turn")
    after = [s[0] for s in tops[i + 1:i + 6]]
    assert after[0] == "rdb.engine.admit"
    j = after.index("rdb.engine.turn")
    assert after[1:j] in ([], ["rdb.engine.prefill"])
    k = after.index("rdb.engine.publish")
    assert after[j + 1:k] in ([], ["rdb.engine.prefill"])
    before = [s[0] for s in tops[max(i - 3, 0):i]]
    assert before[-1] in ("rdb.engine.prefill", "rdb.engine.admit")
    assert "rdb.engine.fabric" in before and "rdb.engine.admit" in before
    # the idle wait follows an iteration that found nothing to do
    j = max(i for i, s in enumerate(tops) if s[0] == "rdb.engine.idle_wait")
    assert tops[j - 1][0] == "rdb.engine.admit"


# --- the turn ring ---------------------------------------------------------------
def test_ring_holds_one_record_per_dispatch_with_monotone_stamps(lm):
    engine, queue = _engine(lm)
    steps0 = engine.steps
    reqs = _submit(queue, engine.model.name)
    engine.run_until_idle(timeout_s=300)
    for r in reqs:
        r.future.result(timeout=5)
    ring = list(engine.turns)
    assert ring and all(isinstance(t, Turn) for t in ring)
    assert engine.turns_dropped == 0
    assert {t.kind for t in ring} == {"turn", "chunk"}
    assert sum(t.substeps for t in ring) == engine.steps - steps0
    # 40 and 30 tokens at a 16-token chunk: 3 + 2 chunks; the four short
    # prompts one chunk each, grouped at most two to a program
    chunks = [t for t in ring if t.kind == "chunk"]
    assert sum(t.tokens for t in chunks) >= 3 * 16 + 2 * 16 + 8 + 16 + 16 + 16
    for t in ring:
        assert t.t_dispatch <= t.t_issued <= t.t_done
        assert t.t_fetched == 0.0 or t.t_issued <= t.t_fetched <= t.t_done
        assert 0 <= t.active <= engine.num_slots and t.queue_len >= 0
        assert 0 <= t.pages_allocated <= engine.num_pages
        assert not t.after_idle        # run_until_idle never waits
        if t.kind == "turn":
            assert t.t_fetched and t.substeps >= 1 and t.tokens == 0
            assert t.active >= 1
        else:
            assert t.substeps == 0 and t.tokens > 0 and t.trains >= 1
    # in dispatch order; a program is dispatched before the previous one's
    # work is done only behind a scan not fetched yet, and says so
    assert all(t.queued_behind >= 0 for t in ring)
    for a, b in zip(ring, ring[1:]):
        assert a.t_dispatch <= b.t_dispatch
        assert a.t_done <= b.t_dispatch or (
            b.queued_behind > 0 and a.kind == "turn" and b.kind == "chunk")
    # a chunk that finishes no prompt fetches nothing; one that does, does
    assert any(t.t_fetched == 0.0 for t in chunks)
    assert any(t.t_fetched for t in chunks)


def test_reset_clears_the_ring_and_a_full_ring_counts_what_it_drops(lm):
    engine, queue = _engine(lm)
    reqs = _submit(queue, engine.model.name)
    engine.run_until_idle(timeout_s=300)
    n = len(engine.turns)
    assert n > 4
    engine.reset_ttft_window()
    assert len(engine.turns) == 0 and engine.turns_dropped == 0
    assert engine.turn_summary() == {"dispatches": 0, "scans": 0,
                                     "dropped": 0}
    # the same work again into a ring of four: same dispatches, n - 4 dropped
    engine.turns = collections.deque(maxlen=4)
    reqs = _submit(queue, engine.model.name)
    engine.run_until_idle(timeout_s=300)
    for r in reqs:
        r.future.result(timeout=5)
    assert len(engine.turns) == 4 and engine.turns_dropped == n - 4
    assert engine.snapshot()["turns"]["dropped"] == n - 4


def test_idle_wait_marks_the_next_record(lm):
    engine, queue = _engine(lm)
    engine.warmup()
    engine.reset_ttft_window()
    engine.start()
    try:
        time.sleep(0.03)                  # the loop idles
        for r in _submit(queue, engine.model.name, lens=(5,)):
            r.future.result(timeout=120)
    finally:
        engine.stop()
    first, *rest = list(engine.turns)
    assert first.after_idle and first.kind == "chunk"
    assert not any(t.after_idle for t in rest)
    # no gap is charged to the host across the wait
    assert first.t_dispatch not in {
        g["at_ms"] for g in engine.turn_summary(longest=10 ** 6)["longest_gaps"]}


def test_flight_recorder_turn_spans_and_scan_wait_come_from_the_ring(lm):
    t = tracer()
    t.reset()
    spans = []
    t.set_exporter(spans.append)
    try:
        engine, queue = _engine(lm)
        reqs = _submit(queue, engine.model.name)
        engine.run_until_idle(timeout_s=300)
        for r in reqs:
            r.future.result(timeout=5)
    finally:
        t.reset()
    ring = [x for x in engine.turns if x.kind == "turn"]
    turn_spans = [s for s in spans if s.name == "decode.turn"]
    assert [(s.start_ms, s.end_ms) for s in turn_spans] == [
        (x.t_dispatch, x.t_fetched) for x in ring]
    assert [(s.attributes["horizon"], s.attributes["active"])
            for s in turn_spans] == [(x.substeps, x.active) for x in ring]
    assert all(len(s.links) <= s.attributes["active"] for s in turn_spans)
    # every admission's scan wait is an overlap with a scan of the ring
    parts = list(engine._ttft_parts)
    assert len(parts) == len(reqs)
    longest = max(x.t_fetched - x.t_dispatch for x in ring)
    assert all(0.0 <= scan <= min(wait, longest) for wait, scan, _ in parts)
    prefill = [s for s in spans if s.name == "decode.prefill"]
    assert sorted(s.attributes["scan_wait_ms"] for s in prefill) == sorted(
        round(scan, 2) for _, scan, _ in parts)


def test_snapshot_sums_the_ring(lm):
    engine, queue = _engine(lm)
    reqs = _submit(queue, engine.model.name)
    engine.run_until_idle(timeout_s=300)
    for r in reqs:
        r.future.result(timeout=5)
    ring = list(engine.turns)
    s = engine.snapshot()["turns"]
    scans = [t for t in ring if t.kind == "turn"]
    assert s["dispatches"] == len(ring) and s["dropped"] == 0
    assert s["substeps_per_dispatch"] == pytest.approx(
        sum(t.substeps for t in scans) / len(scans))
    assert 0.0 < s["mean_occupancy"] <= 1.0
    assert 0.0 < s["host_gap_share"] < 1.0
    gaps = s["longest_gaps"]
    assert 1 <= len(gaps) <= 8
    assert [g["gap_ms"] for g in gaps] == sorted(
        (g["gap_ms"] for g in gaps), reverse=True)
    for g in gaps:
        assert g["gap_ms"] == pytest.approx(g["harvest_ms"] + g["feed_ms"],
                                            abs=2e-3)
        assert g["after"] in ("turn", "chunk") and g["before"] in ("turn", "chunk")
        # the load the engine stood under, from the two records of the gap
        prev, cur = next((a, b) for a, b in zip(ring, ring[1:])
                         if round(b.t_dispatch, 3) == g["at_ms"])
        assert g["trains"] == cur.trains and g["queue_len"] == prev.queue_len
        assert g["pages_allocated"] == prev.pages_allocated > 0
        assert g["positions_cached"] == prev.positions_cached > 0
    hg = s["host_gap_ms"]
    # a gap: the earlier record fetched, and nothing queued on the device
    # at the later one's dispatch
    fetched = sum(1 for a, b in zip(ring, ring[1:])
                  if a.t_fetched and not b.queued_behind)
    assert hg["n"] == fetched
    assert hg["sum"] == pytest.approx(hg["harvest_sum"] + hg["feed_sum"])
    assert hg["p50"] <= hg["p99"] <= hg["max"] == pytest.approx(
        gaps[0]["gap_ms"], abs=1e-3)
    # a slice of the ring over a stated span: what the benchmark reads
    part = engine.turn_summary(records=ring[:len(ring) // 2], span_ms=1e6)
    assert part["dispatches"] == len(ring) // 2
    assert part["host_gap_share"] == pytest.approx(
        part["host_gap_ms"]["sum"] / 1e6)
    assert len(engine.turn_summary(longest=10 ** 6)["longest_gaps"]) == fetched
    assert s["overlapped_dispatch_share"] == pytest.approx(
        sum(1 for t in ring if t.queued_behind) / len(ring))


# --- the ring's count of live page-table entries (ISSUE 28) ----------------------
def _scan(substeps, live=None):
    rec = Turn("turn", 0.0, 1.0, 2.0, 3.0, substeps, 0, 2, 0, 0, 8, 100,
               False)
    return rec if live is None else rec._replace(kv_pages_live=live)


CHUNK = Turn("chunk", 4.0, 5.0, 0.0, 6.0, 0, 512, 2, 1, 0, 8, 100, False)


@pytest.mark.parametrize("records, entries, share", [
    # 4 slots x 8 entries: (6 x 2 + 10 x 8) live of 32 x (2 + 8) walked
    ([_scan(2, 6), CHUNK, _scan(8, 10)], 8, (6 * 2 + 10 * 8) / (32 * 10)),
    ([_scan(1, 32), _scan(1, 32)], 8, 1.0),            # every entry live
    ([_scan(8, 4), _scan(8, 4)], 8, 1 / 8),            # idle: page 0 a slot
    ([_scan(2), _scan(8)], 8, None),       # records without the field: 0
    ([_scan(2, 6), _scan(8, 10)], 0, None),            # no table width
    ([CHUNK, CHUNK], 8, None),                         # no scan
])
def test_summarize_turns_gives_the_live_page_share_by_hand(
        records, entries, share):
    from ray_dynamic_batching_tpu.engine.decode import summarize_turns

    s = summarize_turns(records, 4, table_entries=entries)
    if share is None:
        assert not {"kv_pages_live", "kv_pages_scanned",
                    "kv_live_page_share"} & set(s)
        return
    assert s["kv_live_page_share"] == pytest.approx(share)
    assert s["kv_pages_live"] == sum(
        t.kv_pages_live * t.substeps for t in records)
    assert s["kv_pages_scanned"] == 4 * entries * sum(
        t.substeps for t in records if t.kind == "turn")


def test_a_paged_scan_counts_the_entries_its_first_substep_may_attend(lm):
    """Each slot counts the pages up to its cached length's (an idle slot
    its first): with one prompt past a page's end, a scan with that slot
    decoding counts one entry more than there are slots."""
    engine, queue = _engine(lm, max_len=300)       # 3 entries a slot
    reqs = _submit(queue, engine.model.name, lens=(5, 140, 9))
    engine.run_until_idle(timeout_s=300)
    for r in reqs:
        r.future.result(timeout=5)
    scans = [t for t in engine.turns if t.kind == "turn"]
    assert scans and all(t.kv_pages_live in (4, 5) for t in scans)
    assert any(t.kv_pages_live == 5 for t in scans)
    assert all(t.kv_pages_live == 0 for t in engine.turns
               if t.kind != "turn")
    snap = engine.snapshot()
    live = sum(t.kv_pages_live * t.substeps for t in scans)
    walked = 4 * 3 * sum(t.substeps for t in scans)
    assert snap["turns"]["kv_live_page_share"] == pytest.approx(live / walked)
    assert snap["kv_pool"]["pages_live"] == live
    assert snap["kv_pool"]["pages_scanned"] == walked


# --- the ring's order and its gaps since a scan is fetched last (ISSUE 37) -----------
def _at(kind, dispatch, fetched, done, behind=0, substeps=0, after_idle=False):
    return Turn(kind, dispatch, dispatch + 1.0, fetched, done, substeps, 0, 2,
                1, 0, 8, 100, after_idle)._replace(queued_behind=behind)


# scan; the next chunk issued behind it before it is fetched; the next scan
# behind that chunk (it ended no prompt: never fetched); a chunk that ends a
# prompt, fetched after the scan before it; a scan to a device known empty;
# a scan right after it
OVERLAPPED = [
    _at("turn", 100.0, 150.0, 152.0, substeps=1),
    _at("chunk", 103.0, 0.0, 153.0, behind=1),
    _at("turn", 156.0, 200.0, 202.0, behind=1, substeps=1),
    _at("chunk", 159.0, 206.0, 208.0, behind=1),
    _at("turn", 212.0, 260.0, 262.0, substeps=2),      # gap 6 = 2 + 4
    _at("turn", 265.0, 300.0, 301.0, substeps=2),      # gap 5 = 2 + 3
]


def test_a_gap_counts_only_before_a_dispatch_that_found_nothing_queued():
    from ray_dynamic_batching_tpu.engine.decode import summarize_turns

    s = summarize_turns(OVERLAPPED, 4, span_ms=1000.0, longest=10)
    hg = s["host_gap_ms"]
    # not 103 - 150 (the chunk went out BEFORE that scan was fetched), not
    # 159 - 200 either: the device held a program both times
    assert hg["n"] == 2 and hg["sum"] == pytest.approx(6.0 + 5.0)
    assert hg["harvest_sum"] == pytest.approx(2.0 + 2.0)
    assert hg["feed_sum"] == pytest.approx(4.0 + 3.0)
    assert s["host_gap_share"] == pytest.approx(11.0 / 1000.0)
    assert [(g["at_ms"], g["after"], g["before"]) for g in s["longest_gaps"]] \
        == [(212.0, "chunk", "turn"), (265.0, "turn", "turn")]
    assert all(g["gap_ms"] >= 0 for g in s["longest_gaps"])
    assert s["overlapped_dispatch_share"] == pytest.approx(3 / 6)
    assert s["substeps_per_dispatch"] == pytest.approx(6 / 4)


@pytest.mark.parametrize("behind, gaps, share", [
    ((0, 0, 0), 2, 0.0),          # nothing queued anywhere: both gaps count
    ((0, 1, 0), 1, 1 / 3),        # the second dispatch hid behind the first
    ((0, 1, 1), 0, 2 / 3),
    ((0, 0, 2), 1, 1 / 3),        # a count above one is one dispatch
])
def test_overlapped_dispatch_share_counts_dispatches_not_programs(
        behind, gaps, share):
    from ray_dynamic_batching_tpu.engine.decode import summarize_turns

    ring = [_at("turn", 10.0 * i, 10.0 * i + 5, 10.0 * i + 6, behind=b,
                substeps=1) for i, b in enumerate(behind)]
    s = summarize_turns(ring, 4)
    assert s["overlapped_dispatch_share"] == pytest.approx(share)
    assert s.get("host_gap_ms", {"n": 0})["n"] == gaps
    assert len(s["longest_gaps"]) == gaps


def test_a_ring_with_no_overlap_sums_as_it_did():
    """The old order's ring (every record's work done before the next
    dispatch, ``queued_behind`` 0 wherever the earlier record was fetched):
    every field of the summary the parent gave, at the parent's value."""
    from ray_dynamic_batching_tpu.engine.decode import summarize_turns

    ring = [
        _at("turn", 100.0, 150.0, 152.0, substeps=2),
        _at("chunk", 155.0, 0.0, 157.0),                     # gap 5 = 2 + 3
        _at("turn", 158.0, 210.0, 211.0, behind=1, substeps=2),
        _at("turn", 300.0, 340.0, 341.0, substeps=8, after_idle=True),
        _at("turn", 345.0, 380.0, 382.0, substeps=4),        # gap 5 = 1 + 4
    ]
    s = summarize_turns(ring, 4, span_ms=1000.0)
    # ... beside the thread's tiling (ISSUE 55), which records without its
    # fields give whole to the host's side
    tiling = {k: s.pop(k) for k in TILING_KEYS}
    assert tiling["thread_ms"] == {"wall": 230.0, "blocked": 0.0, "idle": 0.0,
                                   "host": 230.0, "cpu": 0.0, "clipped": 0.0}
    assert tiling["thread_host_share"] == 1.0
    assert s.pop("overlapped_dispatch_share") == pytest.approx(1 / 5)
    gaps = s.pop("longest_gaps")
    assert [g["gap_ms"] for g in gaps] == [5.0, 5.0]
    assert s.pop("host_gap_ms") == {
        "n": 2, "p50": 5.0, "p99": 5.0, "max": 5.0, "sum": 10.0,
        "harvest_sum": 3.0, "feed_sum": 7.0, "harvest_p50": 1.5,
        "feed_p50": 3.5}
    # ... and the counters of scans issued ahead (ISSUE 56): none here
    assert [s.pop(k) for k in (
        "scans_issued_ahead_share", "ahead_wasted_substeps",
        "ahead_wasted_substep_share")] == [0.0, 0, 0.0]
    assert s == {"dispatches": 5, "scans": 4, "dropped": 0,
                 "substeps_per_dispatch": 4.0,
                 "mean_occupancy": 2 * 16 / (4 * 16),
                 "host_gap_share": 10.0 / 1000.0}


def test_the_engines_ring_is_in_dispatch_order_with_overlap(lm):
    """A started engine's ring: records in ``t_dispatch`` order though a
    scan's record is written after the chunk's dispatch behind it, and the
    summary's gaps are all non-negative."""
    engine, queue = _engine(lm)
    reqs = _submit(queue, engine.model.name, lens=(5, 40, 30, 44), new=12)
    engine.run_until_idle(timeout_s=300)
    for r in reqs:
        r.future.result(timeout=5)
    ring = list(engine.turns)
    assert [t.t_dispatch for t in ring] == sorted(t.t_dispatch for t in ring)
    assert any(t.queued_behind for t in ring)
    s = engine.turn_summary(longest=10 ** 6)
    assert all(g["gap_ms"] >= 0 and g["harvest_ms"] >= 0 and g["feed_ms"] >= 0
               for g in s["longest_gaps"])
    assert 0.0 < s["overlapped_dispatch_share"] < 1.0


# --- the engine thread's time, tiled from the ring (ISSUE 55) ----------------------
def test_fetch_stamps_lie_between_issue_and_done_and_are_zero_together(lm):
    engine, queue = _engine(lm)
    reqs = _submit(queue, engine.model.name)
    engine.run_until_idle(timeout_s=300)
    for r in reqs:
        r.future.result(timeout=5)
    ring = list(engine.turns)
    assert any(t.t_fetched == 0.0 for t in ring)
    for t in ring:
        assert (t.t_fetch == 0.0) == (t.t_fetched == 0.0)
        if t.t_fetched:
            assert t.t_issued <= t.t_fetch <= t.t_fetched <= t.t_done
        else:
            assert t.ready_at_fetch is False
        assert isinstance(t.ready_at_fetch, bool)
        assert t.cpu_ms >= 0.0
        assert t.idle_ms == 0.0            # run_until_idle never waits
    # one thread wrote them all: only the first found no baseline, and a
    # thread's CPU clock never runs ahead of the wall (1 ms for the grain)
    assert ring[0].cpu_ms == 0.0
    for a, b in zip(ring, ring[1:]):
        assert a.t_done <= b.t_done        # appended in ``t_done`` order
        assert b.cpu_ms <= b.t_done - a.t_done + 1.0
    # every record carries its program's number, each once
    assert sorted(t.seq for t in ring) == list(
        range(ring[0].seq, ring[0].seq + len(ring)))


def test_a_scan_waited_out_before_its_fetch_is_found_ready(lm):
    from ray_dynamic_batching_tpu.engine.decode import thread_parts

    engine, queue = _engine(lm)
    _submit(queue, engine.model.name, lens=(5,))
    engine._admit()
    engine._drain_prefill()
    with engine._phase("rdb.engine.turn") as ph:
        issued = engine._issue_turn(ph, 2)
    issued.packed.block_until_ready()
    time.sleep(0.01)
    prev = engine.turns[-1]
    with engine._phase("rdb.engine.turn") as ph:
        engine._complete_turn(ph, issued)
    rec = engine.turns[-1]
    assert rec.kind == "turn" and rec.t_fetched and rec.ready_at_fetch is True
    # the device waited for the host: none of the tile counts as blocked
    assert thread_parts(prev, rec).blocked == 0.0
    engine.run_until_idle(timeout_s=300)


def test_idle_ms_holds_the_idle_waits_and_nothing_else(lm, monkeypatch):
    from ray_dynamic_batching_tpu.utils.metrics import now_ms

    engine, queue = _engine(lm)
    engine.warmup()
    engine.reset_ttft_window()
    waits = []
    wait = queue.wait_for_requests

    def stamped(timeout_s):
        t = now_ms()
        try:
            return wait(timeout_s)
        finally:
            waits.append((t, now_ms()))

    monkeypatch.setattr(queue, "wait_for_requests", stamped)
    t_start = now_ms()
    engine.start()
    try:
        time.sleep(0.03)                  # the loop idles
        for r in _submit(queue, engine.model.name, lens=(5,)):
            r.future.result(timeout=120)
    finally:
        engine.stop()
    first, *rest = list(engine.turns)
    assert first.after_idle and rest
    for t in rest:
        assert not t.after_idle and t.idle_ms == 0.0
    # the engine's pair of stamps stands round each wait: no less than the
    # waits' own wall, no more than the time since the loop began
    inside = sum(b - a for a, b in waits if b <= first.t_dispatch)
    assert inside > 0.0
    assert inside <= first.idle_ms <= first.t_dispatch - t_start


def test_a_thread_switch_restarts_the_cpu_baseline(lm):
    import threading

    engine, queue = _engine(lm)
    me = threading.get_ident()
    reqs = _submit(queue, engine.model.name, lens=(5, 9))
    engine.run_until_idle(timeout_s=300)       # this thread writes
    n = len(engine.turns)
    assert n >= 2 and engine._cpu_thread == me
    engine.start()                              # then the loop's own
    try:
        loop = engine._thread.ident
        reqs += _submit(queue, engine.model.name, lens=(7, 11))
        for r in reqs:
            r.future.result(timeout=120)
    finally:
        engine.stop()
    m = len(engine.turns)
    assert m > n and loop != me and engine._cpu_thread == loop
    reqs = _submit(queue, engine.model.name, lens=(6,))
    engine.run_until_idle(timeout_s=300)       # and this one again
    for r in reqs:
        r.future.result(timeout=5)
    ring = list(engine.turns)
    assert len(ring) > m and engine._cpu_thread == me
    # the first record a thread writes after another's has no baseline of
    # its own clock: 0.0, never one thread's clock less another's
    assert ring[0].cpu_ms == 0.0 and ring[n].cpu_ms == 0.0
    assert ring[m].cpu_ms == 0.0
    assert all(t.cpu_ms >= 0.0 for t in ring)


def _tile(kind, dispatch, done, fetch=(), ready=False, cpu=0.0, idle=0.0,
          substeps=0, behind=0):
    """A record whose call took 1 ms; ``fetch``: (t_fetch, t_fetched)."""
    t_fetch, t_fetched = fetch or (0.0, 0.0)
    return _at(kind, dispatch, t_fetched, done, behind, substeps,
               after_idle=idle > 0)._replace(
        t_fetch=t_fetch, ready_at_fetch=ready, cpu_ms=cpu, idle_ms=idle)


FIRST = _tile("turn", 90.0, 100.0, fetch=(92.0, 98.0), cpu=50.0, substeps=1)


@pytest.mark.parametrize("records, ms", [
    # the host waited 7 of 12 ms; the rest is its own side
    ([_tile("turn", 101.0, 112.0, fetch=(103.0, 110.0), cpu=3.0)],
     dict(wall=12.0, blocked=7.0, idle=0.0, host=5.0, cpu=3.0)),
    # the same fetch found its result ready: a copy, counted as the host's
    ([_tile("turn", 101.0, 112.0, fetch=(103.0, 110.0), ready=True,
            cpu=10.0)],
     dict(wall=12.0, blocked=0.0, idle=0.0, host=12.0, cpu=10.0)),
    # a chunk that ended no prompt fetched nothing
    ([_tile("chunk", 101.0, 104.0, cpu=2.5)],
     dict(wall=4.0, blocked=0.0, idle=0.0, host=4.0, cpu=2.5)),
    # an idle wait of 40 ms inside a tile of 50
    ([_tile("chunk", 141.0, 150.0, fetch=(143.0, 148.0), cpu=4.0,
            idle=40.0)],
     dict(wall=50.0, blocked=5.0, idle=40.0, host=5.0, cpu=4.0)),
    # two tiles sum; the first record's own parts never count
    ([_tile("turn", 101.0, 110.0, fetch=(102.0, 108.0), cpu=2.0),
      _tile("chunk", 103.0, 114.0, fetch=(111.0, 113.0), cpu=1.0, behind=1)],
     dict(wall=14.0, blocked=8.0, idle=0.0, host=6.0, cpu=3.0)),
    # a CPU clock of 10 ms ticks charges one tile of four a whole tick: a
    # reading beside the parts, which it does not move
    ([_tile("turn", 100.0 + 3 * i, 103.0 + 3 * i, cpu=10.0 * (i == 1))
      for i in range(4)],
     dict(wall=12.0, blocked=0.0, idle=0.0, host=12.0, cpu=10.0)),
    # a hand-built tile whose fetch and idle wait outlast it (8 + 6 of 12
    # ms): its rest is clipped at 0 and kept; the next tile's is its own
    ([_tile("turn", 101.0, 112.0, fetch=(102.0, 110.0), idle=6.0),
      _tile("turn", 113.0, 122.0, fetch=(114.0, 120.0), cpu=3.0)],
     dict(wall=22.0, blocked=14.0, idle=6.0, host=4.0, cpu=3.0,
          clipped=2.0)),
], ids=["blocked", "found-ready", "unfetched", "idle", "two-tiles",
        "coarse-clock", "clipped"])
def test_summarize_turns_tiles_the_thread_by_hand(records, ms):
    from ray_dynamic_batching_tpu.engine.decode import summarize_turns

    s = summarize_turns([FIRST] + records, 4)
    ms = dict(ms, clipped=ms.get("clipped", 0.0))
    assert s["thread_ms"] == ms
    whole = ms["wall"] + ms["clipped"]
    assert [s[k] for k in SHARES] == [
        ms[k] / whole for k in ("blocked", "idle", "host")]
    assert sum(s[k] for k in SHARES) == pytest.approx(1.0, abs=1e-12)
    worst = s["longest_records"][0]
    assert worst["wall"] == max(
        b.t_done - a.t_done for a, b in zip([FIRST] + records, records))
    assert worst["wall"] + worst["clipped"] == pytest.approx(
        worst["blocked"] + worst["idle"] + worst["host"])


def test_summarize_turns_gives_the_fetches_and_the_longest_tiles_by_hand():
    from ray_dynamic_batching_tpu.engine.decode import summarize_turns

    ring = [
        FIRST,
        _tile("turn", 101.0, 112.0, fetch=(103.0, 110.0), ready=True,
              substeps=2),
        _tile("chunk", 113.0, 116.0),
        _tile("turn", 117.0, 190.0, fetch=(119.0, 188.0), cpu=20.0,
              substeps=4, behind=1),
        _tile("chunk", 191.0, 196.0, fetch=(192.0, 195.0)),
    ]
    s = summarize_turns(ring, 4, longest=2)
    assert s["fetch_found_ready_share"] == 1 / 4     # of FETCHED records
    assert [(r["wall"], r["kind"], r["substeps"], r["queued_behind"],
             r["t_dispatch"], r["blocked"], r["host"], r["cpu"])
            for r in s["longest_records"]] == [
        (74.0, "turn", 4, 1, 117.0, 69.0, 5.0, 20.0),
        (12.0, "turn", 2, 0, 101.0, 0.0, 12.0, 0.0)]
    # one record, or none, tiles nothing
    assert not set(TILING_KEYS) & set(summarize_turns(ring[:1], 4))
    # the tiling does not care for a span, nor for the slots
    assert summarize_turns(ring, 16, span_ms=1e6)["thread_ms"] == s["thread_ms"]


def test_snapshot_carries_the_threads_tiling(lm):
    engine, queue = _engine(lm)
    reqs = _submit(queue, engine.model.name)
    engine.run_until_idle(timeout_s=300)
    for r in reqs:
        r.future.result(timeout=5)
    ring = list(engine.turns)
    s = engine.snapshot()["turns"]
    assert set(TILING_KEYS) <= set(s)
    ms = s["thread_ms"]
    assert ms["wall"] == pytest.approx(ring[-1].t_done - ring[0].t_done)
    assert ms["idle"] == 0.0 and ms["cpu"] == pytest.approx(
        sum(t.cpu_ms for t in ring[1:]))
    assert ms["clipped"] == 0.0 and ms["wall"] == pytest.approx(
        ms["blocked"] + ms["idle"] + ms["host"])
    assert sum(s[k] for k in SHARES) == pytest.approx(1.0)
    assert all(0.0 <= s[k] <= 1.0 for k in SHARES)
    assert 0.0 <= s["fetch_found_ready_share"] <= 1.0
    longest = s["longest_records"]
    assert 1 <= len(longest) <= 8
    assert [r["wall"] for r in longest] == sorted(
        (r["wall"] for r in longest), reverse=True)
    assert {"blocked", "idle", "host", "cpu", "clipped", "kind",
            "substeps", "queued_behind", "t_dispatch"} <= set(longest[0])


def test_a_record_that_stalled_is_logged_with_its_parts(lm, monkeypatch):
    from ray_dynamic_batching_tpu.engine import decode
    from ray_dynamic_batching_tpu.utils.metrics import now_ms

    engine, _queue = _engine(lm)
    said = []
    monkeypatch.setattr(decode.logger, "warning",
                        lambda fmt, *a: said.append(fmt % a))
    t = now_ms()
    engine._log_dispatch("turn", t - 3.0, t - 2.0, t, 1, 0, 1, 0,
                         fetch=(t - 1.0, False))
    assert said == []          # no record before it: nothing to tile
    t = now_ms()
    engine._log_dispatch("turn", t - 3.0, t - 2.0, t, 1, 0, 1, 0,
                         fetch=(t - 1.0, False))
    assert said == []          # a millisecond in its fetch
    t = now_ms()
    engine._log_dispatch("chunk", t - 3.0, t - 2.0, t, 0, 16, 1, 1,
                         fetch=(t - 2 * decode._STALL_WARN_MS, True))
    assert said == []          # long, but the result was ready: a copy
    t = now_ms()
    engine._log_dispatch("turn", t - 3.0, t - 2.0, t, 2, 0, 1, 0,
                         fetch=(t - 2 * decode._STALL_WARN_MS, False),
                         queued_behind=1)
    (line,) = said
    assert "a turn record" in line and "blocked in its fetch 2000" in line
    assert "substeps=2 queued_behind=1" in line
    # and one whose HOST side outlasts the limit (no fetch waited)
    monkeypatch.setattr(decode, "_STALL_WARN_MS", 5.0)
    time.sleep(0.01)
    t = now_ms()
    engine._log_dispatch("chunk", t - 3.0, t - 2.0, 0.0, 0, 16, 1, 1)
    assert len(said) == 2 and "blocked in its fetch 0, idle 0" in said[1]
    assert float(said[1].split("the host's side ")[1].split()[0]) >= 10.0


def test_dispatch_and_fetch_phases_carry_the_programs_number(traced):
    """``seq`` (``_note_issue``'s number, the record's ``Turn.seq``) on the
    dispatch and fetch phases, ``ready`` on the fetches: a device program of
    the trace joins its ring record, whatever order the ring stands in and
    however often it wrapped, and an idle gap under a fetch says who
    waited."""
    engine, (spans,) = traced
    ring = list(engine.turns)
    kinds = {"rdb.engine.turn": "turn", "rdb.engine.prefill": "chunk"}
    by_seq = {t.seq: t for t in ring}
    # the warm-up numbers no program: the records are programs 1 .. n
    assert sorted(by_seq) == list(range(1, len(ring) + 1))
    issued = {s[3]["seq"]: kinds[s[0].rsplit(".", 1)[0]]
              for s in spans if s[0].endswith(".dispatch")}
    assert issued == {n: t.kind for n, t in by_seq.items()}
    fetches = [s for s in spans if s[0].endswith(".fetch")]
    assert all({"seq", "ready"} <= set(s[3]) for s in fetches)
    assert sorted(s[3]["seq"] for s in fetches) == sorted(
        t.seq for t in ring if t.t_fetched)
    for s in fetches:
        rec = by_seq[s[3]["seq"]]
        assert kinds[s[0].rsplit(".", 1)[0]] == rec.kind
        assert s[3]["ready"] == int(rec.ready_at_fetch)
    # every other phase's attributes stand as they were
    assert not any("seq" in s[3] for s in spans
                   if not s[0].endswith((".dispatch", ".fetch")))
