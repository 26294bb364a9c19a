"""``engine_thread`` on hand-made rings: the three shares of the engine
thread's time and what the fetches found, by hand, from the program's own
``summarize_turns``; the ring's contiguous run, whatever order the records
were dispatched in; ``None`` on a summary without the keys (the parent's)
and on a wrapped ring. Then a traced dry run on the CPU of the chat cell and
of one closed-loop cell (slow like ``test_dry_run.py``)."""

import collections
import json
from types import SimpleNamespace as NS

import pytest

from benchmark.readers import engine_thread
from benchmark.tests.test_dry_run import _DRY, _run, BENCH, CELLS, ROOT
from ray_dynamic_batching_tpu.engine.decode import Turn, summarize_turns

T0_S = 1000.0
SLOTS = 16


def _spec(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())


MINE = [m for m in BENCH["per_layer"]
        if _spec(m["name"])["reader"] == "engine_thread"]
SHARES = ("engine_thread_blocked_pct", "engine_thread_host_pct")


def _rec(kind, dispatch, done, fetch=(), ready=False, cpu=0.0, idle=0.0,
         substeps=0):
    """Times in ms from the window's start; the call takes 1 ms."""
    ms = T0_S * 1000.0
    t_fetch, t_fetched = (ms + x for x in fetch) if fetch else (0.0, 0.0)
    return Turn(kind, ms + dispatch, ms + dispatch + 1, t_fetched, ms + done,
                substeps, 0, 8, 1, 0, 10, 100, idle > 0)._replace(
        t_fetch=t_fetch, ready_at_fetch=ready, cpu_ms=cpu, idle_ms=idle)


# before the trace, tiles of 20 + 10 + 70 = 100 ms: blocked 12 + 0 + 8,
# idle 50, the host's side 8 + 10 + 12 (its CPU clock 5 + 4 + 6); then one
# traced pair
RING = [
    _rec("turn", 90, 100, fetch=(92, 98), cpu=99.0, substeps=1),
    _rec("turn", 101, 120, fetch=(103, 115), cpu=5.0, substeps=1),
    _rec("chunk", 121, 130, fetch=(122, 129), ready=True, cpu=4.0),
    _rec("turn", 187, 200, fetch=(190, 198), cpu=6.0, idle=50.0, substeps=2),
    _rec("turn", 21_000, 21_010, fetch=(21_002, 21_008), cpu=1.0,
         substeps=8),                                           # traced
    _rec("turn", 21_011, 21_020, fetch=(21_013, 21_018), cpu=2.0,
         substeps=8),
]
BY_HAND = {"engine_thread_blocked_pct": 20.0, "engine_thread_host_pct": 30.0,
           "fetch_found_ready_pct": 100.0 * 1 / 4}


def _ctx(engines, win=(20.4, 24.4)):
    return {"engines": engines, "trace_host_window": win,
            "run": {"t0": T0_S, "window_s": 51.0}}


def _engine(ring=RING, dropped=0, summarize=summarize_turns):
    return NS(turns=collections.deque(ring), turns_dropped=dropped,
              num_slots=SLOTS,
              turn_summary=lambda records, span_ms=None: summarize(
                  records, SLOTS, dropped, span_ms))


def test_the_six_metric_files_name_the_reader_and_a_known_key():
    assert len(MINE) == 6
    assert {m["name"].rsplit(".", 1)[0] for m in MINE} == set(BY_HAND)
    serve = {"gpt2m-chat-steady", "gpt2m-x4-chat-steady"}
    batch = {name for name, _ in CELLS} - serve
    for m in MINE:
        spec = _spec(m["name"])
        name, kind = m["name"].rsplit(".", 1)
        assert spec == {"reader": "engine_thread", "args": {"metric": name}}
        assert engine_thread.read(_ctx([_engine()]), **spec["args"]) is not None
        assert m["source"] == "program_span"
        assert m["layer"] == "engine (engine/decode.py)"
        assert m["unit"] == "%"
        assert (set(m["workloads"]), m["moves"]) == {
            "serve": (serve, "tpot_p90_ms"),
            "batch": (batch, "out_tok_per_s")}[kind]


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_metric_is_the_parts_share_before_the_trace(metric, capsys):
    assert engine_thread.read(_ctx([_engine()]), metric) == pytest.approx(
        BY_HAND[metric])
    capsys.readouterr()


def test_the_shares_and_the_idle_share_sum_to_100(capsys):
    ctx = _ctx([_engine()])
    two = sum(engine_thread.read(ctx, m) for m in SHARES)
    assert two + 50.0 == pytest.approx(100.0)
    lines = [x for x in capsys.readouterr().out.splitlines()
             if x.startswith("thread: ")]
    # printed once a run, not once a metric
    (before,) = [x for x in lines if "before the trace" in x]
    assert ("blocked=20.00 idle=50.00 host=30.00 % of 0.100 s tiled by 3 "
            "records (clipped 0.000 ms; the thread's CPU clock moved 15 of "
            "the host's 30 ms), fetches found ready 25.00%") in before
    (inside,) = [x for x in lines if "inside the trace" in x]
    assert "blocked=50.00 idle=0.00 host=50.00 % of 0.010 s" in inside
    worst = [x for x in lines if "longest tile" in x]
    assert len(worst) == 3 and "longest tile 70.000 ms at +0.187 s" in worst[0]
    assert "idle 50.000 host 12.000 ms (CPU clock 6.000)" in worst[0]
    assert "(substeps=2 queued_behind=0)" in worst[0]


def test_a_part_is_the_rings_contiguous_run(capsys):
    """A scan dispatched BEFORE the part and fetched inside it (behind the
    chunk group issued after it) stands in the ring between two records of
    the part: it is read with them, so that its wall is its own tile's and
    not the next record's; one that was done before the part's first record
    is not."""
    ms = T0_S * 1000.0
    ring = [
        _rec("turn", -30, -10, fetch=(-25, -12), substeps=1),    # before
        _rec("chunk", 5, 20, fetch=(10, 18)),        # the part's first
        _rec("turn", -5, 60, fetch=(21, 51), substeps=1),   # issued at -5
        _rec("turn", 61, 70, fetch=(62, 66), substeps=1),
    ]
    assert [t.t_dispatch - ms for t in ring] == [-30, 5, -5, 61]
    # tiles 40 + 10 ms, blocked 30 + 4; cut by ``t_dispatch`` alone the one
    # tile of 50 ms would have read blocked 4 and the host's side 46
    assert engine_thread.read(_ctx([_engine(ring=ring)]),
                              "engine_thread_blocked_pct") == pytest.approx(
        100.0 * 34 / 50)
    assert "tiled by 2 records" in capsys.readouterr().out


def test_without_a_traced_part_the_whole_window_counts(capsys):
    # tiles of 100 + 20,810 + 10 ms; blocked 20 + 6 + 5
    assert engine_thread.read(_ctx([_engine()], win=None),
                              "engine_thread_blocked_pct") == pytest.approx(
        100.0 * 31 / 20_920)
    assert "inside the trace" not in capsys.readouterr().out


def test_engines_are_averaged(capsys):
    running = [r._replace(ready_at_fetch=True) for r in RING]
    assert engine_thread.read(_ctx([_engine(), _engine(ring=running)]),
                              "engine_thread_blocked_pct") == pytest.approx(
        (20.0 + 0.0) / 2)
    capsys.readouterr()


def _parents_summary(records, slots, dropped, span_ms):
    """A program whose summary lacks the keys: the parent's."""
    out = summarize_turns(records, slots, dropped, span_ms)
    for key in [k for k in out if k.startswith("thread_")] + [
            "fetch_found_ready_share", "longest_records"]:
        out.pop(key)
    return out


@pytest.mark.parametrize("engines", [
    [NS(num_slots=16)],                        # a program without the ring
    [_engine(summarize=_parents_summary)],     # a summary without the keys
    [_engine(), _engine(summarize=_parents_summary)],
    [_engine(dropped=3)],                      # the ring wrapped
    [_engine(ring=RING[:1])],                  # one record: nothing tiled
    [],
])
@pytest.mark.parametrize("metric", ["engine_thread_host_pct",
                                    "fetch_found_ready_pct"])
def test_nothing_to_read_is_none_and_never_raises(engines, metric, capsys):
    assert engine_thread.read(_ctx(engines), metric) is None
    capsys.readouterr()


def test_a_part_without_a_fetch_has_no_fetches_share(capsys):
    blind = [r._replace(t_fetch=0.0, t_fetched=0.0) for r in RING]
    ctx = _ctx([_engine(ring=blind)])
    assert engine_thread.read(ctx, "fetch_found_ready_pct") is None
    assert engine_thread.read(ctx, "engine_thread_blocked_pct") == 0.0
    assert engine_thread.read(ctx, "engine_thread_host_pct") == (
        pytest.approx(50.0))
    capsys.readouterr()


def test_an_unknown_metric_raises():
    with pytest.raises(ValueError):
        engine_thread.read(_ctx([_engine()]), "engine_thread_idle_pct")


@pytest.mark.parametrize("name,chips", [
    c for c in CELLS if c[0] in ("gpt2m-chat-steady", "mistral7b-rag-batch")])
def test_a_cells_traced_dry_run_reports_every_metric_and_both_parts(
        name, chips):
    """CPU, tiny widths (``test_dry_run.py``'s harness): the cell's last
    line carries each of its three metrics, each within 0-100, and the
    reader's lines say where the thread's time went before the trace and
    inside it."""
    mine = [m["name"] for m in MINE if name in m["workloads"]]
    assert len(mine) == 3
    proc = _run(["-c", _DRY], [name, 2 ** 31 + 5555, 1], devices=chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    res = json.loads(lines[-1])
    for m in mine:
        assert 0.0 <= res["metrics"][m]["value"] <= 100.0
    said = [x for x in lines if x.startswith("thread: ")]
    for label in ("before the trace", "inside the trace"):
        (line,) = [x for x in said if label in x]
        shares = [float(line.split(f"{part}=")[1].split()[0])
                  for part in ("blocked", "idle", "host")]
        assert sum(shares) == pytest.approx(100.0, abs=0.05)
        assert "(clipped 0.000 ms" in line
    assert any("longest tile" in x for x in said)
