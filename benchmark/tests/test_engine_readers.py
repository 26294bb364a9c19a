"""The two readers of the engine's own record: ``engine_turns`` on a
hand-made ring, ``idle_by_phase`` on a hand-made trace (every number below
is in milliseconds on the host's clock; the device's stamps are 1 ms
early) and both on a small trace recorded on a TPU v5e
(``data/engine_small.xplane.pb`` with the ring of the same run,
``data/engine_small.turns.json``; ``record_engine_trace.py``)."""

import collections
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import device_idle, engine_turns, idle_by_phase
from ray_dynamic_batching_tpu.engine.decode import Turn, summarize_turns

DATA = Path(__file__).parent / "data"


# --- engine_turns on a hand-made ring ---------------------------------------------
T0_S = 1000.0


def _rec(kind, dispatch, fetched, done, substeps=0, tokens=0, active=0,
         after_idle=False):
    ms = T0_S * 1000.0
    return Turn(kind, ms + dispatch, ms + dispatch + 1,
                ms + fetched if fetched else 0.0, ms + done, substeps, tokens,
                active, 1, 0, 10, 100 + dispatch, after_idle)


RING = [
    _rec("turn", 100, 150, 152, substeps=2, active=8),
    _rec("chunk", 155, 0, 157, tokens=512, active=8),      # gap 5 = 2 + 3
    _rec("turn", 158, 210, 211, substeps=2, active=9),     # after an unfetched chunk
    _rec("turn", 300, 340, 341, substeps=8, active=16, after_idle=True),
    _rec("turn", 345, 380, 382, substeps=4, active=12),    # gap 5 = 1 + 4
    _rec("turn", 21_000, 21_050, 21_051, substeps=8, active=1,
         after_idle=True),                                 # the traced part
    _rec("turn", 21_055, 21_100, 21_102, substeps=8, active=1),  # gap 5
]


def _ctx(engines, win=(20.4, 24.4)):
    return {"engines": engines, "trace_host_window": win,
            "run": {"t0": T0_S, "window_s": 51.0}}


def _engine(ring=RING, dropped=0, slots=16):
    """What the reader touches of a ``DecodeEngine``: the ring and the
    program's own summary of a slice of it."""
    return NS(turns=collections.deque(ring), turns_dropped=dropped,
              num_slots=slots,
              turn_summary=lambda records, span_ms=None: summarize_turns(
                  records, slots, dropped, span_ms))


def test_gaps_count_only_after_a_fetched_dispatch_with_no_idle_wait(capsys):
    ctx = _ctx([_engine()])
    assert engine_turns.read(ctx, "host_gap_share_pct") == pytest.approx(
        100.0 * (5 + 5) / 20_400.0)
    assert engine_turns.read(ctx, "substeps_per_dispatch") == pytest.approx(
        (2 + 2 + 8 + 4) / 4)
    assert engine_turns.read(ctx, "slot_occupancy_pct") == pytest.approx(
        100.0 * (8 * 2 + 9 * 2 + 16 * 8 + 12 * 4) / (16 * 16))
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 6 and all(line.startswith("turns: ") for line in out)
    assert "5 dispatches (4 scans)" in out[0] and "turns_dropped=0" in out[0]
    assert "turn: call p50=1.000 wait p50=44.000 ms over 4" in out[1]
    assert "over 2 gaps" in out[2]
    assert "harvest 0.003 s + feed 0.007 s" in out[2]
    # the longest gap (the first of two of 5 ms), ended by the chunk at
    # +155 ms, with the load the turn before it recorded
    assert "longest gap 5.000 ms at +0.155 s, turn -> chunk" in out[3]
    assert ("harvest 2.000 + feed 3.000 ms, with trains=1 queue_len=0 "
            "pages_allocated=10 positions_cached=200") in out[3]
    assert "before the trace: host gap p50=5.000 ms" in out[4]
    assert "inside the trace: host gap p50=5.000 ms, 4.0 slot-" in out[5]
    with pytest.raises(ValueError):
        engine_turns.read(ctx, "no_such_metric")


def test_without_a_traced_part_the_whole_window_counts():
    ctx = _ctx([_engine()], win=None)
    assert engine_turns.read(ctx, "substeps_per_dispatch") == pytest.approx(
        (2 + 2 + 8 + 4 + 8 + 8) / 6)
    assert engine_turns.read(ctx, "host_gap_share_pct") == pytest.approx(
        100.0 * (5 + 5 + 5) / 51_000.0)


def test_engines_are_averaged():
    ctx = _ctx([_engine(), _engine(slots=32)])
    one = engine_turns.read(_ctx([_engine()]), "slot_occupancy_pct")
    assert engine_turns.read(ctx, "slot_occupancy_pct") == pytest.approx(
        0.75 * one)


@pytest.mark.parametrize("engines", [
    [NS(num_slots=16)],                        # a program without the ring
    [NS(num_slots=16, turns=collections.deque(RING), turns_dropped=0)],
    [_engine(dropped=3)],                      # the ring wrapped
    [_engine(ring=[r for r in RING if r.kind == "chunk"])],   # no scan
    [],
])
def test_nothing_to_read_is_none_and_never_raises(engines):
    for metric in ("host_gap_share_pct", "substeps_per_dispatch",
                   "slot_occupancy_pct"):
        assert engine_turns.read(_ctx(engines), metric) is None


# --- idle_by_phase on a hand-made trace -------------------------------------------
SKEW_MS = 1.0
E = "rdb.engine."
HOST_SPANS = [
    (E + "idle_wait", -5, 10),
    (E + "fabric", 10, 10.1), (E + "admit", 10.1, 10.5),
    # 10.5 - 11: nothing
    (E + "turn", 11, 40), (E + "turn.prepare", 11, 13),
    (E + "turn.dispatch", 13, 14), (E + "turn.fetch", 14, 35),
    (E + "turn.harvest", 35, 40),
    (E + "publish", 40, 40.5),
    # 40.5 - 42: nothing
    (E + "turn", 42, 70), (E + "turn.prepare", 42, 44),
    (E + "turn.dispatch", 44, 45), (E + "turn.fetch", 45, 65),
    (E + "turn.harvest", 65, 70),
    (E + "idle_wait", 70, 105),
]
PROGRAMS = [(13.5, 34.9), (44.5, 65.0)]      # on the host's clock
WINDOW = (0.0, 100.0)


def _ev(name, start_ms, end_ms, **stats):
    return NS(name=name, start_ns=start_ms * 1e6,
              duration_ns=(end_ms - start_ms) * 1e6, stats=stats)


def _trace(spans=HOST_SPANS, programs=PROGRAMS, skew_ms=SKEW_MS,
           replica="m", pause=None):
    """``pause``: an interval (host clock) inside a program in which none
    of its operations runs."""
    host = [_ev(tr.WINDOW_ANNOTATION, *WINDOW)] + [
        _ev(n, s, e, replica=replica,
            **({"horizon": 2, "active": 9} if n == E + "turn" else {}))
        for n, s, e in spans]
    dev = [(s - skew_ms, e - skew_ms) for s, e in programs]
    ops = []
    for s, e in programs:
        if pause and s < pause[0] and pause[1] < e:
            ops += [(s, pause[0]), (pause[1], e)]
        else:
            ops.append((s, e))
    pd = NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name=tr.OPS_LINE, events=[
                _ev("%fusion.1 = bf16[8,8]{1,0} fusion()", s - skew_ms,
                    e - skew_ms) for s, e in ops]),
            NS(name=tr.MODULES_LINE, events=[
                _ev("jit_decode_impl(1)", s, e) for s, e in dev])]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=host)]),
    ])
    return tr.Trace(pd)


def _ms(acc):
    return {k: round(v * 1000.0, 6) for k, v in acc.items()}


def test_each_gap_is_split_over_the_innermost_spans_it_overlaps():
    acc, skew, n_fetch, lower, worst = idle_by_phase.split_idle(_trace())
    assert skew == pytest.approx(SKEW_MS / 1000.0) and n_fetch == 2
    # the longest piece under one span, the idle wait aside: a harvest
    assert worst[0] == pytest.approx(5e-3)
    assert [ev.name for ev in worst[2]] == [E + "turn", E + "turn.harvest"]
    # the launches (a program 0.5 ms after its dispatch began) allow 0.5
    assert lower == pytest.approx(0.5e-3)
    assert _ms(acc) == pytest.approx({
        E + "idle_wait": 9.0 + 31.0,             # no work
        E + "fabric": 0.1, E + "admit": 0.4,     # host work
        E + "turn.prepare": 4.0, E + "turn.harvest": 10.0,
        E + "publish": 0.5,
        E + "turn.dispatch": 1.0, E + "turn.fetch": 0.1,   # runtime edges
        None: 0.5 + 1.5,                         # no span
    })
    kinds = {name: idle_by_phase.kind_of(name) for name in acc}
    assert kinds[E + "turn.harvest"] == "host_work"
    assert kinds[E + "idle_wait"] == "no_work"
    assert kinds[None] == "unattributed"
    assert kinds[E + "turn.fetch"] == kinds[E + "turn.dispatch"] == "edge"


def test_the_parts_sum_to_the_idle_share(capsys):
    trace = _trace()
    ctx = {"trace": trace}
    work = idle_by_phase.read(ctx, "host_work")
    none = idle_by_phase.read(ctx, "no_work")
    assert work == pytest.approx(15.0) and none == pytest.approx(40.0)
    idle = device_idle.read(ctx)
    assert idle == pytest.approx(58.1)
    assert work + none + 1.1 + 2.0 == pytest.approx(idle)
    out = capsys.readouterr().out.splitlines()     # printed once, not twice
    assert sum(1 for line in out if "shifted by +1.000 ms" in line) == 1
    assert sum(1 for line in out if line.startswith("idle: ")) == 1 + 9 + 2
    # two harvests of 5 ms: either, with its turn's attributes
    assert out[-2].startswith("idle: longest under one span 5.000 ms at +0.0")
    assert out[-2].endswith("5 s of the trace, under turn > turn.harvest "
                            "(active=9 horizon=2)")
    assert "the launches allow no less than +0.500 ms" in out[0]
    assert any("rdb.engine.turn.prepare" in line and "under 2 spans" in line
               for line in out)
    assert any("host_work" in line and "rdb.engine.turn.harvest" in line
               and "10.00% of the window" in line for line in out)
    assert out[-1].startswith("idle: host work 15.00 + no work 40.00 + "
                              "runtime edges 1.10 + inside programs 0.00 + "
                              "unattributed 2.00 = 58.10%")
    with pytest.raises(ValueError):
        idle_by_phase.read(ctx, "no_such_part")


def test_the_skew_shift_moves_a_boundary_gap_to_the_right_phase(monkeypatch):
    # On the device's own stamps the first program ends at 33.9, a
    # millisecond early inside the fetch (14 - 35), and starts at 12.5,
    # inside `prepare`: unshifted, the fetch is charged a millisecond too
    # much and the launch is taken from `dispatch` and `prepare`.
    monkeypatch.setattr(idle_by_phase, "clock_skew",
                        lambda *_: (0.0, 0, None))
    raw = _ms(idle_by_phase.split_idle(_trace())[0])
    assert raw[E + "turn.fetch"] == pytest.approx(1.1 + 1.0)
    assert raw.get(E + "turn.dispatch", 0.0) == pytest.approx(0.0)
    assert raw[E + "turn.prepare"] == pytest.approx(4.0 - 1.0)
    monkeypatch.undo()
    shifted = _ms(idle_by_phase.split_idle(_trace())[0])
    assert shifted[E + "turn.fetch"] == pytest.approx(0.1)
    assert shifted[E + "turn.dispatch"] == pytest.approx(1.0)
    assert shifted[E + "turn.prepare"] == pytest.approx(4.0)
    # what lies in the middle of a gap does not depend on the shift
    assert shifted[E + "turn.harvest"] == raw[E + "turn.harvest"]
    # a device clock that runs LATE is shifted back the same way
    late, skew, _, lower, _ = idle_by_phase.split_idle(_trace(skew_ms=-2.0))
    assert skew == pytest.approx(-0.002) and lower == pytest.approx(-0.0025)
    assert _ms(late) == pytest.approx(shifted)


def test_a_pause_inside_a_running_program_is_charged_to_no_host_phase():
    plain = _ms(idle_by_phase.split_idle(_trace())[0])
    acc = _ms(idle_by_phase.split_idle(_trace(pause=(20.0, 21.0)))[0])
    name = idle_by_phase.IN_PROGRAM
    assert acc.pop(name) == pytest.approx(1.0)
    assert idle_by_phase.kind_of(name) == "in_program"
    assert acc == pytest.approx(plain)       # the fetch it lay under: as before


def test_several_replicas_the_first_chips_spans_are_taken():
    other = [(n, s + 3.0, e + 3.0) for n, s, e in HOST_SPANS]
    one = _trace(replica="m@0")
    both = _trace(replica="m@0")
    both.host["python3"] += [
        tr.Event(n, s / 1e3, e / 1e3, {"replica": "m@1"}) for n, s, e in other]
    a = idle_by_phase.split_idle(one)[0]
    b = idle_by_phase.split_idle(both)[0]
    assert _ms(a) == pytest.approx(_ms(b))


@pytest.mark.parametrize("trace", [
    None,
    _trace(spans=[]),                                   # the parent program
    tr.Trace(NS(planes=[NS(name="/host:CPU", lines=[    # a CPU trace
        NS(name="python3", events=[_ev(E + "turn", 0, 1)])])])),
])
def test_nothing_to_read_is_none(trace, capsys):
    assert idle_by_phase.read({"trace": trace}, "host_work") is None
    assert idle_by_phase.read({"trace": trace}, "no_work") is None
    assert capsys.readouterr().out == ""


def test_innermost_segments_are_disjoint_and_cover_the_union():
    spans = [tr.Event("a", 0, 10, {}), tr.Event("a.x", 1, 4, {}),
             tr.Event("a.x.y", 2, 3, {}), tr.Event("a.z", 4, 9, {}),
             tr.Event("b", 12, 13, {})]
    assert idle_by_phase.innermost(spans) == [
        (0, 1, "a"), (1, 2, "a.x"), (2, 3, "a.x.y"), (3, 4, "a.x"),
        (4, 9, "a.z"), (9, 10, "a"), (12, 13, "b")]


# --- both readers on the recorded trace and its ring --------------------------------
@pytest.fixture(scope="module")
def recorded():
    trace = tr.Trace(tr.load(str(DATA / "engine_small.xplane.pb")))
    ring = json.loads((DATA / "engine_small.turns.json").read_text())
    return trace, [Turn(**t) for t in ring["turns"]], ring["device_kind"]


def test_recorded_trace_holds_the_engines_spans_and_one_program_a_dispatch(
        recorded):
    trace, ring, kind = recorded
    assert kind == "TPU v5 lite" and sorted(trace.devices) == [0]
    spans = idle_by_phase.engine_spans(trace, 0)
    names = {ev.name for ev in spans}
    assert {E + n for n in (
        "fabric", "admit", "prefill", "prefill.prepare", "prefill.dispatch",
        "prefill.fetch", "prefill.finish", "turn", "turn.prepare",
        "turn.dispatch", "turn.fetch", "turn.harvest", "publish",
        "idle_wait")} == names
    assert {ev.stats["replica"] for ev in spans} == {"bench_gpt2_medium_tiny"}
    lo, hi = trace.window
    inside = [ev for ev in spans if lo <= ev.start and ev.end <= hi]
    turns = [ev for ev in inside if ev.name == E + "turn"]
    chunks = [ev for ev in inside if ev.name == E + "prefill.dispatch"]
    # the ring of the same run: a record a dispatch, a program a record
    assert len(turns) == sum(1 for t in ring if t.kind == "turn")
    assert len(chunks) == sum(1 for t in ring if t.kind == "chunk")
    assert [(ev.stats["horizon"], ev.stats["active"]) for ev in turns] == [
        (t.substeps, t.active) for t in ring if t.kind == "turn"]
    programs = [m.name.split("(")[0] for m in trace.in_window(trace.modules[0])]
    assert programs == [
        "jit__decode_impl" if t.kind == "turn"
        else "jit__chunk_group_paged_impl" for t in ring]
    fetches = [ev for ev in inside if ev.name.endswith(".fetch")]
    assert len(fetches) == sum(1 for t in ring if t.t_fetched)


def test_recorded_idle_time_splits_into_its_parts(recorded, capsys):
    trace, ring, _ = recorded
    acc, skew, n_fetch, lower, worst = idle_by_phase.split_idle(trace)
    assert n_fetch == sum(1 for t in ring if t.t_fetched)
    # the two bounds of the clocks' offset agree to under a millisecond
    assert lower <= skew and skew - lower < 1e-3
    ctx = {"trace": trace}
    work = idle_by_phase.read(ctx, "host_work")
    none = idle_by_phase.read(ctx, "no_work")
    idle = device_idle.read(ctx)
    window = trace.window_s()
    assert sum(acc.values()) == pytest.approx(window * idle / 100.0, rel=1e-9)
    kinds = collections.Counter()
    for name, secs in acc.items():
        kinds[idle_by_phase.kind_of(name)] += 100.0 * secs / window
    assert work == pytest.approx(kinds["host_work"])
    assert none == pytest.approx(kinds["no_work"])
    assert sum(kinds.values()) == pytest.approx(idle)
    # the engine idles for 30 of the window's ~65 ms, works in the rest on
    # programs of a tenth of a millisecond: nearly all of the window is
    # idle, and nearly none of that unnamed
    assert idle > 95.0 and none > 30.0 and work > 5.0 and kinds["edge"] > 5.0
    assert kinds["unattributed"] < 1.0 and kinds["in_program"] < 0.5
    out = capsys.readouterr().out.splitlines()
    assert out[-1].startswith("idle: host work ")
    assert out[-1].endswith(f"= {idle:.2f}% of the window idle")


def test_the_ring_and_the_trace_agree_on_the_hosts_time(recorded):
    """The ring's gaps (fetch returned -> next dispatch) are host time with
    an empty device, so the trace must show at least as much idle time under
    the host's phases; and no more than the ring's time outside a dispatch's
    call and wait (the gaps, those after an unfetched chunk too, and the
    work after the last fetch) plus a millisecond for the wake from the one
    idle wait, whose time no record holds."""
    trace, ring, _ = recorded
    acc = idle_by_phase.split_idle(trace)[0]
    host_ms = 1000.0 * sum(secs for name, secs in acc.items()
                           if idle_by_phase.kind_of(name) == "host_work")
    summary = summarize_turns(ring, 4, span_ms=100.0)
    gaps_ms = summary["host_gap_ms"]["sum"]
    free_ms = sum(b.t_dispatch - (a.t_fetched or a.t_issued)
                  for a, b in zip(ring, ring[1:]) if not b.after_idle)
    free_ms += ring[-1].t_done - ring[-1].t_fetched
    assert [t.after_idle for t in ring] == [True] + [False] * (len(ring) - 1)
    assert 0.9 * gaps_ms <= host_ms <= 1.1 * (free_ms + 1.0)
    ctx = {"engines": [NS(turns=ring, turns_dropped=0, num_slots=4,
                          turn_summary=lambda records, span_ms=None:
                          summarize_turns(records, 4, 0, span_ms))],
           "trace_host_window": None,
           "run": {"t0": ring[0].t_dispatch / 1000.0 - 0.001, "window_s": 0.1}}
    assert engine_turns.read(ctx, "host_gap_share_pct") == pytest.approx(
        gaps_ms / 1.0)       # ms over 100 ms, in percent
    assert engine_turns.read(ctx, "substeps_per_dispatch") == pytest.approx(
        sum(t.substeps for t in ring) / sum(1 for t in ring if t.kind == "turn"))
    assert summary["longest_gaps"][0]["after"] == "chunk"
