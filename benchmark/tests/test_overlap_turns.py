"""``overlap_turns`` on a hand-made ring in the order the engine dispatches
since it fetches a scan last: the share of the dispatches that found a
program queued on the device, by hand; ``None`` on a summary without the
key (the parent's ``summarize_turns``), and the gap's rule beside it. Then
every cell's traced dry run on the CPU (slow like ``test_dry_run.py``)."""

import collections
import json
from types import SimpleNamespace as NS

import pytest

from benchmark.readers import engine_turns, overlap_turns
from benchmark.tests.test_dry_run import _DRY, _run, BENCH, CELLS
from ray_dynamic_batching_tpu.engine.decode import Turn, summarize_turns

T0_S = 1000.0
SLOTS = 16


def _rec(kind, dispatch, fetched, done, behind=0, substeps=0):
    ms = T0_S * 1000.0
    return Turn(kind, ms + dispatch, ms + dispatch + 1,
                ms + fetched if fetched else 0.0, ms + done, substeps, 0, 8,
                1, 0, 10, 100, False)._replace(queued_behind=behind)


# scan, the next chunk issued behind it BEFORE it is fetched, the next scan
# behind that chunk (never fetched: it ended no prompt), a chunk that ends a
# prompt (fetched), a scan dispatched to a device known empty
RING = [
    _rec("turn", 100, 150, 152, substeps=1),
    _rec("chunk", 103, 0, 153, behind=1),
    _rec("turn", 156, 200, 202, behind=1, substeps=1),
    _rec("chunk", 159, 206, 208, behind=1),
    _rec("turn", 212, 260, 262, substeps=1),            # gap 212 - 206 = 6
    _rec("turn", 21_000, 21_050, 21_051, behind=1, substeps=8),   # traced
]


def _ctx(engines, win=(20.4, 24.4)):
    return {"engines": engines, "trace_host_window": win,
            "run": {"t0": T0_S, "window_s": 51.0}}


def _engine(ring=RING, dropped=0, summarize=summarize_turns):
    return NS(turns=collections.deque(ring), turns_dropped=dropped,
              num_slots=SLOTS,
              turn_summary=lambda records, span_ms=None: summarize(
                  records, SLOTS, dropped, span_ms))


def test_the_share_is_dispatches_behind_a_program_over_all(capsys):
    ctx = _ctx([_engine()])
    assert overlap_turns.read(ctx, "overlapped_dispatch_pct") == (
        pytest.approx(100.0 * 3 / 5))
    # the gap beside it: one dispatch found the device known empty after a
    # fetched record; the chunk issued behind the first scan is no gap
    # though that scan was fetched (later than the chunk's dispatch)
    assert engine_turns.read(ctx, "host_gap_share_pct") == pytest.approx(
        100.0 * 6 / 20_400.0)
    capsys.readouterr()


def test_without_a_traced_part_the_whole_window_counts():
    assert overlap_turns.read(_ctx([_engine()], win=None),
                              "overlapped_dispatch_pct") == pytest.approx(
        100.0 * 4 / 6)


def test_engines_are_averaged():
    none = [r._replace(queued_behind=0) for r in RING]
    assert overlap_turns.read(_ctx([_engine(), _engine(ring=none)]),
                              "overlapped_dispatch_pct") == pytest.approx(
        100.0 * (3 / 5 + 0) / 2)


def _parents_summary(records, slots, dropped, span_ms):
    """A program whose summary lacks the key: the parent's."""
    out = summarize_turns(records, slots, dropped, span_ms)
    out.pop("overlapped_dispatch_share", None)
    return out


@pytest.mark.parametrize("engines", [
    [NS(num_slots=16)],                        # a program without the ring
    [_engine(summarize=_parents_summary)],     # a summary without the key
    [_engine(), _engine(summarize=_parents_summary)],
    [_engine(dropped=3)],                      # the ring wrapped
    [_engine(ring=RING[:1])],                  # one record: nothing summed
    [],
])
def test_nothing_to_read_is_none_and_never_raises(engines, capsys):
    assert overlap_turns.read(_ctx(engines), "overlapped_dispatch_pct") is None
    capsys.readouterr()


def test_an_unknown_metric_raises():
    with pytest.raises(ValueError):
        overlap_turns.read(_ctx([_engine()]), "overlapped_dispatch")


# ``tests/tiny.py`` cannot cut these two cells (their reference check's
# prompt lengths lie beyond its ``max_len``): ``test_dry_run.py`` is red on
# them already, and by hand they run as ``.claude/skills/verify`` says.
_UNCUT = {"kexaone-reason-batch", "keye-longdoc-batch"}


@pytest.mark.parametrize("name,chips",
                         [c for c in CELLS if c[0] not in _UNCUT])
def test_a_cells_traced_dry_run_reports_the_share(name, chips):
    """CPU, tiny widths (``test_dry_run.py``'s harness): the cell's last
    line carries its ``overlapped_dispatch_pct``, a number, four engines'
    mean included."""
    mine = [m["name"] for m in BENCH["per_layer"]
            if m["name"].startswith("overlapped_dispatch_pct.")
            and name in m["workloads"]]
    assert len(mine) == 1
    proc = _run(["-c", _DRY], [name, 2 ** 31 + 3737, 1], devices=chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert 0.0 <= res["metrics"][mine[0]]["value"] <= 100.0
