"""``kv_turns`` on a hand-made ring: the share of the page tables that the
decode scans' grid walks and that holds KV a slot attends, by hand; the
records of a program without the counter read 0 and the metric ``None``.
Then every cell's traced dry run on the CPU (slow like ``test_dry_run.py``)."""

import collections
import json
from types import SimpleNamespace as NS

import pytest

from benchmark.readers import kv_turns
from benchmark.tests.test_dry_run import _DRY, _run, BENCH, CELLS
from ray_dynamic_batching_tpu.engine.decode import Turn, summarize_turns

T0_S = 1000.0
SLOTS, ENTRIES = 16, 8


def _rec(kind, dispatch, substeps=0, live=None):
    ms = T0_S * 1000.0 + dispatch
    rec = Turn(kind, ms, ms + 1, ms + 40, ms + 41, substeps, 0, 8, 0, 0, 10,
               100, False)
    return rec if live is None else rec._replace(kv_pages_live=live)


RING = [
    _rec("turn", 100, substeps=2, live=20),
    _rec("chunk", 155),                        # a chunk scans no table
    _rec("turn", 200, substeps=8, live=40),
    _rec("turn", 300, substeps=1, live=128),   # every entry live
    _rec("turn", 21_000, substeps=8, live=16),     # in the traced part
]


def _ctx(engines, win=(20.4, 24.4)):
    return {"engines": engines, "trace_host_window": win,
            "run": {"t0": T0_S, "window_s": 51.0}}


def _engine(ring=RING, dropped=0, entries=ENTRIES):
    return NS(turns=collections.deque(ring), turns_dropped=dropped,
              num_slots=SLOTS,
              turn_summary=lambda records, span_ms=None: summarize_turns(
                  records, SLOTS, dropped, span_ms, table_entries=entries))


def test_live_share_is_weighed_by_substeps_over_the_scans_grid(capsys):
    assert kv_turns.read(_ctx([_engine()]), "kv_live_pages_pct") == (
        pytest.approx(100.0 * (20 * 2 + 40 * 8 + 128 * 1)
                      / (SLOTS * ENTRIES * (2 + 8 + 1))))
    capsys.readouterr()


def test_without_a_traced_part_the_whole_window_counts():
    assert kv_turns.read(_ctx([_engine()], win=None), "kv_live_pages_pct") == (
        pytest.approx(100.0 * (20 * 2 + 40 * 8 + 128 * 1 + 16 * 8)
                      / (SLOTS * ENTRIES * (2 + 8 + 1 + 8))))


def test_engines_are_averaged():
    sparse = [r._replace(kv_pages_live=16) if r.kind == "turn" else r
              for r in RING]
    one = kv_turns.read(_ctx([_engine()]), "kv_live_pages_pct")
    assert kv_turns.read(_ctx([_engine(), _engine(ring=sparse)]),
                         "kv_live_pages_pct") == pytest.approx(
        (one + 100.0 * 16 / (SLOTS * ENTRIES)) / 2)


@pytest.mark.parametrize("engines", [
    [NS(num_slots=16)],                        # a program without the ring
    # records without the field (the parent's; a slab engine's) read 0
    [_engine(ring=[_rec("turn", 100, substeps=2), _rec("turn", 200, 2)])],
    [_engine(entries=0)],                      # a slab engine has no table
    [_engine(dropped=3)],                      # the ring wrapped
    [_engine(ring=[r for r in RING if r.kind == "chunk"])],   # no scan
    [_engine(), _engine(ring=[_rec("turn", 100, substeps=2)])],
    [],
])
def test_nothing_to_read_is_none_and_never_raises(engines, capsys):
    assert kv_turns.read(_ctx(engines), "kv_live_pages_pct") is None
    capsys.readouterr()


def test_a_record_without_the_field_reads_zero():
    assert _rec("turn", 0, substeps=1).kv_pages_live == 0


def test_an_unknown_metric_raises():
    with pytest.raises(ValueError):
        kv_turns.read(_ctx([_engine()]), "kv_pages")


@pytest.mark.parametrize("name,chips", CELLS)
def test_a_cells_traced_dry_run_reports_the_share(name, chips):
    """CPU, tiny widths (``test_dry_run.py``'s harness): the cell's last
    line carries its ``kv_live_pages_pct``, four engines' mean included."""
    mine = [m["name"] for m in BENCH["per_layer"]
            if m["name"].startswith("kv_live_pages_pct.")
            and name in m["workloads"]]
    assert len(mine) == 1
    proc = _run(["-c", _DRY], [name, 2 ** 31 + 2828, 1], devices=chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert 0.0 < res["metrics"][mine[0]]["value"] <= 100.0
