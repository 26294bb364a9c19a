"""``chunk_dispatch_call`` on a hand-made ring of two engines: the median
of a chunk launch's call (``t_issued - t_dispatch``) over the records
dispatched before the traced sub-window, the mean over engines; turn
records and records inside the trace left out; ``None`` on a wrapped ring,
a program without one, or an engine that launched no chunk. Then the
four-replica cell's traced dry run on the CPU (slow like
``test_dry_run.py``) carries the metric, and a one-chip cell's does not."""

import collections
import json
from types import SimpleNamespace as NS

import pytest

from benchmark.readers import chunk_dispatch_call
from benchmark.tests.test_dry_run import _DRY, _run, BENCH
from ray_dynamic_batching_tpu.engine.decode import Turn

T0_S = 1000.0
NAME = "chunk_dispatch_call_p50_ms"


def _rec(kind, dispatch, call):
    ms = T0_S * 1000.0
    return Turn(kind, ms + dispatch, ms + dispatch + call,
                ms + dispatch + call + 4, ms + dispatch + call + 5,
                int(kind == "turn"), 128, 8, 1, 0, 10, 100, False)


# chunk calls of 2, 7 and 3 ms before the trace (median 3); a turn's call
# of 40 ms and a chunk's of 90 ms INSIDE the trace must not be read
RING_A = [
    _rec("chunk", 100, 2.0),
    _rec("turn", 110, 40.0),
    _rec("chunk", 200, 7.0),
    _rec("chunk", 300, 3.0),
    _rec("chunk", 21_000, 90.0),
]
RING_B = [_rec("chunk", 150, 5.0), _rec("turn", 160, 1.0)]


def _ctx(engines, win=(20.4, 24.4)):
    return {"engines": engines, "trace_host_window": win,
            "run": {"t0": T0_S, "window_s": 51.0}}


def _engine(ring, dropped=0):
    return NS(turns=collections.deque(ring), turns_dropped=dropped)


def test_the_median_call_of_the_chunks_before_the_trace():
    assert chunk_dispatch_call.read(_ctx([_engine(RING_A)])) == (
        pytest.approx(3.0))


def test_engines_are_averaged():
    assert chunk_dispatch_call.read(
        _ctx([_engine(RING_A), _engine(RING_B)])) == pytest.approx(4.0)


def test_without_a_traced_part_the_whole_window_counts():
    # 2, 3, 7, 90: the harness's percentile of four
    want = chunk_dispatch_call.stats.percentile([2.0, 7.0, 3.0, 90.0], 50)
    assert chunk_dispatch_call.read(
        _ctx([_engine(RING_A)], win=None)) == pytest.approx(want)


@pytest.mark.parametrize("engines", [
    [NS(num_slots=16)],                           # a program without the ring
    [_engine(RING_A, dropped=2)],                 # the ring wrapped
    [_engine(RING_A), _engine(RING_B, dropped=1)],
    [_engine(RING_A), _engine(RING_B[1:])],       # an engine without a chunk
    [_engine(RING_A[-1:])],                       # chunks inside the trace only
    [],
])
def test_nothing_to_read_is_none_and_never_raises(engines):
    assert chunk_dispatch_call.read(_ctx(engines)) is None


def test_the_benchmark_lists_it_for_the_four_replica_cell_alone():
    (mine,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert mine["workloads"] == ["gpt2m-x4-chat-steady"]
    assert mine["moves"] == "ttft_p90_ms" and mine["unit"] == "ms"
    assert mine["source"] == "program_span"
    assert mine["layer"] == "engine (engine/decode.py)"
    assert BENCH["per_layer"][-1] is mine     # appended, nothing moved


@pytest.mark.parametrize("name,chips,listed", [
    ("gpt2m-x4-chat-steady", 4, True),
    ("gpt2m-chat-steady", 1, False),
])
def test_a_traced_dry_run_reports_it_where_it_is_listed(name, chips, listed):
    proc = _run(["-c", _DRY], [name, 2 ** 31 + 4747, 1], devices=chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert (NAME in res["metrics"]) is listed
    if listed:
        assert res["metrics"][NAME]["unit"] == "ms"
        assert res["metrics"][NAME]["value"] > 0.0
