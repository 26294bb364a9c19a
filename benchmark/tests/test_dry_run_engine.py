"""The engine readers' lines in the dry run of every cell (CPU, tiny widths;
slow like ``test_dry_run.py``, whose harness this borrows). The CPU's trace
has no device plane, so ``idle_by_phase`` reads nothing here; the recorded
trace of ``test_engine_readers.py`` covers it."""

import json

import pytest

from benchmark.tests.test_dry_run import _DRY, _run, BENCH, CELLS

RING_METRICS = {m["name"] for m in BENCH["per_layer"]
                if m["name"].split(".")[0] in (
                    "host_gap_share_pct", "substeps_per_dispatch",
                    "slot_occupancy_pct")}


@pytest.mark.parametrize("name,chips", CELLS)
def test_traced_dry_run_prints_the_rings_lines_and_metrics(name, chips):
    proc = _run(["-c", _DRY], [name, 2 ** 31 + 2424, 1], devices=chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    res = json.loads(lines[-1])
    want = {m for m in RING_METRICS
            if name in next(x for x in BENCH["per_layer"]
                            if x["name"] == m)["workloads"]}
    assert want and want <= set(res["metrics"])
    for m in want:
        v = res["metrics"][m]["value"]
        assert v > 0 and (v <= 100.0 or not m.endswith("_pct"))
    turns = [line for line in lines if line.startswith("turns: ")]
    assert any("dispatches" in t and "turns_dropped=0" in t for t in turns)
    assert any("host gap p50=" in t and "harvest" in t for t in turns)
    assert any("before the trace" in t for t in turns)
    assert not any(line.startswith("idle: ") for line in lines)
