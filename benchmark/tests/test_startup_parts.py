"""``readers/startup_parts.py`` in the traced dry run (CPU, tiny widths;
slow like ``test_dry_run.py``, whose harness this borrows): the six metrics,
a ``startup:`` line a span, the by-program table, and the five parts in
seconds summing to the ``rdb.startup.deploy`` span, which is the harness's
``deploy_warmup_s``."""

import json
import re

import pytest

from benchmark.readers import startup_parts
from benchmark.tests.test_dry_run import _DRY, _run, BENCH

NAMES = {"startup_trace_lower_s", "startup_backend_s", "startup_first_run_s",
         "startup_engine_build_s", "startup_unaccounted_s",
         "startup_cache_hit_pct"}


def test_the_six_metrics_move_setup_s_in_every_cell():
    mine = [m for m in BENCH["per_layer"] if m["name"] in NAMES]
    assert {m["name"] for m in mine} == NAMES
    cells = [w["name"] for w in BENCH["workloads"]]
    assert all(m["moves"] == "setup_s" and m["workloads"] == cells
               for m in mine)


@pytest.mark.parametrize("name,replicas", [("gpt2m-chat-steady", 1),
                                           ("gpt2m-x4-chat-steady", 4)])
def test_parts_sum_to_the_deploy_span_on_a_dry_run(name, replicas):
    proc = _run(["-c", _DRY], [name, 2 ** 31 + 3838, 1], devices=replicas)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    got = json.loads(lines[-1])["metrics"]
    assert NAMES <= set(got)
    seconds = [got[n]["value"] for n in sorted(NAMES) if n.endswith("_s")]
    assert all(v > 0 for v in seconds)
    assert 0.0 <= got["startup_cache_hit_pct"]["value"] <= 100.0
    total = next(line for line in lines if "rdb.startup.deploy " in line
                 and " = " in line)
    said, deploy = (float(x) for x in re.search(
        r"= ([0-9.]+) s; rdb.startup.deploy ([0-9.]+) s", total).groups())
    assert said == deploy == pytest.approx(sum(seconds), abs=2e-3)
    setup = next(line for line in lines if line.startswith("setup: "))
    harness = float(re.search(r"deploy_warmup_s=([0-9.]+)", setup).group(1))
    assert abs(harness - deploy) < 0.3
    spans = [line.split()[1] for line in lines
             if line.startswith("startup:   ") or
             line.startswith("startup: rdb.")]
    assert spans.count("rdb.startup.deploy") == 1
    assert spans.count("rdb.startup.register") == 1
    for per_replica in ("rdb.startup.replica", "rdb.startup.engine_build",
                        "rdb.startup.warmup"):
        assert spans.count(per_replica) == replicas
    # The deploy's self time is what none of its replicas covers.
    took = {name: [tuple(float(x) for x in re.search(
        r"s +([0-9.]+) s \(self (-?[0-9.]+)\)", line).groups())
        for line in lines if line.startswith("startup: ")
        and line.split()[1:2] == [name]]
        for name in ("rdb.startup.deploy", "rdb.startup.replica",
                     "rdb.startup.register")}
    (whole, own), = took["rdb.startup.deploy"]
    below = sum(dur for name in ("rdb.startup.replica",
                                 "rdb.startup.register")
                for dur, _ in took[name])
    assert 0 <= own == pytest.approx(whole - below, abs=1e-3 * (replicas + 3))
    # tiny.py: buckets 32 and 64, groups 1 and 2, horizons 1, 2 and 8
    assert spans.count("rdb.startup.warmup.program") == 7 * replicas
    table = [line for line in lines
             if line.startswith("startup: by program: ") and " | " in line
             and "span_s" not in line]
    assert len(table) == 7 * replicas
    assert len({line.split(" | ")[0] for line in table}) == 7 * replicas


def test_a_program_without_the_spans_reads_nothing():
    class Old:      # an engine of before PR 38: no ``startup_summary``
        def snapshot(self):
            return {"turns": {}}

    for part in startup_parts.SECONDS + ("cache_hit_pct",):
        assert startup_parts.read({"engines": [Old()]}, part=part) is None
