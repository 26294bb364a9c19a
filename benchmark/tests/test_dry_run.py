"""Every cell's files end to end on the CPU at tiny widths, down to the last
line's keys — and the measuring path's refusal without a TPU. Slow (each
cell deploys and warms a tiny engine): run by hand and in rehearsal."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [(w["name"], w["chips"]) for w in BENCH["workloads"]]

_DRY = """
import json, sys
from benchmark.tests.tiny import tiny_cell
from benchmark.run import run_cell
cell = tiny_cell(sys.argv[1], rate_rps=10.0)
res = run_cell(cell, seed=int(sys.argv[2]), seconds=3.0,
               trace=bool(int(sys.argv[3])), require_tpu=False)
print(json.dumps(res))
"""


def _run(code_or_module, args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = [sys.executable] + code_or_module + [str(a) for a in args]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name,chips", CELLS)
def test_cell_reaches_the_last_line_with_the_contracts_keys(name, chips, trace):
    # a seed beyond 32 signed bits, as the driver's are
    proc = _run(["-c", _DRY], [name, 2 ** 31 + 4242, trace], devices=chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    res = json.loads(lines[-1])
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["count"] == chips
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    want = {m["name"] for m in BENCH["per_layer" if trace else "end_to_end"]
            if name in m.get("workloads", [name])}
    got = set(res["metrics"])
    if trace:   # the CPU trace has no device plane: those readers return nothing
        assert got <= want and got, got
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert "breakdown" in res
    else:
        assert got == want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    earlier = "\n".join(lines[:-1])
    for marker in ("device:", "reference:", "paths:", "setup:", "window:",
                   "stall: compiles in window", "stall: gc pauses",
                   "stall: heartbeat", "stall: completions per replica"):
        assert marker in earlier, marker
    if not trace and "tpot_p90_ms" in want:
        assert "metric: tpot_p90_ms = " in earlier
        assert "requests of the window" in earlier
        assert "parts: ttft_ms part 0: n=" in earlier
        assert "parts: ttft_ms whole window: n=" in earlier


def test_the_measuring_path_refuses_without_a_tpu():
    proc = _run(["-m", "benchmark.run"],
                ["--workload", CELLS[0][0], "--seed", 1, "--seconds", 1,
                 "--trace", 0])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
