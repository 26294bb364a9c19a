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


# --- one stated draw of the weights (``weights_seed``) -----------------------
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}
STATED = sorted(n for n, c in CONFIGS.items() if "weights_seed" in c)


@pytest.mark.parametrize("stated", [True, False])
def test_a_stated_draw_gives_every_seed_the_same_weights_and_its_own_tokens(
        stated):
    import jax
    import jax.numpy as jnp

    from benchmark import loadgen, views
    from benchmark.run import model_factory, seeded_params
    from benchmark.tests.tiny import tiny_cell

    cell = tiny_cell("mimo-longdoc-batch")
    prog = cell.config["program"]
    model = model_factory(prog, prog["register_as"])(dtype=jnp.float32)
    view = views.get(cell.config["view"])
    assert "weights_seed" in cell.config
    if not stated:      # as every other configuration: the seed's, as today
        del cell.config["weights_seed"]
    seeds = (11, 2 ** 31 + 12)
    a, b = (jax.tree_util.tree_leaves(
        seeded_params(cell.config, model, view, s, jnp.float32))
        for s in seeds)
    same = [bool((x == y).all()) for x, y in zip(a, b)]
    drawn = [x.size > 1 and float(x.std()) > 0 for x in a]  # not the ones
    assert any(drawn)
    if stated:
        assert all(same)
    else:
        assert not any(s for s, d in zip(same, drawn) if d)
    ra, rb = (loadgen.build_requests(cell.traffic, 512, s, 3.0) for s in seeds)
    assert [r["tokens"] for r in ra] != [r["tokens"] for r in rb]
    assert [len(r["tokens"]) for r in ra] == [len(r["tokens"]) for r in rb]


def test_the_stated_draw_follows_from_the_rule_its_file_states():
    """Of the 16 integers from ``base``, the one whose held share of the
    routed rows, as read on the chip, lies nearest the even share: a
    reviewer can recompute the choice from the file alone."""
    assert STATED == ["mimo-v2-flash-ep16-1chip"]   # no other file names it
    for name in STATED:
        cfg = CONFIGS[name]
        rule = cfg["assumed"]["weights_seed"]
        shares = rule["held_share_pct"]
        assert len(shares) == 16
        dc = cfg["program"]["decoder_config"]
        even = 100.0 * dc["moe_held_experts"] / dc["num_experts"]
        assert rule["even_share_pct"] == even == 6.25
        nearest = min(range(16), key=lambda i: abs(shares[i] - even))
        assert cfg["weights_seed"] == rule["base"] + nearest
