"""The readers a state-space mixer brings: ``ssm_counts`` by hand,
``ssm_state_update`` on the small trace recorded on a TPU v5e
(``data/small.xplane.pb``; its fusions stand for the update's operations and
for the paged kernel's calls) with a hand-made ring, and the three patterns
(the plane's shape, the first product's width, the blocked scan's shapes) on
the names the cell's traced run recorded (``data/falcon_h1_ops.txt``).
Nothing to read is ``None``, never 0 and never an exception: the parent of
the PR that brought them has neither the plane nor the configuration's keys."""

import collections
import json
import re
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import ssm_counts
from benchmark import trace_reduce as tr
from benchmark.readers import device_op_share, ssm_state_update
from ray_dynamic_batching_tpu.engine.decode import Turn

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data" / "small.xplane.pb"
OPS = Path(__file__).parent / "data" / "falcon_h1_ops.txt"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "falcon-h1-34b-1chip.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "falconh1-gen-batch"
METRICS = ("ssm_state_update_roofline_pct",
           "ssm_state_update_dev_share_pct.batch",
           "ssm_chunk_scan_dev_share_pct.batch",
           "ssm_in_proj_dev_share_pct.batch")


def _spec(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())


def test_bytes_are_the_models_from_the_published_keys():
    assert ssm_counts.state_itemsize(CONFIG) == 4           # float32
    assert ssm_counts.state_bytes_per_slot_layer(CONFIG) == 4_194_304
    # a slot a substep: 6 layers read and written, 50.3 MB
    assert ssm_counts.state_step_bytes(CONFIG) == 50_331_648
    # 64 slots: the 3.2 GB a substep of the cell's reckoning
    assert ssm_counts.scan_bytes(CONFIG, 64, 1) == 3_221_225_472
    half = dict(CONFIG, assumed={"ssm_state_dtype": "bfloat16: halved"})
    assert ssm_counts.state_step_bytes(half) == 25_165_824
    cut = dict(CONFIG, num_hidden_layers=2)
    assert 3 * ssm_counts.state_step_bytes(cut) == (
        ssm_counts.state_step_bytes(CONFIG))


def test_the_cell_lists_the_four_metrics_and_not_the_paged_roofline():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_per_s"
        assert m["unit"] == "%" and m["source"] == "device_trace"
    assert by_name[METRICS[0]]["better"] == "higher"
    # its reader takes head_dim as d_model // num_heads: 256 here, truth 128
    assert CELL not in by_name["paged_decode_roofline_pct"]["workloads"]
    assert CELL in by_name["conv_state_carried_chunks_pct.batch"]["workloads"]
    assert CELL in by_name["decode_substep_dev_ms.batch"]["workloads"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["out_tok_per_s"]["workloads"]


# --- the roofline's arithmetic ------------------------------------------------------
@pytest.fixture(scope="module")
def trace():
    return tr.Trace(tr.load(str(DATA)))


T0_S = 1000.0
KERNEL = "convolution_tanh_fusion"       # stands for the update AND the count
ARGS = dict(module=None, count=KERNEL, kernel=KERNEL)


def _scan(at_s, active, substeps=8):
    ms = (T0_S + at_s) * 1000.0
    return Turn("turn", ms, ms + 1, ms + 130, ms + 131, substeps, 0, active,
                0, 0, 10, 100, False)


def _chunk(at_s):
    ms = (T0_S + at_s) * 1000.0
    return Turn("chunk", ms, ms + 1, ms + 20, ms + 21, 0, 512, 60, 2, 0, 10,
                100, False)


def _ctx(trace, ring, config=CONFIG):
    return {"trace": trace, "trace_host_window": (20.4, 24.4),
            "config": config, "peaks": {"hbm_bytes_per_s": 819e9},
            "run": {"t0": T0_S, "window_s": 51.0},
            "engines": [NS(turns=collections.deque(ring))]}


RING = [_scan(10.0, 3), _scan(20.5, 64), _chunk(21.0), _scan(21.5, 62, 2),
        _scan(23.0, 60), _scan(30.0, 1)]


def test_roofline_share_is_least_time_over_the_operations_time(
        trace, capsys):
    secs, calls = trace.op_time(KERNEL)
    got = ssm_state_update.read(_ctx(trace, RING), "roofline_pct", **ARGS)
    # the scans dispatched inside the traced window: 64 x 8, 62 x 2, 60 x 8
    active = (64 * 8 + 62 * 2 + 60 * 8) / 18
    substeps = calls / 6            # a call a layer a substep
    need = active * substeps * 50_331_648
    assert got == pytest.approx(100.0 * need / 819e9 / secs)
    out = capsys.readouterr().out
    assert f"{substeps:.0f} substeps of {active:.1f} advancing slots" in out
    share = ssm_state_update.read(_ctx(trace, RING), "dev_share_pct", **ARGS)
    assert share == pytest.approx(100.0 * secs / trace.busy_s())


@pytest.mark.parametrize("case", [
    "no_trace", "other_model", "no_operation", "no_scan_in_the_window",
    "no_ring", "no_kernel_calls"])
@pytest.mark.parametrize("metric", ["roofline_pct", "dev_share_pct"])
def test_nothing_to_read_is_none_and_never_raises(case, metric, trace):
    ctx, args = _ctx(trace, RING), dict(ARGS)
    if case == "no_trace":
        ctx["trace"] = None
    elif case == "other_model":
        ctx["config"] = {"n_layer": 24}
    elif case == "no_operation":    # by the plane's shape: not in this trace
        args["kernel"] = ""
    elif case == "no_scan_in_the_window":
        ctx["engines"] = [NS(turns=collections.deque([_scan(1.0, 5)]))]
    elif case == "no_ring":
        ctx["engines"] = [NS()]
    else:
        args["count"] = "paged_decode_attention"
    got = ssm_state_update.read(ctx, metric, **args)
    if metric == "dev_share_pct" and case in (
            "no_scan_in_the_window", "no_ring", "no_kernel_calls"):
        assert got is not None      # the share needs neither
    else:
        assert got is None
    with pytest.raises(ValueError, match="unknown metric"):
        ssm_state_update.read(ctx, "ms", **args)


def test_the_plane_is_found_by_its_shape_and_nothing_else_is():
    rx = re.compile(ssm_state_update.plane_pattern(CONFIG))
    for name in ("fusion_f32_64_32_128_256_",
                 "select_dynamic-update-slice_fusion_f32_6_64_32_128_256_",
                 "copy_f32_6_64_32_128_256_",
                 "fusion_f32_64_32_128_"):           # the read-out y = S C
        assert rx.search(name), name
    for name in ("fusion_f32_64_1_32_128_",          # the mixer's x, a row
                 "fusion_bf16_64_32_128_",
                 "fusion_f32_6_2_32_128_256_",       # a chunk's rows' states
                 "fusion_bf16_64_32_128_256_",
                 "fusion_f32_2_64_32_128_256_",      # another depth
                 "fusion_f32_64_32_128_256_1_"):
        assert not rx.search(name), name
    both = re.compile(ssm_state_update.plane_pattern(CONFIG, "_ssm_update"))
    assert both.search("jit__ssm_update") and both.search(
        "fusion_f32_64_32_128_256_")
    for name in ("ssm_state_update_roofline_pct",
                 "ssm_state_update_dev_share_pct.batch"):
        spec = _spec(name)
        assert spec["reader"] == "ssm_state_update"
        assert spec["args"]["module"] == "decode_impl"
        assert spec["args"]["count"] == "paged_decode_attention"


def test_the_first_products_width_is_no_other_sublayers():
    spec = _spec("ssm_in_proj_dev_share_pct.batch")
    wide = (2 * CONFIG["mamba_d_ssm"] + 2 * CONFIG["mamba_n_groups"]
            * CONFIG["mamba_d_state"] + CONFIG["mamba_n_heads"])
    assert spec["reader"] == "device_op_share"
    assert spec["args"] == {"op": f"_{wide}_$"}
    heads, kv, hd = (CONFIG["num_attention_heads"],
                     CONFIG["num_key_value_heads"], CONFIG["head_dim"])
    others = {CONFIG["hidden_size"], heads * hd, kv * hd,
              CONFIG["intermediate_size"], 2 * CONFIG["intermediate_size"],
              CONFIG["mamba_d_ssm"], CONFIG["vocab_size"]}
    assert wide == 9248 and wide not in others


def test_a_pattern_that_finds_nothing_reads_none_and_not_zero(trace):
    # this trace is not this model's: nothing is 9,248 wide
    for name in ("ssm_in_proj_dev_share_pct.batch",
                 "ssm_chunk_scan_dev_share_pct.batch"):
        spec = _spec(name)
        assert spec["reader"] == "device_op_share"
        assert device_op_share.read({"trace": trace}, **spec["args"]) is None
        assert device_op_share.read({"trace": None}, **spec["args"]) is None


# --- the patterns on the names the cell's traced run recorded ------------------------
def _recorded():
    """(busy ms, [(ms, program, stable name)]) of the cell's traced run on a
    v5e (my chip run, PR 53, seed 2147490012; written by
    ``tools/record_cell_ops.py``)."""
    lines = OPS.read_text().splitlines()
    busy_ms = 1000.0 * float(lines[1].split()[2])
    rows = [ln.split() for ln in lines if not ln.startswith("#")]
    return busy_ms, [(float(ms), program, name)
                     for ms, _, program, name in rows]


def _taken(pattern, module=None):
    rx = re.compile(pattern)
    busy_ms, rows = _recorded()
    taken = collections.defaultdict(float)
    for ms, program, name in rows:
        if (module is None or module in program) and rx.search(name):
            taken[name] += ms
    return busy_ms, taken


def test_the_update_is_two_fusions_a_layer_on_the_chip_and_both_are_read():
    """In the decode programs XLA writes the step as TWO fusions a layer:
    the update in place (the plane's shape) and the read-out ``y = S C``
    (``[slots, heads, head]``), which reads the state a second time. The
    pattern takes both (and the 0.4 ms of ``d x``, which has the read-out's
    shape); the share is the run's own reading."""
    busy_ms, taken = _taken(ssm_state_update.plane_pattern(CONFIG),
                            "decode_impl")
    big = {n: ms for n, ms in taken.items() if ms > 1.0}
    assert set(big) == {
        "select_dynamic-update-slice_fusion_f32_6_64_32_128_256_",
        "fusion_f32_64_32_128_"}
    update, read_out = (big[n] for n in sorted(big, reverse=True))
    assert update == pytest.approx(609.27, abs=0.01)
    assert read_out == pytest.approx(266.13, abs=0.01)
    assert 100.0 * sum(taken.values()) / busy_ms == pytest.approx(
        28.6957, abs=1e-3)
    # 124 substeps x 6 layers of each, by the paged kernel's calls
    # beside it in that run: the paged kernel, 2.1% of busy
    _, rows = _recorded()
    paged = [ms for ms, program, name in rows
             if "decode_impl" in program and "paged_decode_attention" in name]
    assert paged and sum(paged) / busy_ms < 0.03
    # the roofline the line printed: 484.5 ms at the peak over these
    assert 100.0 * 484.5 / sum(taken.values()) == pytest.approx(55.32,
                                                                abs=0.05)


def test_the_blocked_scan_and_the_first_product_on_the_recorded_names():
    spec = _spec("ssm_chunk_scan_dev_share_pct.batch")["args"]
    busy_ms, scan = _taken(spec["op"], spec["module"])
    assert 100.0 * sum(scan.values()) / busy_ms == pytest.approx(
        0.4417, abs=1e-3)
    # the block states, the within-block weights, the rows' states' reset
    # and hand-over: shapes only the blocked form has
    for name in ("fusion_f32_4_2_16_128_128_",
                 "convolution_bitcast_fusion_f32_1_4_2_16_128_256_",
                 "copy_f32_1_4_128_2_16_128_",
                 "add_dynamic-update-slice_fusion_f32_6_1_32_128_256_",
                 "dynamic-slice_select_fusion_f32_6_1_32_128_256_"):
        assert name in scan, name
    assert not any("9248" in n or "21504" in n or "261120" in n
                   for n in scan)
    # nothing of it in a decode program
    _, elsewhere = _taken(spec["op"], "decode_impl")
    assert sum(elsewhere.values()) < 0.5
    spec = _spec("ssm_in_proj_dev_share_pct.batch")["args"]
    busy_ms, proj = _taken(spec["op"])
    assert 100.0 * sum(proj.values()) / busy_ms == pytest.approx(
        3.1181, abs=1e-3)
    products = sum(ms for n, ms in proj.items() if "fusion" in n)
    assert products > 0.85 * sum(proj.values())
    assert "bitcast_multiply_fusion_bf16_64_1_9248_" in proj      # decode
    assert "bitcast_multiply_fusion_bf16_1_512_9248_" in proj     # a chunk
