"""The readers a model with conv layers beside attention layers brings:
``hybrid_counts`` by hand, ``paged_decode_hybrid_roofline`` on the small
trace recorded on a TPU v5e (``data/small.xplane.pb``; its fusions stand for
the kernel's calls), ``state_turns`` on a hand-made ring, and
``short_conv_in_proj_dev_share_pct.batch``'s pattern (one width) on the
names the cell's traced run recorded (``data/lfm2_ops.txt``). Nothing to read
is ``None``, never an exception: the parent of the PR that brought them has
neither the counters nor the configuration's keys."""

import collections
import json
import re
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import hybrid_counts, kernel_bytes
from benchmark import trace_reduce as tr
from benchmark.readers import (
    device_op_share,
    device_op_time,
    device_op_time_per_page_layer,
    paged_decode_hybrid_roofline,
    state_turns,
)
from ray_dynamic_batching_tpu.engine.decode import Turn, summarize_turns

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data" / "small.xplane.pb"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "lfm2-24b-a2b-ep8-1chip.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "lfm2-reason-batch"


def test_bytes_are_counted_over_the_layers_that_hold_pages():
    pages, convs = (hybrid_counts.page_layers(CONFIG),
                    hybrid_counts.conv_layers(CONFIG))
    assert pages == [2, 6, 10, 14, 18, 22, 26, 30, 34, 38]
    assert len(convs) == 30 and sorted(pages + convs) == list(range(40))
    assert hybrid_counts.head_dim(CONFIG) == 64       # hidden / heads
    assert hybrid_counts.head_dim(dict(CONFIG, head_dim=128)) == 128
    # 20 KiB a position: 10 layers x 8 heads x 64 x k and v x 2 B
    assert hybrid_counts.hybrid_scan_bytes(1, CONFIG) == 20 * 1024
    assert hybrid_counts.hybrid_scan_bytes(3000, CONFIG) == (
        kernel_bytes.paged_decode_scan_bytes(3000, 10, 8, 64))
    # ... a quarter of what all 40 layers would hold
    assert 4 * hybrid_counts.hybrid_scan_bytes(3000, CONFIG) == (
        kernel_bytes.paged_decode_scan_bytes(3000, 40, 8, 64))
    # the state: 30 layers x 2 rows x 2,048 x 2 B a slot, 15.7 MB for 64
    assert hybrid_counts.conv_state_bytes_per_slot(CONFIG) == 245_760
    assert 64 * hybrid_counts.conv_state_bytes_per_slot(CONFIG) == 15_728_640
    # a depth cut reads the layers it leaves
    cut = dict(CONFIG, num_hidden_layers=4)
    assert hybrid_counts.page_layers(cut) == [2]
    assert hybrid_counts.conv_layers(cut) == [0, 1, 3]


def test_the_configuration_file_states_what_the_issue_asks():
    dc = CONFIG["program"]["decoder_config"]
    assert CONFIG["num_hidden_layers"] == dc["num_layers"] == 40
    assert CONFIG["vocab_size"] == dc["vocab_size"] == 65536
    assert CONFIG["reduced"] == ["num_experts"]
    assert CONFIG["reduced_from"] == {"num_experts": 64}
    assert (CONFIG["num_experts"], dc["num_experts"],
            dc["moe_held_experts"], dc["moe_top_k"]) == (8, 64, 8, 4)
    assert CONFIG["expert_parallel"]["router_width"] == 64
    assert dc["layer_pattern"] == "CCGC" and dc["conv_kernel"] == 3
    assert [("conv" if c == "C" else "full_attention")
            for c in dc["layer_pattern"] * 10] == CONFIG["layer_types"]
    assert (dc["d_model"], dc["num_heads"], dc["num_kv_heads"],
            dc["head_dim"], dc["mlp_dim"], dc["dense_mlp_dim"]) == (
        CONFIG["hidden_size"], CONFIG["num_attention_heads"],
        CONFIG["num_key_value_heads"], 64, CONFIG["moe_intermediate_size"],
        CONFIG["intermediate_size"])
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-ep8-1chip", "reason-batch", 1)
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", [])}
    # 40 where 10 layers hold KV: four times too high, and refused
    assert "paged_decode_roofline_pct" not in listed
    assert "decode_substep_dev_ms.batch" not in listed
    assert {"paged_decode_hybrid_roofline_pct", "decode_substep_dev_ms.hybrid",
            "short_conv_in_proj_dev_share_pct.batch",
            "conv_state_carried_chunks_pct.batch"} <= listed
    # one ulp of a bfloat16 score in [0.5, 1) excuses a row; nothing else
    check = CONFIG["reference_check"]
    assert set(check) == {"prompt_lens", "new_tokens", "undecided_score_gap"}
    assert check["undecided_score_gap"] == 2.0 ** -8
    # every prompt bucket closes a prompt; tiny.py's 200 + new fits 256
    buckets = CONFIG["deployment"]["llm"]["prompt_buckets"]
    assert {min(b for b in buckets if b >= n % max(buckets))
            for n in check["prompt_lens"]} == set(buckets)
    assert 200 + check["new_tokens"] <= 256


@pytest.fixture(scope="module")
def trace():
    return tr.Trace(tr.load(str(DATA)))


def _ctx(trace, config=CONFIG, stamps=(20.5, 21.0, 22.0, 30.0)):
    return {"trace": trace, "trace_host_window": (20.4, 24.4),
            "config": config, "peaks": {"hbm_bytes_per_s": 819e9},
            "records": [{"prompt_len": 1500, "stamps": list(stamps)}]}


def test_roofline_share_is_least_time_over_the_kernels_time(trace, capsys):
    op = "convolution_tanh_fusion"
    secs, _ = trace.op_time(op)
    got = paged_decode_hybrid_roofline.read(_ctx(trace), op=op)
    # tokens 1 and 2 fall inside the traced window (token 0 is the
    # prefill's; token 3 is stamped after it): 1,501 and 1,502 resident
    need = (1501 + 1502) * 20 * 1024
    assert got == pytest.approx(100.0 * need / 819e9 / secs)
    out = capsys.readouterr().out
    assert "2 tokens over 10 of 40 layers" in out


@pytest.mark.parametrize("case", ["no_trace", "no_kernel", "other_model",
                                  "no_layer_types", "no_token"])
def test_nothing_to_read_is_none_and_never_raises(case, trace):
    ctx, op = _ctx(trace), "convolution_tanh_fusion"
    if case == "no_trace":
        ctx["trace"] = None
    elif case == "no_kernel":
        op = "paged_decode_attention"
    elif case == "other_model":       # layers differ, none is a conv layer
        ctx["config"] = {"layer_types": ["full_attention"] * 4,
                         "num_hidden_layers": 4}
    elif case == "no_layer_types":
        ctx["config"] = {"n_layer": 24}
    else:
        ctx["records"] = [{"prompt_len": 5, "stamps": [1.0, 2.0]}]
    assert paged_decode_hybrid_roofline.read(ctx, op=op) is None


# --- the ring's counts ------------------------------------------------------------
T0_S, SLOTS = 1000.0, 64


def _chunk(dispatch, resets=0, carries=0):
    ms = T0_S * 1000.0 + dispatch
    return Turn("chunk", ms, ms + 1, ms + 20, ms + 21, 0, 512, 60, 2, 0,
                10, 100, False, state_resets=resets, state_carries=carries)


def _scan(dispatch):
    ms = T0_S * 1000.0 + dispatch
    return Turn("turn", ms, ms + 1, ms + 130, ms + 131, 8, 0, 64, 0, 0, 10,
                100, False, kv_pages_live=50)


def _engine(ring, dropped=0):
    return NS(turns=collections.deque(ring), turns_dropped=dropped,
              num_slots=SLOTS,
              turn_summary=lambda records, span_ms=None: summarize_turns(
                  records, SLOTS, dropped, span_ms, table_entries=32))


def _turns_ctx(engines):
    return {"engines": engines, "trace_host_window": (20.4, 24.4),
            "run": {"t0": T0_S, "window_s": 51.0}}


def test_carried_chunks_are_counted_over_the_chunks_run(capsys):
    ring = [_chunk(100, resets=2), _scan(150), _chunk(300, carries=1),
            _chunk(500, resets=1), _chunk(700, carries=1),
            _chunk(21_000, carries=1)]           # the last: the traced part
    got = state_turns.read(_turns_ctx([_engine(ring)]),
                           "conv_state_carried_chunks_pct")
    assert got == pytest.approx(100.0 * 2 / 5)
    assert "3 chunks began a prompt" in capsys.readouterr().out
    with pytest.raises(ValueError, match="unknown metric"):
        state_turns.read(_turns_ctx([_engine(ring)]), "other")


@pytest.mark.parametrize("engines", [
    [NS(num_slots=16)],                                   # no ring
    [_engine([_chunk(100), _scan(150), _chunk(300)])],    # no conv layers
    [_engine([_scan(150), _scan(300)])],                  # no chunk ran
    [_engine([_chunk(100, resets=1), _scan(150)], dropped=1)],
    [],
])
def test_a_model_without_a_conv_state_reads_none(engines, capsys):
    assert state_turns.read(_turns_ctx(engines),
                            "conv_state_carried_chunks_pct") is None
    capsys.readouterr()


# --- a substep's device time: the divisor is the configuration's -----------------
def test_the_substeps_divisor_is_the_layers_that_hold_pages(trace):
    spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                       / "decode_substep_dev_ms.hybrid.json").read_text())
    assert spec["reader"] == "device_op_time_per_page_layer"
    assert "count_divisor" not in spec["args"]       # stated by no metric file
    args = dict(reduce="ms_per_count", module="bench_probe",
                count_pattern="convolution_tanh_fusion")
    by_ten = device_op_time.read({"trace": trace}, count_divisor=10, **args)
    assert by_ten is not None
    got = device_op_time_per_page_layer.read(
        {"trace": trace, "config": CONFIG}, **args)
    assert got == pytest.approx(by_ten)
    # another ratio: the file's own layer_types, not a number written here
    cut = dict(CONFIG, num_hidden_layers=8)          # CCGC CCGC: 2 of 8
    assert device_op_time_per_page_layer.read(
        {"trace": trace, "config": cut}, **args) == pytest.approx(by_ten / 5)
    for config in ({"n_layer": 24},                  # no layer_types
                   {"layer_types": ["conv"] * 2, "num_hidden_layers": 2}):
        assert device_op_time_per_page_layer.read(
            {"trace": trace, "config": config}, **args) is None
    assert device_op_time_per_page_layer.read(
        {"trace": None, "config": CONFIG}, **args) is None


# --- the conv mixers' first product: one width ------------------------------------
IN_PROJ = json.loads((ROOT / "benchmark" / "layer_metrics" / (
    "short_conv_in_proj_dev_share_pct.batch.json")).read_text())
OPS = Path(__file__).parent / "data" / "lfm2_ops.txt"


def _recorded():
    lines = OPS.read_text().splitlines()
    busy_ms = 1000.0 * float(lines[1].split()[2])
    rows = [ln.split() for ln in lines if not ln.startswith("#")]
    return busy_ms, [(float(ms), program, name)
                     for ms, _, program, name in rows]


def test_a_pattern_that_finds_nothing_reads_none_and_not_zero(trace):
    assert IN_PROJ["reader"] == "device_op_share"
    # this trace is not this model's: no operation is 6,144 wide
    assert device_op_share.read({"trace": trace}, **IN_PROJ["args"]) is None
    assert device_op_share.read({"trace": None}, **IN_PROJ["args"]) is None


def test_the_pattern_is_the_first_products_width_and_nothing_else():
    """The metric is named for what it reads: operations whose last
    dimension is 3 x hidden_size, the conv mixers' ``[B | C | x] = u W_in``
    with its streamed weights. The width comes from the configuration file,
    no other sublayer of it has that width, and on the names the cell's
    traced run recorded the pattern takes the first product in the decode
    and the chunk programs and its share is the run's own reading."""
    wide = 3 * CONFIG["hidden_size"]
    assert IN_PROJ["args"]["op"] == f"_{wide}_$"
    heads, kv = CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"]
    others = {CONFIG["hidden_size"], kv * hybrid_counts.head_dim(CONFIG),
              heads * hybrid_counts.head_dim(CONFIG),
              CONFIG["moe_intermediate_size"], CONFIG["intermediate_size"],
              2 * CONFIG["intermediate_size"],
              2 * CONFIG["moe_intermediate_size"], CONFIG["vocab_size"],
              CONFIG["expert_parallel"]["router_width"]}
    assert wide not in others
    rx = re.compile(IN_PROJ["args"]["op"])
    busy_ms, rows = _recorded()
    taken = collections.defaultdict(float)
    for ms, _, name in rows:
        if rx.search(name):
            taken[name] += ms
    assert set(taken) == {
        "convolution_bitcast_fusion_bf16_64_1_6144_",
        "convolution_bitcast_fusion_bf16_1_512_6144_",
        "slice-done_bf16_512_6144_", "slice-start_bf16_2048_6144_",
        "custom-call_bf16_2048_6144_"}
    products = sum(ms for n, ms in taken.items() if "convolution" in n)
    assert products > 0.8 * sum(taken.values())
    programs = {program for ms, program, name in rows if rx.search(name)}
    assert any("decode_impl" in p for p in programs)
    assert any("chunk_group_paged_impl" in p for p in programs)
    assert 100.0 * sum(taken.values()) / busy_ms == pytest.approx(
        3.1581, abs=1e-3)
    # beside it in that run: the kernel the attention layers' reads take
    # and the experts' (the line's 25.5 and 42.3 are of the same busy time)
    paged = sum(ms for ms, _, n in rows if "paged_decode_attention" in n)
    moe = sum(ms for ms, _, n in rows if "moe_grouped_matmul" in n)
    assert 100.0 * paged / busy_ms == pytest.approx(25.535, abs=0.01)
    assert 100.0 * moe / busy_ms == pytest.approx(42.305, abs=0.01)
