"""The readers a model with a learned SELECTION over a LATENT cache brings:
``sparse_latent_counts`` by hand, ``sparse_latent_decode_roofline`` on the
small trace recorded on a TPU v5e (``data/small.xplane.pb``; its fusions
stand for the kernel's calls), and the two patterns of XLA's names
(``sparse_latent_select_dev_share_pct.batch``,
``chunk_attention_sparse_latent_dev_share_pct.batch``) held to the names the
cell's traced run recorded (``data/glm5_ops.txt``). Nothing to read is
``None``, never an exception: the parent of the PR that brought them has
neither the kernel nor the configuration's keys."""

import json
import re
from pathlib import Path

import pytest

from benchmark import sparse_latent_counts as counts
from benchmark import trace_reduce as tr
from benchmark.readers import (
    device_op_share,
    sparse_latent_decode_roofline,
    sparse_latent_select,
    sparse_select,
)

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "glm-5-ep16-1chip.json").read_text())
CELL = "glm5-longdoc-batch"


def _metric(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())


def test_the_attention_needs_the_selected_rows_and_the_selection_the_keys():
    assert counts.latent_row_bytes(CONFIG) == 1152            # 576 x 2 B
    # under index_topk every resident row, past it 2,048 of them: 5 layers
    assert counts.selected_positions(300, CONFIG) == 300
    assert counts.selected_positions(2048, CONFIG) == 2048
    assert counts.selected_positions(18432, CONFIG) == 2048
    assert counts.sparse_latent_scan_bytes(300, CONFIG) == 300 * 5 * 1152
    assert counts.sparse_latent_scan_bytes(9400, CONFIG) == 2048 * 5 * 1152
    # 2.4 MB a slot a layer where a walk of 18k rows reads 21 MB
    assert counts.sparse_latent_scan_bytes(18000, CONFIG) / 5 == 2359296
    # every resident position's index key, 256 B a layer
    assert counts.index_key_scan_bytes(9400, CONFIG) == 9400 * 5 * 256
    # 64 heads score 576 values and sum 512 of each selected row
    assert counts.sparse_latent_scan_flops(9400, CONFIG) == (
        2048 * 5 * 2 * 64 * 1088)
    # 121 flop a byte: under the v5e's ridge (197e12 / 819e9 = 240), so the
    # bytes bound it
    assert (counts.sparse_latent_scan_flops(9400, CONFIG)
            / counts.sparse_latent_scan_bytes(9400, CONFIG)) == pytest.approx(
                120.9, abs=0.1)
    # the pool holds the row as 640 lanes; the count does not know, nor
    # which form reads the rows
    text = (ROOT / "benchmark" / "sparse_latent_counts.py").read_text()
    assert "640" not in text and "import ray_dynamic" not in text


@pytest.fixture(scope="module")
def trace():
    return tr.Trace(tr.load(str(DATA / "small.xplane.pb")))


def _ctx(trace, config=CONFIG, stamps=(20.5, 21.0, 22.0, 30.0), prompt=5000):
    return {"trace": trace, "trace_host_window": (20.4, 24.4),
            "config": config,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "records": [{"prompt_len": prompt, "stamps": list(stamps)}]}


@pytest.mark.parametrize("prompt", [5000, 300])
def test_roofline_share_is_least_time_over_the_kernels_time(
        trace, prompt, capsys):
    op = "convolution_tanh_fusion"
    secs, _ = trace.op_time(op)
    got = sparse_latent_decode_roofline.read(_ctx(trace, prompt=prompt),
                                             op=op)
    # tokens 1 and 2 fall inside the traced window (token 0 is the
    # prefill's; token 3 is stamped after it)
    need = sum(counts.sparse_latent_scan_bytes(prompt + i, CONFIG)
               for i in (1, 2))
    ops = sum(counts.sparse_latent_scan_flops(prompt + i, CONFIG)
              for i in (1, 2))
    assert need / 819e9 > ops / 197e12
    assert got == pytest.approx(100.0 * need / 819e9 / secs)
    said = capsys.readouterr().out
    assert "2 tokens" in said and "of the compute peak" in said
    assert "of index keys beside them" in said


@pytest.mark.parametrize("case", ["no_trace", "no_kernel", "other_model",
                                  "latent_without_indexer", "no_token"])
def test_nothing_to_read_is_none_and_never_raises(case, trace):
    ctx, op = _ctx(trace), "convolution_tanh_fusion"
    if case == "no_trace":
        ctx["trace"] = None
    elif case == "no_kernel":
        op = _metric("sparse_latent_decode_roofline_pct")["args"]["op"]
    elif case == "other_model":
        ctx["config"] = {"num_key_value_heads": 8}
    elif case == "latent_without_indexer":
        ctx["config"] = {k: v for k, v in CONFIG.items()
                         if k != "index_topk"}
    else:
        ctx["records"] = [{"prompt_len": 5, "stamps": [1.0, 2.0]}]
    assert sparse_latent_decode_roofline.read(ctx, op=op) is None


def test_the_kernels_metrics_name_the_kernel_and_the_substep_counts_it():
    """The kernel is the latent pool's own, handed a selection
    (``ops/latent_attention.py``); its jitted name holds
    ``paged_decode_attention``, so that ``decode_substep_dev_ms.batch`` (not
    edited) counts one call a layer a substep, as it counts Xing's and
    Keye's."""
    name = "_latent_paged_decode_attention"
    for metric in ("sparse_latent_decode_roofline_pct",
                   "sparse_latent_decode_dev_share_pct.batch"):
        assert re.search(_metric(metric)["args"]["op"], name)
    assert re.search(_metric("decode_substep_dev_ms.batch")["args"][
        "count_pattern"], name)
    src = (ROOT / "ray_dynamic_batching_tpu" / "ops"
           / "latent_attention.py").read_text()
    assert f"def {name}(" in src


def test_the_cell_is_under_the_metrics_whose_counters_it_sets():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"sparse_latent_decode_roofline_pct",
            "sparse_latent_decode_dev_share_pct.batch",
            "sparse_latent_select_dev_share_pct.batch",
            "chunk_attention_sparse_latent_dev_share_pct.batch",
            "kv_selected_rows_pct.batch", "kv_live_pages_pct.batch",
            "moe_held_rows_share_pct.batch", "moe_dev_share_pct.batch",
            "decode_substep_dev_ms.batch"} <= mine
    # Xing's and Keye's kernels' own metrics read other names
    assert not {"latent_decode_roofline_pct", "sparse_decode_roofline_pct",
                "sparse_select_dev_share_pct.batch",
                "chunk_attention_latent_dev_share_pct.batch"} & mine
    new = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert len(new) == 4
    assert {m["name"]: m["layer"] for m in new} == {
        "sparse_latent_select_dev_share_pct.batch":
            "kernels (ops/sparse_latent_attention.py)",
        **{name: "kernels (ops/latent_attention.py)" for name in (
            "sparse_latent_decode_roofline_pct",
            "sparse_latent_decode_dev_share_pct.batch",
            "chunk_attention_sparse_latent_dev_share_pct.batch")}}
    assert {m["moves"] for m in new} == {"out_tok_per_s"}


# --- the two patterns of XLA's names, held to the recorded run --------------------
OPS = DATA / "glm5_ops.txt"
# a chunk's walk over the latent pool under the rows' selection on a v5e: the
# gathered block of 4 pages' rows, the keys' expansion (one layer's keeps its
# shape, four are rematerialised and renamed), the rotary key's broadcast,
# the score product with mask and running max, the value contraction, the
# sums, the block's mask
CHUNK_WALK = {
    "fusion_bf16_4_128_640_", "convolution_bitcast_fusion_bf16_1_512_64_192_",
    "convolution_bitcast_fusion.11.remat", "convolution_bitcast_fusion.12.remat",
    "convolution_bitcast_fusion.13.remat", "convolution_bitcast_fusion.14.remat",
    "broadcast_in_dim_bf16_1_512_64_64_", "select_reduce_fusion_f32_64_512_",
    "fusion_f32_1_64_512_256_", "fusion_f32_64_512_",
    "and_bitcast_fusion_pred_512_512_"}
# the selection outside its loops: a decode step's scores, order keys and
# gathered index keys; a chunk's score blocks, its blocks of index keys and
# queries, the table-wide scores, keys and masks
SELECTION = {
    "fusion_f32_40_18432_", "fusion_bf16_5760_128_128_",
    "fusion_s32_40_18432_", "fusion_u32_40_1_18432_",
    "fusion_f32_128_2048_", "fusion_bf16_16_128_128_",
    "constant_dynamic-slice_fusion_bf16_1_1_128_32_128_",
    "fusion_u32_1_512_18432_", "copy_pred_1_512_18432_"}


def _recorded():
    lines = OPS.read_text().splitlines()
    busy_ms = 1000.0 * float(lines[1].split()[2])
    rows = [ln.split() for ln in lines if not ln.startswith("#")]
    return busy_ms, [(float(ms), program, name)
                     for ms, _, program, name in rows]


def test_the_chunk_walk_pattern_finds_the_recorded_runs_operations():
    """NOTHING reads ``jax.named_scope("sparse_latent_chunk")`` (the TPU's
    trace carries no scope): the metric is a pattern of names inside the
    chunk programs, held here to the names the cell's traced run recorded."""
    spec = _metric("chunk_attention_sparse_latent_dev_share_pct.batch")
    assert spec["reader"] == "device_op_share"
    rx, module = re.compile(spec["args"]["op"]), spec["args"]["module"]
    busy_ms, rows = _recorded()
    taken = {(program, name): ms for ms, program, name in rows
             if rx.search(name) and re.search(module, program)}
    assert all("chunk_group_paged_impl" in program for program, _ in taken)
    names = {name for _, name in taken}
    assert CHUNK_WALK <= names
    # nothing of the experts, the projections, the head, the selection or
    # the decode step
    assert not [n for n in names if re.search(
        r"moe_|_6144_|_12288_|_2048_$|_19360_|_18432_|_448_|_576_$"
        r"|paged_decode|_128_2048_", n)]
    walk = sum(ms for (_, n), ms in taken.items() if n in CHUNK_WALK)
    assert walk > 0.99 * sum(taken.values())
    assert 100.0 * sum(taken.values()) / busy_ms == pytest.approx(
        18.27, abs=0.05)
    # every rematerialised convolution_bitcast_fusion of the run is the
    # walk's key expansion: one a layer but the one that kept its shape
    remat = {n for _, _, n in rows
             if re.fullmatch(r"convolution_bitcast_fusion\.\d+\.remat", n)}
    assert len(remat) == CONFIG["num_hidden_layers"] - 1
    assert remat <= names


def test_the_selection_patterns_find_the_recorded_runs_operations():
    """``sparse_select``'s reader with this cell's ``ops``: the names it
    takes outside the loops, in both programs, and none of the attention's
    side (the selection reshaped into the kernel's operand, the kernel, the
    chunk walk) or the indexer's projections."""
    spec = _metric("sparse_latent_select_dev_share_pct.batch")
    assert spec["reader"] == "sparse_latent_select"
    rxs = [re.compile(p) for p in spec["args"]["ops"]]
    busy_ms, rows = _recorded()
    # the decode program's views of the index keys computed a second time:
    # renamed, with no shape, so taken by name inside that program alone
    remat = spec["args"]["remat"]
    again = {(program, name): ms for ms, program, name in rows
             if re.search(remat["op"], name)
             and re.search(remat["module"], program)}
    assert len(again) == 3
    assert all(program.startswith("jit__decode_impl") for program, _ in again)
    assert not any(rx.search(name) for _, name in again for rx in rxs)
    assert 2.0 < 100.0 * sum(again.values()) / busy_ms < 5.0
    # the same name in a chunk program is something else (a norm, the
    # experts' gather): the pattern alone would take those too
    assert [name for _, program, name in rows
            if re.search(remat["op"], name) and "chunk" in program]
    taken = {(program, name): ms for ms, program, name in rows
             if any(rx.search(name) for rx in rxs)}
    names = {name for _, name in taken}
    assert SELECTION <= names
    assert {p.split("_impl")[0] for p, _ in taken} == {
        "jit__decode", "jit__chunk_group_paged"}
    assert not [n for n in names if re.search(
        r"_40_36_512_|_144_128_$|paged_decode|_64_512_|_640_$|_6144_|moe_",
        n)]
    # (the loops' own operations are inside these too: the reader skips an
    # operation inside a loop it took whole; the table cannot tell)
    assert 6.0 < 100.0 * sum(taken.values()) / busy_ms < 9.0
    # the loop's pattern is of an HLO line: the order keys of a slot's table
    loop = re.compile(spec["args"]["loops"])
    assert loop.search(
        "%while.12 = (s32[], u32[40,1,18432]{2,1,0}, u32[40,1,1]{2,1,0}) "
        "while(%tuple.3), condition=%c, body=%b")
    assert not loop.search(
        "%while.3 = (s32[], f32[1,64,512]{2,1,0}) while(%tuple.9), "
        "condition=%c, body=%b")


def test_a_pattern_that_finds_nothing_reads_none_and_not_zero(trace):
    spec = _metric("chunk_attention_sparse_latent_dev_share_pct.batch")
    assert device_op_share.read({"trace": trace}, **spec["args"]) is None


def test_the_selections_reader_adds_the_views_computed_again(trace, capsys):
    """``sparse_latent_select`` is ``sparse_select`` plus the operations
    ``remat`` names inside its programs; ``None`` where the first reads
    nothing."""
    ops, loops = ["convolution_tanh_fusion"], "^no loop$"
    ctx = {"trace": trace}
    base = sparse_select.read(ctx, ops, loops)
    secs, count = trace.op_time("^copy_bf16", "bench_probe")
    assert base and count == 8
    got = sparse_latent_select.read(
        ctx, ops, loops, {"module": "bench_probe", "op": "^copy_bf16"})
    assert got == pytest.approx(base + 100.0 * secs / trace.busy_s())
    assert "gathered again" in capsys.readouterr().out
    nothing = {"module": "bench_probe", "op": "^no such operation$"}
    assert sparse_latent_select.read(
        ctx, ops, loops, {"module": "^decode_impl", "op": "^copy_bf16"}
    ) == base
    assert sparse_latent_select.read(ctx, ops, loops, nothing) == base
    assert sparse_latent_select.read(
        ctx, ["^no such operation$"], loops, nothing) is None
    assert sparse_latent_select.read(
        {"trace": None}, ops, loops, nothing) is None
