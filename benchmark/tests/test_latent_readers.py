"""The readers a model with a LATENT cache on a residual path of streams
brings: ``latent_counts`` by hand, ``latent_decode_roofline`` on the small
trace recorded on a TPU v5e (``data/small.xplane.pb``; its fusions stand for
the kernel's calls), and the two patterns of XLA's fusion names
(``chunk_attention_latent_dev_share_pct.batch``,
``hc_mix_dev_share_pct.batch``) held to the names the cell's traced run
recorded (``data/xing_ops.txt``). Nothing to read is ``None``, never an
exception: the parent of the PR that brought them has neither the kernel nor
the configuration's keys."""

import json
import re
from pathlib import Path

import pytest

from benchmark import latent_counts
from benchmark import trace_reduce as tr
from benchmark.readers import device_op_share, latent_decode_roofline

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "xing4-29b-ep8-1chip.json").read_text())


def _metric(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())


def test_a_position_is_one_row_at_its_true_width():
    assert latent_counts.latent_row_bytes(CONFIG) == 1152     # 576 x 2 B
    # 10 served layers, every resident row once: 11.25 KiB a position
    assert latent_counts.latent_scan_bytes(1, CONFIG) == 11520
    assert latent_counts.latent_scan_bytes(9400, CONFIG) == 9400 * 11520
    # 32 heads score 576 values and sum 512: 69,632 flop a row a layer
    assert latent_counts.latent_scan_flops(1, CONFIG) == 10 * 2 * 32 * 1088
    # 60 flop a byte: under the v5e's ridge (197e12 / 819e9 = 240)
    assert (latent_counts.latent_scan_flops(1, CONFIG)
            / latent_counts.latent_scan_bytes(1, CONFIG)) == pytest.approx(
                60.4, abs=0.1)
    # the pool holds the row as 640 lanes; the count does not know
    text = (ROOT / "benchmark" / "latent_counts.py").read_text()
    assert "640" not in text and "ray_dynamic_batching_tpu" not in text


@pytest.fixture(scope="module")
def trace():
    return tr.Trace(tr.load(str(DATA / "small.xplane.pb")))


def _ctx(trace, config=CONFIG, stamps=(20.5, 21.0, 22.0, 30.0)):
    return {"trace": trace, "trace_host_window": (20.4, 24.4),
            "config": config,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "records": [{"prompt_len": 5000, "stamps": list(stamps)}]}


def test_roofline_share_is_least_time_over_the_kernels_time(trace, capsys):
    op = "convolution_tanh_fusion"
    secs, _ = trace.op_time(op)
    got = latent_decode_roofline.read(_ctx(trace), op=op)
    # tokens 1 and 2 fall inside the traced window (token 0 is the
    # prefill's; token 3 is stamped after it): 5,001 and 5,002 resident
    need = sum(latent_counts.latent_scan_bytes(n, CONFIG)
               for n in (5001, 5002))
    assert got == pytest.approx(100.0 * need / 819e9 / secs)
    said = capsys.readouterr().out
    assert "2 tokens" in said and "of the compute peak" in said


@pytest.mark.parametrize("case", ["no_trace", "no_kernel", "other_model",
                                  "no_token"])
def test_nothing_to_read_is_none_and_never_raises(case, trace):
    ctx, op = _ctx(trace), "convolution_tanh_fusion"
    if case == "no_trace":
        ctx["trace"] = None
    elif case == "no_kernel":
        op = _metric("latent_decode_roofline_pct")["args"]["op"]
    elif case == "other_model":
        ctx["config"] = {"num_key_value_heads": 8}
    else:
        ctx["records"] = [{"prompt_len": 5, "stamps": [1.0, 2.0]}]
    assert latent_decode_roofline.read(ctx, op=op) is None


def test_the_kernels_metrics_name_the_kernel_and_the_substep_counts_it():
    """The kernel's jitted name holds ``paged_decode_attention``, so that
    ``decode_substep_dev_ms.batch`` (not edited) counts one call a layer a
    substep, as it counts Keye's ``_sparse_paged_decode_attention``."""
    name = "_latent_paged_decode_attention"
    for metric in ("latent_decode_roofline_pct",
                   "latent_decode_dev_share_pct.batch"):
        assert re.search(_metric(metric)["args"]["op"], name)
    assert re.search(_metric("decode_substep_dev_ms.batch")["args"][
        "count_pattern"], name)
    src = (ROOT / "ray_dynamic_batching_tpu" / "ops"
           / "latent_attention.py").read_text()
    assert f"def {name}(" in src


# --- the two patterns of XLA's fusion names, held to the recorded run ----------
OPS = DATA / "xing_ops.txt"
# a chunk's walk over the latent pool on a v5e: the gathered block of 4
# pages' rows, the expansion, the scores' running max, the probabilities, the
# value contraction, the one score product that keeps a shape, the mask
CHUNK_WALK = {
    "fusion_bf16_4_128_640_", "convolution_bitcast_fusion_bf16_1_512_32_256_",
    "select_reduce_fusion_f32_32_512_", "fusion_f32_32_512_",
    "fusion_f32_1_32_512_128_", "fusion_f32_32_512_512_",
    "compare_and_fusion_pred_512_512_"}
# the streams: the Sinkhorn kernel (a chunk's and a step's), x~ Phi, the
# mixes (fused into the products before them)
STREAMS = {
    "_hc_sinkhorn_f32_16_4_128_", "_hc_sinkhorn_f32_16_1_128_",
    "fusion_f32_24_512_", "fusion_f32_24_40_", "fusion_bf16_1_512_1_3584_",
    "fusion_bf16_40_1_1_3584_", "clamp_exponential_fusion_f32_16_4_128_"}


def _recorded():
    lines = OPS.read_text().splitlines()
    busy_ms = 1000.0 * float(lines[1].split()[2])
    rows = [ln.split() for ln in lines if not ln.startswith("#")]
    return busy_ms, [(float(ms), program, name)
                     for ms, _, program, name in rows]


def _taken(metric):
    spec = _metric(metric)
    assert spec["reader"] == "device_op_share"
    rx, module = re.compile(spec["args"]["op"]), spec["args"].get("module")
    busy_ms, rows = _recorded()
    return busy_ms, rows, {
        (program, name): ms for ms, program, name in rows
        if rx.search(name) and (module is None or re.search(module, program))}


def test_the_chunk_attention_pattern_finds_the_recorded_runs_operations():
    """NOTHING reads ``jax.named_scope("latent_chunk_*")`` (the TPU's trace
    carries no scope): the metric is a pattern of fusion names inside the
    chunk programs, held here to the names the cell's traced run recorded."""
    busy_ms, rows, taken = _taken("chunk_attention_latent_dev_share_pct.batch")
    assert all("chunk_group_paged_impl" in program for program, _ in taken)
    names = {name for _, name in taken}
    assert CHUNK_WALK <= names
    # nothing of the experts, the streams, the projections or the decode step
    assert not [n for n in names if re.search(
        r"moe_|_3584_|_9216_|_1024_|_192_$|_576_$|hc_sinkhorn|paged_decode",
        n)]
    walk = sum(ms for (_, n), ms in taken.items() if n in CHUNK_WALK)
    assert walk > 0.97 * sum(taken.values())
    assert 100.0 * sum(taken.values()) / busy_ms == pytest.approx(
        16.578, abs=1e-2)
    # what it cannot take: nine layers' score products, renamed without a
    # shape by XLA's rematerialisation (the metric reads low by them)
    lost = sum(ms for ms, program, name in rows if re.fullmatch(
        r"fusion\.28\d\d\.remat|fusion\.27\d\d\.remat", name))
    assert 3.0 < 100.0 * lost / busy_ms < 4.5


def test_the_streams_pattern_finds_the_recorded_runs_operations():
    busy_ms, rows, taken = _taken("hc_mix_dev_share_pct.batch")
    names = {name for _, name in taken}
    assert STREAMS <= names
    # both programs' (a decode step mixes its 40 tokens' streams too)
    assert {p.split("_impl")[0] for p, _ in taken} == {
        "jit__decode", "jit__chunk_group_paged"}
    assert not [n for n in names if re.search(
        r"moe_grouped|paged_decode|_9216_|_16384_|_32_512_|_640_$", n)]
    assert 100.0 * sum(taken.values()) / busy_ms == pytest.approx(
        8.401, abs=1e-2)


def test_a_pattern_that_finds_nothing_reads_none_and_not_zero(trace):
    for metric in ("chunk_attention_latent_dev_share_pct.batch",
                   "hc_mix_dev_share_pct.batch"):
        assert device_op_share.read(
            {"trace": trace}, **_metric(metric)["args"]) is None
