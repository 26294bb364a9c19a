"""The readers a model with KV state by layer kind brings: ``kv_kind_counts``
by hand, ``paged_decode_kinds_roofline`` on the small trace recorded on a TPU
v5e (``data/small.xplane.pb``; its fusions stand for the kernel's calls),
``kv_kind_turns`` on a hand-made ring. Nothing to read is ``None``, never an
exception: the parent of the PR that brought them has neither the counter
nor the configuration's keys."""

import collections
import json
import re
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import kv_kind_counts
from benchmark import trace_reduce as tr
from benchmark.readers import (
    device_op_share,
    kv_kind_turns,
    paged_decode_kinds_roofline,
)
from ray_dynamic_batching_tpu.engine.decode import Turn, summarize_turns

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data" / "small.xplane.pb"
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "mimo-v2-flash-ep16-1chip.json").read_text())


def test_bytes_are_summed_by_layer_kind_at_the_true_widths():
    kinds = kv_kind_counts.layer_kinds(CONFIG)
    assert [k["window"] for k in kinds] == [0, 128, 128, 128, 128, 0, 128]
    assert [k["kv_heads"] for k in kinds] == [4, 8, 8, 8, 8, 4, 8]
    assert {(k["k_dim"], k["v_dim"]) for k in kinds} == {(192, 128)}
    full = 4 * (192 + 128) * 2       # 2,560 B a resident position a layer
    window = 8 * (192 + 128) * 2     # 5,120 B a window position a layer
    assert kv_kind_counts.kind_scan_bytes(10_000, kinds) == (
        2 * full * 10_000 + 5 * window * 128)
    # a stream shorter than the window reads what it has
    assert kv_kind_counts.kind_scan_bytes(100, kinds) == (
        2 * full + 5 * window) * 100
    # 5 KiB a position the full layers, as the configuration file counts
    assert 2 * full == 5 * 1024


@pytest.fixture(scope="module")
def trace():
    return tr.Trace(tr.load(str(DATA)))


def _ctx(trace, config=CONFIG, stamps=(20.5, 21.0, 22.0, 30.0)):
    return {"trace": trace, "trace_host_window": (20.4, 24.4),
            "config": config, "peaks": {"hbm_bytes_per_s": 819e9},
            "records": [{"prompt_len": 5000, "stamps": list(stamps)}]}


def test_roofline_share_is_least_time_over_the_kernels_time(trace, capsys):
    op = "convolution_tanh_fusion"
    secs, _ = trace.op_time(op)
    got = paged_decode_kinds_roofline.read(_ctx(trace), op=op)
    # tokens 1 and 2 fall inside the traced window (token 0 is the
    # prefill's; token 3 is stamped after it): 5,001 and 5,002 resident
    kinds = kv_kind_counts.layer_kinds(CONFIG)
    need = sum(kv_kind_counts.kind_scan_bytes(n, kinds) for n in (5001, 5002))
    assert got == pytest.approx(100.0 * need / 819e9 / secs)
    assert "2 tokens" in capsys.readouterr().out


@pytest.mark.parametrize("case", ["no_trace", "no_kernel", "other_model",
                                  "no_token"])
def test_nothing_to_read_is_none_and_never_raises(case, trace):
    ctx, op = _ctx(trace), "convolution_tanh_fusion"
    if case == "no_trace":
        ctx["trace"] = None
    elif case == "no_kernel":
        op = "paged_decode_attention"
    elif case == "other_model":
        ctx["config"] = {"layer_types": ["full_attention"]}
    else:
        ctx["records"] = [{"prompt_len": 5, "stamps": [1.0, 2.0]}]
    assert paged_decode_kinds_roofline.read(ctx, op=op) is None


T0_S, SLOTS, ENTRIES = 1000.0, 40, 144


def _rec(dispatch, substeps, full=None):
    ms = T0_S * 1000.0 + dispatch
    rec = Turn("turn", ms, ms + 1, ms + 40, ms + 41, substeps, 0, 8, 0, 0,
               10, 100, False, kv_pages_live=50)
    return rec if full is None else rec._replace(kv_full_pages_live=full)


def _engine(ring, dropped=0, full_entries=ENTRIES):
    return NS(turns=collections.deque(ring), turns_dropped=dropped,
              num_slots=SLOTS,
              turn_summary=lambda records, span_ms=None: summarize_turns(
                  records, SLOTS, dropped, span_ms, table_entries=30,
                  full_table_entries=full_entries))


def _turns_ctx(engines):
    return {"engines": engines, "trace_host_window": (20.4, 24.4),
            "run": {"t0": T0_S, "window_s": 51.0}}


def test_full_pages_live_share_is_weighed_by_substeps(capsys):
    ring = [_rec(100, 2, full=2000), _rec(200, 8, full=3000),
            _rec(21_000, 8, full=5000)]          # the last: the traced part
    got = kv_kind_turns.read(_turns_ctx([_engine(ring)]),
                             "kv_full_pages_live_pct")
    assert got == pytest.approx(
        100.0 * (2000 * 2 + 3000 * 8) / (SLOTS * ENTRIES * 10))
    capsys.readouterr()


@pytest.mark.parametrize("engines", [
    [NS(num_slots=16)],                           # no ring
    [_engine([_rec(100, 2), _rec(200, 2)])],      # one pool: the field is 0
    [_engine([_rec(100, 2, full=9)], full_entries=0)],
    [_engine([_rec(100, 2, full=9)], dropped=1)],
    [],
])
def test_a_model_with_one_pool_reads_none(engines, capsys):
    assert kv_kind_turns.read(_turns_ctx(engines),
                              "kv_full_pages_live_pct") is None
    capsys.readouterr()


# --- the full layers' chunk attention: a pattern of XLA's fusion names ----------
CHUNK_FULL = json.loads((ROOT / "benchmark" / "layer_metrics" / (
    "chunk_attention_full_dev_share_pct.batch.json")).read_text())
CHUNK_OPS = Path(__file__).parent / "data" / "mimo_chunk_ops.txt"
# what a FULL layer's blocked walk is on a v5e: the block's scores and running
# max, the value contraction, the last division, the gathered block of 4 pages
# of the 4-head pool (k, v) and its transposition
FULL_WALK = {
    "fusion_f32_4_16_512_", "fusion_f32_1_4_16_512_128_",
    "divide_convert_fusion_bf16_1_4_16_512_128_", "fusion_bf16_4_128_4_256_",
    "fusion_bf16_4_128_4_128_", "copy_bf16_1_512_4_192_"}


def _recorded():
    lines = CHUNK_OPS.read_text().splitlines()
    busy_ms = 1000.0 * float(lines[1].split()[2])
    rows = [ln.split() for ln in lines if not ln.startswith("#")]
    return busy_ms, [(float(ms), program, name)
                     for ms, _, program, name in rows]


def test_the_chunk_attention_pattern_finds_the_recorded_runs_operations():
    """NOTHING reads ``jax.named_scope("chunk_attention_full")`` (the TPU's
    trace carries no scope): the metric is a pattern of fusion names, held
    here to the names the cell's traced run recorded. It takes every part of
    a full layer's walk, nothing of a window layer's (8 heads x 8), and its
    share is the run's own reading."""
    assert CHUNK_FULL["reader"] == "device_op_share"
    rx = re.compile(CHUNK_FULL["args"]["op"])
    busy_ms, rows = _recorded()
    assert all(re.search(CHUNK_FULL["args"]["module"], program)
               for _, program, _ in rows)
    taken = {name: ms for ms, _, name in rows if rx.search(name)}
    assert FULL_WALK <= set(taken)
    assert not [n for n in taken if re.search(r"_8_8_|_8_(128|192|256)_", n)]
    # what it takes beside the walk is a few broadcasts and copies
    assert sum(taken[n] for n in FULL_WALK) > 0.99 * sum(taken.values())
    assert 100.0 * sum(taken.values()) / busy_ms == pytest.approx(
        5.8648, abs=1e-3)            # the line of that run: 5.8648
    # the window layers' walk is there too, and about half as dear
    window = sum(ms for ms, _, name in rows if re.search(
        r"_8_8_(128|256|512)_|_4_128_8_(128|256)_", name))
    assert 0.4 < window / sum(taken.values()) < 0.6


def test_a_pattern_that_finds_nothing_reads_none_and_not_zero(trace):
    ctx = {"trace": trace}
    got = device_op_share.read(ctx, op="convolution_tanh_fusion")
    secs, _ = trace.op_time("convolution_tanh_fusion")
    assert got == pytest.approx(100.0 * secs / trace.busy_s()) and got > 0
    # this trace is not this model's: no operation has a full layer's shapes
    assert device_op_share.read(ctx, **CHUNK_FULL["args"]) is None
    assert device_op_share.read(ctx, op="no_such_operation") is None
    assert device_op_share.read({"trace": None}, op=".") is None
