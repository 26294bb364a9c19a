"""The trace reduction on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, by ``record_small_trace.py``: three runs of one
jitted 4-step scan inside the harness's window annotation)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.Trace(tr.load(str(DATA)))


def test_planes_lines_and_counts(trace):
    assert sorted(trace.devices) == [0] and len(trace.devices[0]) == 39
    assert [m.name.split("(")[0] for m in trace.modules[0]] == [
        "jit_bench_probe"] * 3
    assert any(t.startswith("python") for t in trace.host)
    # the window is the harness's own annotation, not the events' extent
    (ann,) = [e for evs in trace.host.values() for e in evs
              if e.name == tr.WINDOW_ANNOTATION]
    assert trace.window == (ann.start, ann.end)


def test_stable_names_drop_suffixes_and_keep_type_and_shape(trace):
    names = {tr.stable_name(e) for e in trace.devices[0]}
    assert names == {"convolution_tanh_fusion_bf16_512_512_",
                     "copy_bf16_512_512_", "copy-start_bf16_512_512_",
                     "copy-done_bf16_512_512_", "while_s32__"}
    ev = tr.Event("%copy.255 = bf16[24,128,128,16,64]{4,3,2,1,0:T(8,128)} "
                  "copy(bf16[24,128,128,16,64]{2,4,3,1,0} %cache_v.1)", 0, 1, {})
    assert tr.stable_name(ev) == "copy_bf16_24_128_128_16_64_"
    ev = tr.Event("fusion.87.remat_uncompressed", 0, 1, {})
    assert tr.stable_name(ev).startswith("fusion.87")


def test_counts_by_pattern_and_by_program(trace):
    # the device's clock runs about a millisecond ahead of the host's, so
    # the first of the three runs falls before the annotated window
    secs, n = trace.op_time("convolution_tanh_fusion")
    assert n == 8            # 2 runs in the window x 4 scan steps
    assert trace.op_time("convolution_tanh_fusion", module="bench_probe") == (secs, n)
    assert trace.op_time("convolution_tanh_fusion", module="no_such") == (0.0, 0.0)
    msecs, runs = trace.module_time("bench_probe")
    assert runs == 2 and msecs >= secs
    assert trace.module_time("no_such") == (0.0, 0.0)


def test_self_times_never_count_a_nanosecond_twice(trace):
    pairs = trace.op_self_times(0)
    busy = trace.busy_s()
    assert sum(t for _, t in pairs) == pytest.approx(busy, rel=1e-6)
    whiles = [(e, t) for e, t in pairs if tr.stable_name(e) == "while_s32__"]
    assert whiles and all(t < 0.2 * e.dur for e, t in whiles)  # body is its children's
    assert 0 < busy < trace.window_s()
    top = trace.top_ops(10)
    assert top[0][0] == "convolution_tanh_fusion_bf16_512_512_"
    assert sum(s for _, s in trace.top_ops(50)) == pytest.approx(busy, rel=1e-6)


def test_idle_gaps_tile_the_rest_of_the_window(trace):
    gaps = trace.idle_gaps(10)
    assert len(gaps) <= 10 and all(len(g) == 2 for g in gaps)
    assert sum(s for _, s in gaps) == pytest.approx(
        trace.window_s() - trace.busy_s(), rel=1e-6)


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.clip([(0, 1), (2, 5), (6, 7)], 0.5, 3) == [(0.5, 1), (2, 3)]
    assert tr.total([(0, 1), (2, 2.5)]) == 1.5
    a, b, c = tr.Event("a", 0, 10, {}), tr.Event("b", 1, 4, {}), tr.Event("c", 2, 3, {})
    assert {e.name: t for e, t in tr.self_times([c, a, b])} == {
        "a": 7, "b": 2, "c": 1}


def test_readers_read_the_trace(trace):
    from benchmark.readers import device_idle, device_op_time

    ctx = {"trace": trace, "config": {"program": {"decoder_config": {
        "num_layers": 4}}}}
    idle = device_idle.read(ctx)
    assert 99.0 < idle < 100.0
    per = device_op_time.read(
        ctx, reduce="ms_per_count", module="bench_probe",
        count_pattern="convolution_tanh_fusion", count_divisor="num_layers")
    secs, _ = trace.module_time("bench_probe")
    assert per == pytest.approx(1000.0 * secs / 2)      # 8 fusions / 4 "layers"
    share = device_op_time.read(ctx, reduce="share_of_busy_pct",
                                op="convolution_tanh_fusion")
    assert 50.0 < share < 100.0
    assert device_idle.read({"trace": None}) is None
