"""The launch scripts' device boundary: ``bench.py`` produces a device
measurement or nothing (no TPU, an unknown peak or a failed row all
raise — none returns a zero-valued record), and
``__graft_entry__.dryrun_multichip`` runs on the devices JAX reports or
fails, never re-pointing JAX at a CPU mesh by itself."""

import pytest

import bench


def test_bench_has_no_device_probe_or_child_process():
    assert not hasattr(bench, "probe_device")
    src = open(bench.__file__).read()
    assert "subprocess" not in src


def test_device_stamp_refuses_the_cpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        bench.device_stamp()
    with pytest.raises(RuntimeError, match="no TPU"):
        bench.main()


class _FakeTPU:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_unknown_device_kind_is_an_error_not_a_default_peak(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_FakeTPU("TPU v9")])
    with pytest.raises(RuntimeError, match="no peak on record.*TPU v9"):
        bench.device_stamp()
    monkeypatch.setattr(jax, "devices", lambda: [_FakeTPU("TPU v5 lite")])
    assert bench.device_stamp() == {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


@pytest.mark.parametrize("scope", ["llm", "full"])
def test_a_failed_row_fails_the_run(monkeypatch, scope):
    """main() raises — it does not return a record with an error field
    and a zero value — whichever row fails."""
    stamp = {"platform": "tpu", "device_kind": "TPU v5 lite",
             "device_count": 1}
    monkeypatch.setattr(bench, "device_stamp", lambda: stamp)
    monkeypatch.setenv("RDB_BENCH_SCOPE", scope)
    good_llm = {"tok_s_per_chip": 1.0, "ttft_p50_ms": 1.0,
                "ttft_p99_ms": 1.0}

    def boom(*a, **kw):
        raise RuntimeError("row failed")

    if scope == "llm":
        monkeypatch.setattr(bench, "bench_llm_serving", boom)
    else:
        monkeypatch.setattr(bench, "bench_llm_serving",
                            lambda **kw: dict(good_llm))
        monkeypatch.setattr(bench, "bench_vision_model", boom)
    with pytest.raises(RuntimeError, match="row failed"):
        bench.main()


def test_a_complete_run_is_stamped_with_its_device(monkeypatch):
    stamp = {"platform": "tpu", "device_kind": "TPU v5 lite",
             "device_count": 1}
    monkeypatch.setattr(bench, "device_stamp", lambda: stamp)
    monkeypatch.setenv("RDB_BENCH_SCOPE", "llm")
    monkeypatch.setattr(
        bench, "bench_llm_serving",
        lambda **kw: {"tok_s_per_chip": 3.0, "ttft_p50_ms": 1.0,
                      "ttft_p99_ms": 2.0})
    record = bench.main()
    assert {k: record[k] for k in stamp} == stamp
    assert record["value"] == 3.0
    assert record["llama3_8b"] == {"skipped": "llm scope"}


def test_dryrun_multichip_fails_rather_than_self_forcing_a_cpu_mesh(
        eight_devices):
    import inspect

    import __graft_entry__ as graft

    with pytest.raises(RuntimeError, match="JAX reports 8 cpu device"):
        graft.dryrun_multichip(16)
    src = inspect.getsource(graft)
    assert "subprocess" not in src and "clear_backends" not in src
