"""The dispatcher's observable contract (ops/attention.py): every
dispatch leaves a trace-time record of the path it took and why each
kernel before it declined; the ``"pallas"`` backend is strict; interpret
mode can never be asked for on a TPU backend."""

import jax
import jax.numpy as jnp
import pytest

from ray_dynamic_batching_tpu.ops import attention, pallas_common
from ray_dynamic_batching_tpu.ops import decode_attention as da
from ray_dynamic_batching_tpu.ops import flash_attention as fa


@pytest.fixture
def pallas():
    attention.clear_attention_paths()
    attention.set_attention_backend("pallas")
    try:
        yield
    finally:
        attention.set_attention_backend("auto")
        attention.clear_attention_paths()


def _qkv(Tq, S=128, N=4, K=2, H=16):
    q = jnp.ones((2, Tq, N, H), jnp.bfloat16)
    kv = jnp.ones((2, S, K, H), jnp.bfloat16)
    return q, kv


def test_strict_pallas_raises_on_a_decline_and_names_the_reasons(pallas):
    # A 12-row window is in neither kernel's band: too wide for the
    # KV-scan kernel (<= 8), too narrow for the query-tiled one (>= 16).
    q, kv = _qkv(12)
    with pytest.raises(attention.AttentionDeclined) as err:
        attention.dot_product_attention(q, kv, kv)
    msg = str(err.value)
    assert "decode kernel: window Tq=12 > 8" in msg
    assert "flash kernel: Tq=12 < 16" in msg
    assert attention.attention_paths() == []  # nothing ran


def test_auto_backend_records_the_same_decline_and_runs_the_reference():
    attention.clear_attention_paths()
    q, kv = _qkv(12)
    out = attention.dot_product_attention(q, kv, kv)
    assert out.shape == q.shape
    (record,) = attention.attention_paths()
    assert record.path == attention.PATH_XLA
    assert record.describe() == "XLA einsum"
    assert record.declines == ("pallas off: backend 'auto' on cpu",)
    attention.clear_attention_paths()


def test_kernel_paths_are_recorded_with_the_declines_before_them(pallas):
    q, kv = _qkv(1)
    attention.dot_product_attention(q, kv, kv)
    q16, _ = _qkv(16)
    attention.dot_product_attention(q16, kv, kv)
    slab, flash = attention.attention_paths()
    assert (slab.path, slab.declines) == (attention.PATH_SLAB_KERNEL, ())
    assert slab.interpret  # off-TPU a forced kernel is interpreted
    assert flash.path == attention.PATH_FLASH
    assert flash.declines == (
        "decode kernel: window Tq=16 > 8 is prefill-shaped",)
    assert (flash.q_shape, flash.kv_shape) == (q16.shape, kv.shape)


def test_paged_read_records_kernel_or_gather(pallas):
    P, ps = 6, 128
    pool = jnp.ones((P, ps, 2, 16), jnp.bfloat16)
    table = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lengths = jnp.asarray([130, 5], jnp.int32)
    q, _ = _qkv(1)
    attention.dot_product_attention(
        q, pool, pool, page_table=table, kv_lengths=lengths)
    q16, _ = _qkv(16)
    attention.dot_product_attention(
        q16, pool, pool, page_table=table, kv_lengths=lengths)
    kernel, gathered = attention.attention_paths()
    assert kernel.describe() == "paged kernel"
    assert gathered.describe() == "gather-then-flash kernel"
    assert gathered.declines[0].startswith("paged kernel: window Tq=16")


def test_wrappers_say_why_they_decline():
    why = []
    q, kv = _qkv(12)
    assert da.decode_attention(q, kv, kv, why=why) is None
    assert fa.flash_attention(q, kv, kv, why=why) is None
    pool = jnp.ones((4, 100, 2, 16), jnp.bfloat16)  # page 100: unaligned
    assert da.paged_decode_attention(
        q[:, :1], pool, pool, jnp.zeros((2, 2), jnp.int32),
        jnp.zeros((2,), jnp.int32), why=why) is None
    assert len(why) == 3
    assert "page size 100 is not a 128-lane multiple" in why[2]


def test_interpret_mode_is_refused_on_a_tpu_backend(monkeypatch):
    assert pallas_common.resolve_interpret(None) is True   # cpu: stand-in
    assert pallas_common.resolve_interpret(False) is False  # jax.export
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_common.resolve_interpret(None) is False
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        pallas_common.resolve_interpret(True)
    q, kv = _qkv(1)
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        da.decode_attention(q, kv, kv, interpret=True)
