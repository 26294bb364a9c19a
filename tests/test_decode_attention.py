"""Pallas decode attention vs the XLA reference (interpret mode on CPU).

Same oracle strategy as test_flash_attention: the einsum attention in
ops.attention._xla_attention is the trusted reference; the fused Tq == 1
KV-scan kernel (VERDICT r4 #8) must match it bit-for-tolerance on every
decode shape the engine produces — MHA, GQA grouping, decode windows
(lengths masks), tail KV tiles — and the dispatch in
ops.attention.dot_product_attention must actually route decode steps to
it under the pallas backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy (fast lane excludes)

from ray_dynamic_batching_tpu.models.decoder import decode_mask
from ray_dynamic_batching_tpu.ops import decode_attention as da
from ray_dynamic_batching_tpu.ops.attention import (
    _xla_attention,
    dot_product_attention,
    set_attention_backend,
)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


def _check(q, k, v, *, mask=None, block_k=512, atol=2e-3):
    out = da.decode_attention(
        q, k, v, mask=mask, block_k=block_k, interpret=True
    )
    assert out is not None, "kernel declined a decode shape"
    ref = _xla_attention(q, k, v, causal=False, mask=mask, scale=None)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=atol, rtol=1e-3,
    )


def test_mha_matches_xla():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand((4, 1, 8, 32), ks[0])
    k = _rand((4, 64, 8, 32), ks[1])
    v = _rand((4, 64, 8, 32), ks[2])
    _check(q, k, v)


def test_gqa_grouping_matches_repeat_semantics():
    """Query head n must read kv head n // (N//K) — the exact mapping
    _xla_attention's jnp.repeat produces; distinct kv heads make any
    grouping mix-up a loud mismatch."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand((2, 1, 8, 16), ks[0])
    k = _rand((2, 96, 2, 16), ks[1])
    v = _rand((2, 96, 2, 16), ks[2])
    _check(q, k, v)


def test_decode_window_mask():
    """The engine's real mask: per-slot attend window [0, length]."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S = 4, 80
    q = _rand((B, 1, 4, 16), ks[0])
    k = _rand((B, S, 4, 16), ks[1])
    v = _rand((B, S, 4, 16), ks[2])
    lengths = jnp.asarray([0, 5, 41, S - 1])
    _check(q, k, v, mask=decode_mask(lengths, S))


def test_tail_kv_tiles():
    """Capacity not a multiple of block_k: the tail tile's out-of-range
    rows must not leak into the softmax."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand((2, 1, 2, 16), ks[0])
    k = _rand((2, 70, 2, 16), ks[1])
    v = _rand((2, 70, 2, 16), ks[2])
    lengths = jnp.asarray([69, 33])
    _check(q, k, v, mask=decode_mask(lengths, 70), block_k=32)


def test_multi_tile_scan_carry():
    """S split across multiple grid steps: the online-softmax state must
    carry through VMEM scratch across sequential S tiles (block_k=128
    forces a 4-tile scan at S=512) — including slots whose window ends
    mid-scan and a slot whose window is empty."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, S = 4, 512
    q = _rand((B, 1, 8, 32), ks[0])
    k = _rand((B, S, 4, 32), ks[1])
    v = _rand((B, S, 4, 32), ks[2])
    lengths = jnp.asarray([0, 100, 300, S - 1])
    _check(q, k, v, mask=decode_mask(lengths, S), block_k=128)


def test_multi_tile_no_mask():
    """Tiled scan without a mask (all positions attend)."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = _rand((2, 1, 4, 32), ks[0])
    k = _rand((2, 256, 4, 32), ks[1])
    v = _rand((2, 256, 4, 32), ks[2])
    _check(q, k, v, block_k=128)


def _quantize(x):
    from ray_dynamic_batching_tpu.models.kv_state import quantize_kv_rows

    return quantize_kv_rows(x)


def test_int8_codes_match_dequantized_oracle():
    """The kernel's in-dot scale application must equal dequantize-then-
    attend exactly (the scales factor out algebraically)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    B, S = 3, 96
    q = _rand((B, 1, 8, 32), ks[0])
    k = _rand((B, S, 4, 32), ks[1]) * 3.0
    v = _rand((B, S, 4, 32), ks[2]) * 3.0
    k8, kscale = _quantize(k)
    v8, vscale = _quantize(v)
    mask = decode_mask(jnp.asarray([10, 50, S - 1]), S)
    out = da.decode_attention(
        q, k8, v8, mask=mask, k_scale=kscale, v_scale=vscale,
        interpret=True,
    )
    assert out is not None, "int8 path declined"
    from ray_dynamic_batching_tpu.models.kv_state import dequantize_kv

    ref = _xla_attention(
        q, dequantize_kv(k8, kscale, q.dtype),
        dequantize_kv(v8, vscale, q.dtype),
        causal=False, mask=mask, scale=None,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-3, rtol=1e-3,
    )


def test_int8_multi_tile_spec_window():
    """Int8 scan across multiple S tiles with a speculative staircase
    window — scales must track their tiles."""
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    B, S, Tq = 2, 256, 4
    q = _rand((B, Tq, 8, 32), ks[0])
    k = _rand((B, S, 8, 32), ks[1]) * 2.0
    v = _rand((B, S, 8, 32), ks[2]) * 2.0
    k8, kscale = _quantize(k)
    v8, vscale = _quantize(v)
    base = jnp.asarray([30, 200])
    pos = jnp.arange(S)[None, None, None, :]
    row = jnp.arange(Tq)[None, None, :, None]
    mask = pos < (base[:, None, None, None] + row + 1)
    out = da.decode_attention(
        q, k8, v8, mask=mask, k_scale=kscale, v_scale=vscale,
        block_k=128, interpret=True,
    )
    assert out is not None
    from ray_dynamic_batching_tpu.models.kv_state import dequantize_kv

    ref = _xla_attention(
        q, dequantize_kv(k8, kscale, q.dtype),
        dequantize_kv(v8, vscale, q.dtype),
        causal=False, mask=mask, scale=None,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-3, rtol=1e-3,
    )


def test_int8_dispatch_reaches_kernel_and_matches(monkeypatch):
    """dot_product_attention with scales must route codes to the kernel
    under the pallas backend (no dequant materialization) and still
    match the dequantized oracle."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    B, S = 2, 48
    q = _rand((B, 1, 4, 16), ks[0])
    k = _rand((B, S, 4, 16), ks[1])
    v = _rand((B, S, 4, 16), ks[2])
    k8, kscale = _quantize(k)
    v8, vscale = _quantize(v)
    mask = decode_mask(jnp.asarray([10, 47]), S)
    calls = []
    real = da.decode_attention

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        calls.append(kwargs.get("k_scale") is not None and out is not None)
        return out

    monkeypatch.setattr(da, "decode_attention", spy)
    set_attention_backend("pallas")
    try:
        out = dot_product_attention(
            q, k8, v8, mask=mask, k_scale=kscale, v_scale=vscale
        )
    finally:
        set_attention_backend("auto")
    assert calls == [True], "int8 decode did not engage the kernel"
    from ray_dynamic_batching_tpu.models.kv_state import dequantize_kv

    ref = _xla_attention(
        q, dequantize_kv(k8, kscale, q.dtype),
        dequantize_kv(v8, vscale, q.dtype),
        causal=False, mask=mask, scale=None,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-3, rtol=1e-3,
    )


def test_bf16_inputs():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand((2, 1, 4, 32), ks[0], jnp.bfloat16)
    k = _rand((2, 64, 4, 32), ks[1], jnp.bfloat16)
    v = _rand((2, 64, 4, 32), ks[2], jnp.bfloat16)
    _check(q, k, v, atol=2e-2)


def test_spec_verify_window_per_row_masks():
    """The speculative-verify shape: Tq = k+1 window per row, each row's
    mask a staircase from its own base length (causal_lm.verify_step) —
    including an INACTIVE row steered fully out of bounds (all-masked
    rows must emit zeros, not NaN)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, Tq, S = 3, 5, 64
    q = _rand((B, Tq, 4, 16), ks[0])
    k = _rand((B, S, 2, 16), ks[1])
    v = _rand((B, S, 2, 16), ks[2])
    base = jnp.asarray([0, 20, S])  # row 2: inactive, everything masked
    positions = base[:, None] + jnp.arange(Tq)[None, :]
    s_idx = jnp.arange(S)[None, None, None, :]
    mask = s_idx <= jnp.where(
        positions < S, positions, -1
    )[:, None, :, None]
    out = da.decode_attention(q, k, v, mask=mask, interpret=True)
    assert out is not None
    ref = _xla_attention(q, k, v, causal=False, mask=mask, scale=None)
    # All rows match the oracle — including the fully-masked one, where
    # the finite -1e30 sentinel makes both sides compute uniform
    # attention (whose output is never consumed for inactive rows).
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-3, rtol=1e-3,
    )
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_window_boundary_sizes():
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    k = _rand((2, 48, 2, 16), ks[1])
    v = _rand((2, 48, 2, 16), ks[2])
    mask = decode_mask(jnp.asarray([30, 47]), 48)
    q8 = _rand((2, 8, 4, 16), ks[0])
    _check(q8, k, v, mask=mask)  # Tq == MAX_WINDOW_FOR_KERNEL
    q9 = _rand((2, 9, 4, 16), ks[0])
    assert da.decode_attention(q9, k, v, mask=mask,
                               interpret=True) is None


def test_declines_non_decode_shapes():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand((2, 12, 4, 16), ks[0])  # window too wide: flash/XLA's job
    k = _rand((2, 64, 4, 16), ks[1])
    v = _rand((2, 64, 4, 16), ks[2])
    assert da.decode_attention(q, k, v, interpret=True) is None


def test_dispatch_routes_decode_to_kernel(monkeypatch):
    """Under the pallas backend a Tq == 1 call must reach the decode
    kernel (and still match the XLA oracle end to end)."""
    calls = []
    real = da.decode_attention

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        calls.append(out is not None)
        return out

    monkeypatch.setattr(da, "decode_attention", spy)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = _rand((2, 1, 4, 16), ks[0])
    k = _rand((2, 48, 4, 16), ks[1])
    v = _rand((2, 48, 4, 16), ks[2])
    mask = decode_mask(jnp.asarray([10, 47]), 48)
    set_attention_backend("pallas")
    try:
        out = dot_product_attention(q, k, v, mask=mask)
    finally:
        set_attention_backend("auto")
    assert calls == [True], "decode step did not route through the kernel"
    ref = _xla_attention(q, k, v, causal=False, mask=mask, scale=None)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-3,
    )
