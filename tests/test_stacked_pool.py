"""The KV pool is read where it lies (ISSUE 25, tier-1).

The stacked ``[L, P, ps, K, H]`` pool goes to the paged read WHOLE and the
layer is an index — in the Pallas kernel's block map, or in the gather
fallback's one (layer, page) gather — so a wrong index map, or a read that
forgets the layer, must fail here: every layer of the test pools holds
different values.

Kernels run on the CPU in interpret mode; the geometries are the two
configurations' head shapes (MHA 16x64, one query row a kv head; GQA
32/8 x 128, four rows) at a small pool.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.decoder import paged_window_mask
from ray_dynamic_batching_tpu.models.kv_state import (
    dequantize_kv,
    fit_head_dim,
    pool_head_dim,
)
from ray_dynamic_batching_tpu.ops import attention
from ray_dynamic_batching_tpu.ops import decode_attention as da

L, P, PS, NP, B = 3, 5, 128, 2, 2
GEOMETRIES = {"mha16x64": (16, 16, 64), "gqa32_8x128": (32, 8, 128)}
# The int8 pool's values reach +-12.7 (codes x scales) and the fallback
# dequantizes them into bf16: its rounding alone is ~0.05 there.
ATOL = {"bf16": 3e-2, "int8": 0.15}


def _pool(geometry, window, dtype, seed=0):
    """q, stacked k/v (every layer different; rows lane-padded with
    zeros as the engine's pool has them), per-layer scale planes
    [L, P, ps, K] (None for bf16), page table, lengths."""
    N, K, H = GEOMETRIES[geometry]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, window, N, H)), jnp.bfloat16)
    shape = (L, P, PS, K, H)
    Hp = pool_head_dim(H)
    if dtype == "int8":
        k = jnp.asarray(rng.integers(-127, 127, shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 127, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1]), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1]), jnp.float32)
    else:
        k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        ks = vs = None
    # Slot 1 has one allocated page (sentinel tail); slot 0 sits near a
    # page boundary so a spec window's staircase crosses it.
    pt = jnp.asarray([[3, 1], [4, P]], jnp.int32)
    lens = jnp.asarray([126, 37], jnp.int32)
    return q, fit_head_dim(k, Hp), fit_head_dim(v, Hp), ks, vs, pt, lens


def _reference(q, k, v, ks, vs, pt, lens, layer):
    """Plain XLA on ONE layer's pool, sliced out by hand (and cut back
    from the pool's row width to the head)."""
    H = q.shape[-1]
    k, v = k[layer, ..., :H], v[layer, ..., :H]
    safe = jnp.minimum(pt, P - 1)

    def logical(pages):
        return pages[safe].reshape((B, NP * PS) + pages.shape[2:])

    kg, vg = logical(k), logical(v)
    if ks is not None:
        kg = dequantize_kv(kg, logical(ks[layer]), jnp.float32)
        vg = dequantize_kv(vg, logical(vs[layer]), jnp.float32)
    return attention._xla_attention(
        q.astype(jnp.float32), kg.astype(jnp.float32),
        vg.astype(jnp.float32), causal=False,
        mask=paged_window_mask(lens, NP * PS, q.shape[1]), scale=None)


def CASES(test):
    """Layer 0 and the last x both head shapes x plain decode and a spec
    window x the bf16 and the int8 pool."""
    for name, values in (("layer", [0, L - 1]),
                         ("geometry", sorted(GEOMETRIES)),
                         ("window", [1, 4]), ("dtype", ["bf16", "int8"])):
        test = pytest.mark.parametrize(name, values)(test)
    return test


@CASES
def test_kernel_reads_the_named_layer_of_the_stack(layer, geometry, window,
                                                   dtype):
    q, k, v, ks, vs, pt, lens = _pool(geometry, window, dtype)
    scales = {} if ks is None else {
        "k_scale": ks[layer], "v_scale": vs[layer]}
    out = da.paged_decode_attention(
        q, k, v, pt, lens, layer=layer, interpret=True, **scales)
    assert out is not None
    ref = _reference(q, k, v, ks, vs, pt, lens, layer)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=ATOL[dtype],
        rtol=3e-2)
    # A neighbouring layer's values are NOT within that tolerance: the
    # comparison above can tell layers apart.
    other = _reference(q, k, v, ks, vs, pt, lens, (layer + 1) % L)
    assert np.abs(np.asarray(ref) - np.asarray(other)).max() > 0.5


@CASES
def test_gather_fallback_matches_the_kernel(layer, geometry, window, dtype):
    """The dispatcher's two paged reads — the kernel, and the fallback's
    one (layer, page) gather — on the same stacked pool."""
    q, k, v, ks, vs, pt, lens = _pool(geometry, window, dtype)
    scales = {} if ks is None else {
        "k_scale": ks[layer], "v_scale": vs[layer]}
    outs = {}
    try:
        for backend in ("pallas", "xla"):
            attention.set_attention_backend(backend)
            attention.clear_attention_paths()
            outs[backend] = attention.dot_product_attention(
                q, k, v, page_table=pt, kv_lengths=lens, layer=layer,
                **scales)
            (path,) = attention.attention_paths()
            assert path.gathered == (backend == "xla")
            assert path.stacked == (backend == "pallas")
    finally:
        attention.set_attention_backend("auto")
        attention.clear_attention_paths()
    np.testing.assert_allclose(
        np.asarray(outs["pallas"], np.float32),
        np.asarray(outs["xla"], np.float32), atol=ATOL[dtype], rtol=3e-2)


def test_one_layer_pool_is_a_one_layer_stack():
    """A caller that holds a single layer's [P, ps, K, H] pool gets the
    same kernel: the pool viewed as a stack of one, layer 0 — whether its
    rows are as wide as the head or lane-padded."""
    q, k, v, _, _, pt, lens = _pool("mha16x64", 1, "bf16")
    stacked = da.paged_decode_attention(
        q, k, v, pt, lens, layer=1, interpret=True)
    for width in (64, 128):
        flat = da.paged_decode_attention(
            q, k[1, ..., :width], v[1, ..., :width], pt, lens,
            interpret=True)
        np.testing.assert_allclose(
            np.asarray(flat, np.float32), np.asarray(stacked, np.float32),
            atol=1e-6)
    with pytest.raises(ValueError, match="not in a stack of 3"):
        da.paged_decode_attention(q, k, v, pt, lens, layer=L, interpret=True)


def test_paths_say_stacked():
    q, k, v, _, _, pt, lens = _pool("mha16x64", 1, "bf16")
    try:
        attention.set_attention_backend("pallas")
        attention.clear_attention_paths()
        attention.dot_product_attention(
            q, k, v, page_table=pt, kv_lengths=lens, layer=2)
        attention.dot_product_attention(
            q, k[2], v[2], page_table=pt, kv_lengths=lens)
        stacked, flat = attention.attention_paths()
    finally:
        attention.set_attention_backend("auto")
        attention.clear_attention_paths()
    assert stacked.describe() == "paged kernel (stacked pool)"
    assert stacked.kv_shape == k.shape
    assert flat.describe() == "paged kernel"


# --- what the engine says about its pool -------------------------------------
def test_snapshot_says_how_the_pool_lies_on_the_device():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    engine = DecodeEngine(
        model, params, RequestQueue(model.name, max_len=64), num_slots=2,
        max_len=64, prompt_buckets=[8], eos_token_id=None, paged=True,
        page_size=128,
    )
    pool = engine.snapshot()["kv_pool"]
    # Row-major, the device's default for lane-padded rows (llama_tiny's
    # 16-wide head sits in 128 lanes); no padding beyond the shape's own.
    assert engine._cache.k.shape[-1] == pool_head_dim(16) == 128
    assert pool["layout"] == [0, 1, 2, 3, 4]
    assert pool["resident_bytes"] == (
        engine._cache.k.nbytes + engine._cache.v.nbytes)
