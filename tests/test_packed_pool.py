"""Heads narrower than the 128 lanes lie side by side in a row of the paged
pool (ISSUE 48, tier-1): ``[L, P, ps, K // f, 128]`` where ``f = 128 //
head_dim`` whole heads pair off (``models/kv_state.py::pool_heads_per_row``,
the ONE rule), the parent's lane-padded ``[L, P, ps, K, 128]`` everywhere
else.

- the paged kernel over such a pool (CPU, interpret mode) gives the gather
  fallback's values, plain float32 attention's, and BIT FOR BIT those of the
  parent's kernel over the lane-padded pool: to the kernel a packed pool is
  a GQA pool of ``K // f`` heads x 128, and zeros are inert;
- nothing of a row's OTHER head reaches an output;
- the paged write (a decode step's, a chunk's scatter across a page edge),
  ``_read_pages`` / ``_write_pages`` and the gather keep ``[.., K, head_dim]``
  bit for bit;
- an engine on a tiny 64-wide-head model serves the reference's tokens
  (``tests/decode_reference.py``), greedy and seeded rows, chunked prompts
  and the spec window alike, and its launches upload what an unpacked
  engine's do;
- the rule's refusals, by shape, build the parent's pool.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.causal_lm import (
    GPT2_MEDIUM,
    LLAMA3_8B,
    TINY_LM,
    CausalLM,
)
from ray_dynamic_batching_tpu.models.decoder import (
    DecoderConfig,
    paged_window_mask,
)
from ray_dynamic_batching_tpu.models.kv_state import (
    fit_head_dim,
    from_pool_rows,
    pool_heads_per_row,
    to_pool_rows,
)
from ray_dynamic_batching_tpu.ops import attention
from ray_dynamic_batching_tpu.ops import decode_attention as da
from tests.decode_reference import assert_served

L, P, PS, NP, B = 2, 5, 128, 3, 2
# name -> (query heads, KV heads, head width): MHA as gpt2-medium's, GQA
# with two query rows a KV head, and four heads a row.
GEOMETRIES = {"mha16x64": (16, 16, 64), "gqa8_4x64": (8, 4, 64),
              "mha4x32": (4, 4, 32)}
# slot 0's cached positions before the window's first row
LENGTHS = {"empty": 0, "mid_page": 70, "a_pages_last": PS - 1,
           "a_pages_first": PS, "whole_table": None}
LAYER = 1


def _pools(geometry, window, length, seed=0):
    """q; k and v as ``[L, P, ps, K, H]`` (every layer different), as the
    packed pool has them and as the parent's lane-padded pool has them; the
    page table and lengths. Slot 0 holds the case's length over a full
    table, slot 1 one allocated page (a sentinel tail)."""
    N, K, H = GEOMETRIES[geometry]
    f = pool_heads_per_row(H, K, jnp.bfloat16)
    assert f == 128 // H
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, window, N, H)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((L, P, PS, K, H)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((L, P, PS, K, H)), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((L, P, PS, K // f, 128), jnp.bfloat16)
    packed = (to_pool_rows(k, pool), to_pool_rows(v, pool))
    padded = (fit_head_dim(k, 128), fit_head_dim(v, 128))
    pt = jnp.asarray([[3, 1, 2], [4, P, P]], jnp.int32)
    if length is None:              # the window's last row at the last position
        length = NP * PS - window
    lens = jnp.asarray([length, 37], jnp.int32)
    return q, k, v, packed, padded, pt, lens, f


def _plain(q, k, v, pt, lens, sliding):
    """Plain float32 attention over the gathered ``[B, S, K, H]`` run."""
    safe = jnp.minimum(pt, P - 1)
    run = lambda pool: pool[LAYER][safe].reshape(  # noqa: E731
        (B, NP * PS) + pool.shape[3:]).astype(jnp.float32)
    return attention._xla_attention(
        q.astype(jnp.float32), run(k), run(v), causal=False,
        mask=paged_window_mask(lens, NP * PS, q.shape[1], sliding),
        scale=None)


def _f32(x):
    return np.asarray(x, np.float32)


# --- the kernel over a packed pool -------------------------------------------
@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("sliding", [0, PS], ids=["full", "sliding"])
@pytest.mark.parametrize("window", [1, 5])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_kernel_over_a_packed_pool_is_the_fallbacks_and_plain_attentions(
        geometry, window, sliding, length):
    q, k, v, packed, padded, pt, lens, f = _pools(
        geometry, window, LENGTHS[length])
    outs = {}
    try:
        for backend in ("pallas", "xla"):
            attention.set_attention_backend(backend)
            attention.clear_attention_paths()
            outs[backend] = attention.dot_product_attention(
                q, *packed, page_table=pt, kv_lengths=lens, layer=LAYER,
                sliding=sliding, heads_per_row=f)
            (path,) = attention.attention_paths()
            assert path.gathered == (backend == "xla")
            if backend == "pallas":
                assert path.heads_per_row == f
                assert f"{f} heads a row" in path.describe()
    finally:
        attention.set_attention_backend("auto")
        attention.clear_attention_paths()
    assert outs["pallas"].shape == q.shape
    # the gather gave the slab view's own bytes back: the fallback over the
    # packed pool IS the fallback over the parent's pool
    np.testing.assert_allclose(_f32(outs["pallas"]), _f32(outs["xla"]),
                               atol=3e-2, rtol=3e-2)
    np.testing.assert_allclose(
        _f32(outs["pallas"]), _f32(_plain(q, k, v, pt, lens, sliding)),
        atol=3e-2, rtol=3e-2)
    # ... and the parent's kernel over its lane-padded pool, bit for bit:
    # the same products in the same order, and zeros elsewhere.
    parent = da.paged_decode_attention(
        q, *padded, pt, lens, layer=LAYER, interpret=True, sliding=sliding)
    assert np.array_equal(_f32(outs["pallas"]), _f32(parent))


@pytest.mark.parametrize("window", [1, 5])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_nothing_of_a_rows_other_head_reaches_an_output(geometry, window):
    """KV head 1 lies in row 0 beside head 0: large finite values there,
    then zeros, leave every OTHER head's output bit-equal, and move its
    own."""
    N, K, H = GEOMETRIES[geometry]
    q, k, v, packed, _, pt, lens, f = _pools(geometry, window, 300)
    G = N // K
    own = np.arange(N) // G == 1       # query heads of KV head 1

    def kernel(k_, v_):
        pool = jax.ShapeDtypeStruct(packed[0].shape, jnp.bfloat16)
        return _f32(da.paged_decode_attention(
            q, to_pool_rows(k_, pool), to_pool_rows(v_, pool), pt, lens,
            layer=LAYER, interpret=True, heads_per_row=f))

    base = kernel(k, v)
    for value in (3.0e4, 0.0):
        out = kernel(k.at[:, :, :, 1].set(value), v.at[:, :, :, 1].set(value))
        assert np.isfinite(out).all()
        assert np.array_equal(out[:, :, ~own], base[:, :, ~own])
        assert not np.array_equal(out[:, :, own], base[:, :, own])


def test_the_kernel_declines_rows_that_are_not_f_heads_wide():
    q, _, _, packed, padded, pt, lens, f = _pools("mha16x64", 1, 70)
    why = []
    assert da.paged_decode_attention(
        q, *padded, pt, lens, layer=LAYER, interpret=True, heads_per_row=4,
        why=why) is None
    assert "4 heads a pool row" in why[0]
    with pytest.raises(ValueError, match="several heads a row"):
        attention.dot_product_attention(
            q, *packed, page_table=pt, kv_lengths=lens, layer=LAYER,
            heads_per_row=f, v_dim=64)
    with pytest.raises(ValueError, match="paged read's"):
        attention.dot_product_attention(q[:, :, :, :], q, q, heads_per_row=2)


def test_the_path_record_says_heads_a_row():
    q, _, _, packed, _, pt, lens, f = _pools("mha16x64", 1, 70)
    da.clear_decode_paths()
    da.paged_decode_attention(q, *packed, pt, lens, layer=LAYER,
                              interpret=True, heads_per_row=f)
    (path,) = da.decode_paths()
    da.clear_decode_paths()
    # 16 x 64 walked as 8 x 128: ONE head block of 8, two rows a pool head
    assert (path.heads_per_row, path.kb, path.rows, path.head_dim) == (
        2, 8, 2, 128)
    assert path.form == da.FORM_FLAT
    assert path.describe().startswith("2 heads a pool row: 8 heads x 2 rows")


# --- the rule ----------------------------------------------------------------
def _pool_shape(cfg, dtype=jnp.bfloat16, tp=1):
    model = CausalLM(cfg, name="shape", dtype=jnp.bfloat16,
                     kv_dtype=None if dtype == jnp.bfloat16 else dtype)
    cache = jax.eval_shape(
        lambda: model.make_paged_cache(2, 4, 128, 256, tp=tp))
    assert cache.k.shape == cache.v.shape
    return cache.k.shape


def _cfg(heads, kv_heads, head_dim, **kw):
    return DecoderConfig(**{**dict(
        vocab_size=64, d_model=heads * head_dim, num_layers=2,
        num_heads=heads, num_kv_heads=kv_heads, mlp_dim=64,
        max_seq_len=256), **kw})


def test_gpt2_medium_packs_two_heads_a_row():
    assert _pool_shape(GPT2_MEDIUM) == (24, 4, 128, 8, 128)
    # half the parent's bytes: 128 pages of 128 positions are 1.61 GB
    model = CausalLM(GPT2_MEDIUM, name="g", dtype=jnp.bfloat16)
    cache = jax.eval_shape(
        lambda: model.make_paged_cache(16, 128, 128, 1024))
    nbytes = 2 * np.prod(cache.k.shape) * 2
    assert nbytes == 128 * model.kv_bytes_per_slot(128) == 1_610_612_736


@pytest.mark.parametrize("case, cfg, kwargs, shape", [
    ("f4", _cfg(4, 4, 32), {}, (2, 4, 128, 1, 128)),
    ("gqa", _cfg(8, 4, 64), {}, (2, 4, 128, 2, 128)),
    ("tp_divides", _cfg(8, 8, 64), {"tp": 2}, (2, 4, 128, 4, 128)),
    # the refusals: the parent's pool, a head a row, lane-padded
    ("heads_do_not_pair_off", _cfg(6, 3, 64), {}, (2, 4, 128, 3, 128)),
    ("llama_tiny_2_of_8", TINY_LM, {}, (2, 4, 128, 2, 128)),
    ("one_head", _cfg(4, 1, 64), {}, (2, 4, 128, 1, 128)),
    ("int8", _cfg(4, 4, 64), {"dtype": jnp.int8}, (2, 4, 128, 4, 128)),
    ("tp_does_not_divide_the_rows", _cfg(4, 4, 64), {"tp": 4},
     (2, 4, 128, 4, 128)),
    ("a_head_fills_the_lanes", LLAMA3_8B, {}, (32, 4, 128, 8, 128)),
    ("a_head_wider_than_the_lanes", _cfg(2, 2, 256), {},
     (2, 4, 128, 2, 256)),
    ("a_width_that_does_not_divide_the_lanes", _cfg(4, 4, 48), {},
     (2, 4, 128, 4, 128)),
    ("an_indexer", _cfg(4, 4, 64, index_topk=8, index_heads=2,
                        index_head_dim=64), {}, (2, 4, 128, 4, 128)),
])
def test_the_rule_by_shape(case, cfg, kwargs, shape):
    assert _pool_shape(cfg, **kwargs) == shape
    f = cfg.num_kv_heads // shape[3]
    assert f == pool_heads_per_row(
        cfg.head_dim, cfg.num_kv_heads, kwargs.get("dtype", jnp.bfloat16),
        kwargs.get("tp", 1), indexed=bool(cfg.index_topk))
    assert shape[4] == max(128, f * cfg.head_dim)


def test_an_int8_pool_keeps_its_scale_planes_a_head():
    model = CausalLM(_cfg(4, 4, 64), name="q", dtype=jnp.bfloat16,
                     kv_dtype=jnp.int8)
    cache = jax.eval_shape(lambda: model.make_paged_cache(2, 4, 128, 256))
    assert cache.k_scale.shape == cache.k.shape[:-1] == (2, 4, 128, 4)


def test_rows_round_trip_bit_for_bit():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((3, 7, 16, 64)), jnp.bfloat16)
    packed = jax.ShapeDtypeStruct((1, 1, 1, 8, 128), jnp.bfloat16)
    padded = jax.ShapeDtypeStruct((1, 1, 1, 16, 128), jnp.bfloat16)
    rows = to_pool_rows(x, packed)
    assert rows.shape == (3, 7, 8, 128)
    # row r holds heads 2r and 2r + 1 side by side
    assert np.array_equal(_f32(rows[..., 3, :64]), _f32(x[..., 6, :]))
    assert np.array_equal(_f32(rows[..., 3, 64:]), _f32(x[..., 7, :]))
    for pool in (packed, padded):
        back = from_pool_rows(to_pool_rows(x, pool), 16, 64)
        assert back.shape == x.shape
        assert np.array_equal(_f32(back), _f32(x))
    # ... a numpy array (the spill, a parcel) as well as a traced one
    assert np.array_equal(
        from_pool_rows(np.asarray(rows, np.float32), 16, 64), _f32(x))


# --- the model's writes and reads --------------------------------------------
# GQA 4 / 2 x 64: ONE pool row of two heads
WIDE = _cfg(4, 2, 64, max_seq_len=512, vocab_size=512)


@pytest.fixture(scope="module")
def lm():
    model = CausalLM(WIDE, name="wide_tiny", dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


def _padded_twin(cache):
    """The parent's pool for the same model: a head a row, lane-padded."""
    zeros = jnp.zeros(cache.k.shape[:3] + (WIDE.num_kv_heads, 128),
                      cache.k.dtype)
    return cache.replace(k=zeros, v=zeros)


def test_a_chunks_scatter_then_decode_steps_across_a_page_edge(lm):
    """A 16-row chunk at positions 120-135 (a page's edge inside it), then
    three decode steps: the packed pool and the parent's hold the same
    ``[K, head_dim]`` blocks bit for bit, the logits are equal, and they
    are the whole forward's."""
    model, params = lm
    W, start, steps = 16, 120, 3
    rng = np.random.default_rng(2)
    seq = rng.integers(1, 500, start + W + steps).astype(np.int32)
    packed = model.make_paged_cache(1, 4, PS, 3 * PS)
    assert packed.k.shape == (2, 4, PS, 1, 128)
    table = jnp.asarray([[2, 0, 3]], jnp.int32)
    packed = packed.replace(page_table=table)
    caches = {"packed": packed, "padded": _padded_twin(packed)}
    chunk = jax.jit(model.prefill_chunk_paged)
    step = jax.jit(model.decode_step_paged)
    logits = {}
    for name, cache in caches.items():
        # the prefix, a chunk of its own, then the chunk across the edge
        for s in range(0, start, 8):
            _, cache = chunk(
                params, jnp.asarray(seq[None, s:s + 8]),
                jnp.ones((1, 8), jnp.int32), cache, table,
                jnp.asarray([s], jnp.int32), jnp.asarray([7], jnp.int32))
        out, cache = chunk(
            params, jnp.asarray(seq[None, start:start + W]),
            jnp.ones((1, W), jnp.int32), cache, table,
            jnp.asarray([start], jnp.int32), jnp.asarray([W - 1], jnp.int32))
        rows = [out[0]]
        cache = cache.replace(lengths=jnp.asarray([start + W], jnp.int32))
        for i in range(steps):
            out, cache = step(
                params, jnp.asarray(seq[None, start + W + i:][:, :1]), cache,
                jnp.ones((1,), bool))
            rows.append(out[0])
        logits[name], caches[name] = np.stack(rows), cache
    assert int(caches["packed"].lengths[0]) == start + W + steps
    for plane in ("k", "v"):
        got = from_pool_rows(getattr(caches["packed"], plane), 2, 64)
        want = from_pool_rows(getattr(caches["padded"], plane), 2, 64)
        assert got.shape == (2, 4, PS, 2, 64)
        assert np.array_equal(_f32(got), _f32(want))
        # both pages of the edge were written, the unallocated one never
        written = np.abs(_f32(got)).sum(axis=(0, 2, 3, 4))
        assert written[2] > 0 and written[0] > 0 and written[1] == 0
    np.testing.assert_allclose(logits["packed"], logits["padded"],
                               atol=1e-5, rtol=1e-5)
    n = start + W + steps
    tokens = np.zeros((1, 160), np.int32)
    tokens[0, :n] = seq
    whole = model.apply(params, jnp.asarray(tokens),
                        jnp.asarray((np.arange(160) < n)[None], jnp.int32))
    np.testing.assert_allclose(
        logits["packed"], _f32(whole[0, start + W - 1:n]), atol=2e-4,
        rtol=2e-4)


def _engine(lm, **kw):
    model, params = lm
    queue = RequestQueue(model.name, max_len=256)
    opts = dict(num_slots=4, max_len=256, prompt_buckets=[8, 16],
                eos_token_id=None, default_max_new_tokens=6,
                decode_horizon=2, page_size=PS, kv_pool_pages=8,
                max_admissions_per_step=2, prefill_token_budget=64)
    opts.update(kw)
    return DecodeEngine(model, params, queue, **opts), queue


def test_read_pages_and_write_pages_keep_the_parcels_form(lm):
    engine, _ = _engine(lm)
    assert engine._cache.k.shape == (2, 8, PS, 1, 128)
    rng = np.random.default_rng(4)
    payload = {name: rng.standard_normal((2, 3, PS, 2, 64)).astype(np.float32)
               for name in ("k", "v")}
    engine._write_pages([5, 1, 6], payload)
    back = engine._read_pages([5, 1, 6])
    for name in ("k", "v"):
        assert back[name].shape == (2, 3, PS, 2, 64)     # [.., K, head_dim]
        assert np.array_equal(back[name], payload[name])
    # another order, and a page never written
    again = engine._read_pages([1, 0])
    assert np.array_equal(again["k"][:, 0], payload["k"][:, 1])
    assert not again["v"][:, 1].any()
    # the pool's own bytes: row 0 of a position is head 0 then head 1
    k = np.asarray(engine._cache.k)
    assert np.array_equal(k[:, 5, :, 0, :64], payload["k"][:, 0, :, 0])
    assert np.array_equal(k[:, 5, :, 0, 64:], payload["k"][:, 0, :, 1])


def test_snapshot_says_heads_a_row_with_the_pools_shape_and_bytes(lm):
    engine, _ = _engine(lm)
    pool = engine.snapshot()["kv_pool"]
    assert pool["heads_per_row"] == 2
    assert pool["pool_shape"] == [2, 8, PS, 1, 128]
    assert pool["resident_bytes"] == 2 * 2 * 8 * PS * 128 * 4
    assert pool["resident_bytes"] == 8 * lm[0].kv_bytes_per_slot(PS)
    tiny = get_model("llama_tiny", dtype=jnp.float32)
    unpacked, _ = _engine((tiny, tiny.init(jax.random.PRNGKey(0))))
    pool = unpacked.snapshot()["kv_pool"]
    assert pool["heads_per_row"] == 1
    assert pool["pool_shape"] == [2, 8, PS, 2, 128]


# --- served tokens -----------------------------------------------------------
def _workload(queue, model_name, sampled=True, seed=7, n=6):
    """Greedy and (``sampled``) seeded sampled rows; prompts of one chunk
    and of several (the widest bucket is 16)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        payload = {"tokens": rng.integers(1, 500, (5, 40, 13, 70, 9, 33)[i]
                                          ).tolist(),
                   "max_new_tokens": int(rng.integers(4, 10))}
        if sampled and i % 2:
            payload.update(temperature=0.8, top_k=20, seed=100 + i)
        req = Request(model=model_name, payload=payload, slo_ms=60_000.0)
        queue.add_request(req)
        reqs.append(req)
    return reqs


def _serve(lm, **kw):
    engine, queue = _engine(lm, **kw)
    # speculative rounds serve all-greedy batches only
    reqs = _workload(queue, lm[0].name, sampled="draft_model" not in kw)
    engine.run_until_idle(timeout_s=300)
    return ([tuple(r.future.result(timeout=5).tokens) for r in reqs],
            engine, reqs)


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec_window"])
def test_an_engine_on_a_packed_pool_serves_the_references_tokens(lm, spec):
    model, params = lm
    kw = {}
    if spec:
        # a DIFFERENT draft: partial acceptance, rejected tails
        kw = dict(draft_model=model,
                  draft_params=model.init(jax.random.PRNGKey(7)),
                  spec_tokens=3)
    served, engine, reqs = _serve(lm, **kw)
    assert engine.snapshot()["kv_pool"]["heads_per_row"] == 2
    if spec:
        assert engine.snapshot()["spec"]["rounds_windowed"] > 0
    assert_served(model, params, reqs, served)
    # several chunks a prompt were written through the packed rows
    assert sum(1 for t in engine.turns if t.kind == "chunk") > len(reqs)
    engine._allocator.check()


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec_window"])
def test_the_kernels_tokens_are_the_fallbacks(lm, spec):
    """The same traffic with the paged kernel (interpret mode) reading the
    packed pool: a decode step's Tq = 1 and the spec window's Tq = 4."""
    model, params = lm
    kw = {}
    if spec:
        kw = dict(draft_model=model,
                  draft_params=model.init(jax.random.PRNGKey(7)),
                  spec_tokens=3)
    attention.set_attention_backend("pallas")
    da.clear_decode_paths()
    try:
        kernel, engine, _ = _serve(lm, **kw)
        paths = da.decode_paths()
        lines = engine.snapshot()["kv_pool"]["decode_paths"]
    finally:
        attention.set_attention_backend("auto")
        da.clear_decode_paths()
    assert paths and all(p.heads_per_row == 2 for p in paths)
    # rows a pool head: window x (f = 2) x (G = 2)
    assert {p.rows for p in paths} >= ({4, 16} if spec else {4})
    assert lines and all("2 heads a pool row" in line for line in lines)
    xla, _, _ = _serve(lm, **kw)
    assert kernel == xla


def test_the_counter_says_a_page_a_fold_for_a_block_of_8_rows():
    """gpt2-medium's geometry (MHA 16 x 64: 8 pool rows a position, ONE head
    block of 8) at one tiny layer: the engine's counter
    (``snapshot()["kv_pool"]["decode_paths"]``) says its decode walk keeps
    a page a fold, where a narrow block's says two (ISSUE 52;
    ``tests/test_lfm2.py``)."""
    model = CausalLM(_cfg(16, 16, 64, num_layers=1, max_seq_len=512,
                          vocab_size=512),
                     name="gpt2m_tiny", dtype=jnp.float32)
    lm = (model, model.init(jax.random.PRNGKey(0)))
    attention.set_attention_backend("pallas")
    da.clear_decode_paths()
    try:
        engine, queue = _engine(lm, num_slots=2)
        assert engine._cache.k.shape == (1, 8, PS, 8, 128)
        _workload(queue, model.name, sampled=False, n=2)
        engine.run_until_idle(timeout_s=300)
        paths = da.decode_paths()
        lines = engine.snapshot()["kv_pool"]["decode_paths"]
    finally:
        attention.set_attention_backend("auto")
        da.clear_decode_paths()
    assert {(p.kb, p.pages, p.depth) for p in paths} == {(8, 1, 3)}
    # (the 8-row bucket's chunk program reads its pages through the kernel
    # too: 16 rows a pool head)
    assert any(line.startswith("decode_step: 2 heads a pool row: 8 heads "
                               "x 2 rows") for line in lines)
    assert all("a loop over the live pages, 1 page a fold, a ring of 3, "
               in line for line in lines)


def test_after_the_warm_up_a_packed_pool_compiles_nothing(lm):
    """The warm-up's programs are the served ones: no shape of the packed
    rows is met first by a request (``tools/check_compiles.py`` holds
    ``llama_tiny`` to the unchanged ``tools/compile_budget.json``)."""
    engine, queue = _engine(lm)
    engine.warmup()
    programs = {name: getattr(engine, name).__wrapped__
                for name in ("_chunk_paged_fn", "_decode_fn")}
    warmed = {name: p._cache_size() for name, p in programs.items()}
    assert warmed["_chunk_paged_fn"] == 4      # buckets 8, 16 x groups 1, 2
    reqs = _workload(queue, lm[0].name)
    engine.run_until_idle(timeout_s=300)
    assert all(r.future.result(timeout=5).tokens for r in reqs)
    assert {n: p._cache_size() for n, p in programs.items()} == warmed


@pytest.mark.parametrize("kv_heads, rows", [(4, 2), (2, 2)],
                         ids=["rows_divide", "rows_do_not"])
def test_under_a_tp_mesh_the_tokens_are_the_single_devices(
        kv_heads, rows, eight_devices):
    """4 KV heads of 64 over tp = 2: two pool rows, one a shard (heads 0-1
    and 2-3, as the k projection's head shards have them); 2 KV heads would
    be ONE row, which two shards cannot split: the parent's pool."""
    from ray_dynamic_batching_tpu.parallel.mesh import MeshConfig, build_mesh

    model = CausalLM(_cfg(4, kv_heads, 64, vocab_size=512),
                     name=f"wide_tp{kv_heads}", dtype=jnp.float32)
    lm = model, model.init(jax.random.PRNGKey(0))
    single, _, reqs = _serve(lm)
    mesh = build_mesh(MeshConfig(tp=2), eight_devices[:2])
    sharded, engine, _ = _serve(lm, mesh=mesh)
    assert engine._cache.k.shape[3:] == (rows, 128)
    assert engine.snapshot()["kv_pool"]["heads_per_row"] == kv_heads // rows
    assert not engine._cache.k.sharding.is_fully_replicated
    assert sharded == single
    assert_served(model, lm[1], reqs, sharded)


# --- the host's side of a launch ---------------------------------------------
def _launches(lm, monkeypatch):
    """Per launch of a turn and of a chunk group: the host arrays it
    uploaded, and whether every leaf the program was handed was already on
    the device."""
    model, _ = lm
    engine, queue = _engine(lm)
    # compile every shape first, so that no warm-up upload is counted
    for length in (10, 30):
        queue.add_request(Request(model=model.name, slo_ms=60_000.0, payload={
            "tokens": list(range(1, length + 1)), "max_new_tokens": 4}))
    engine.run_until_idle(timeout_s=300)
    log, inside = [], []

    def bracket(name):
        real = getattr(engine, name)

        def issuing(*a, **kw):
            inside.append([])
            try:
                return real(*a, **kw)
            finally:
                log.append((name, inside.pop()))
        monkeypatch.setattr(engine, name, issuing)

    def counting(real):
        def wrapped(x, *a, **kw):
            if inside and not isinstance(x, jax.Array):
                inside[-1].append(np.shape(x))
            return real(x, *a, **kw)
        return wrapped

    leaves = []
    for name in ("_decode_fn", "_chunk_paged_fn"):
        def spy(*args, _real=getattr(engine, name)):
            leaves.append(all(
                isinstance(leaf, (jax.Array, int))
                for leaf in jax.tree_util.tree_leaves(args)))
            return _real(*args)
        monkeypatch.setattr(engine, name, spy)
    bracket("_issue_turn")
    bracket("_issue_chunk_group")
    monkeypatch.setattr(jnp, "asarray", counting(jnp.asarray))
    monkeypatch.setattr(jax, "device_put", counting(jax.device_put))
    for length, extra in ((12, {}), (30, dict(temperature=0.7, top_k=8,
                                               seed=5))):
        queue.add_request(Request(model=model.name, slo_ms=60_000.0, payload={
            "tokens": list(range(2, length + 2)), "max_new_tokens": 4,
            **extra}))
    engine.run_until_idle(timeout_s=300)
    monkeypatch.undo()
    assert leaves and all(leaves)
    return log


def test_a_packed_pools_launches_upload_what_an_unpacked_pools_do(
        lm, monkeypatch):
    """Beside ``tests/test_chunk_upload.py``'s one transfer a chunk launch:
    with two heads a row a turn's launch and a chunk group's make exactly
    the transfers an unpacked engine's make (``llama_tiny``: 2 KV heads of
    16, the parent's pool) for the same requests, a chunk group's exactly
    ONE, and the programs are handed device arrays alone: whatever the
    layout needs is built from shapes inside the traced programs."""
    tiny = get_model("llama_tiny", dtype=jnp.float32)
    packed = _launches(lm, monkeypatch)
    unpacked = _launches((tiny, tiny.init(jax.random.PRNGKey(0))),
                         monkeypatch)
    count = lambda log: [(name, len(up)) for name, up in log]  # noqa: E731
    assert count(packed) == count(unpacked)
    chunks = [up for name, up in packed if name == "_issue_chunk_group"]
    turns = [up for name, up in packed if name == "_issue_turn"]
    assert len(chunks) >= 3 and all(len(up) == 1 for up in chunks)
    assert turns and all(up for up in turns)
    # a turn's steady launch: the [4, B] state alone (the carried tokens
    # are the last scan's result, on the device already)
    assert min(len(up) for up in turns) == 1 and (4, 4) in turns[-1]


# --- the programs that must not move -----------------------------------------
# sha256 of the StableHLO text of a tiny 128-WIDE-head model's decode (h = 2)
# and paged chunk (group 1, width 16) programs, taken at the parent commit
# (09bbfbe) on the CPU with ``tests/test_olmoe.py::_lowered``: a head that
# fills the lanes has nothing to pair, so the rule, the row helpers and the
# kernel's wrapper must leave its programs as they were. (``llama_tiny``, 2
# KV heads of 16, is held by ``tests/data/dense_program_digests.json``.)
# ``decode_step`` was retaken in PR 56 (the carried-tokens operand and its
# row of the state, ``tests/test_olmoe.py`` section (g)); the chunk program's
# digest is still 09bbfbe's.
@pytest.mark.parametrize("program", ["decode_step", "chunk_prefill"])
def test_a_128_wide_heads_program_lowers_to_the_parents_text(program):
    import hashlib
    import json
    from pathlib import Path

    from tests.test_olmoe import _lowered

    parent = json.loads((Path(__file__).resolve().parent / "data"
                         / "wide_head_program_digests.json").read_text())
    model = CausalLM(_cfg(2, 2, 128, vocab_size=512, mlp_dim=128),
                     name="wide128_tiny", dtype=jnp.float32)
    engine, _ = _engine((model, model.init(jax.random.PRNGKey(0))))
    assert engine.snapshot()["kv_pool"]["heads_per_row"] == 1
    assert hashlib.sha256(
        _lowered(engine)[program].as_text().encode()).hexdigest() == (
            parent[program])
