"""The KV state's own module (``models/kv_state.py``): every form of state
the tree serves, held to what the class says of itself.

Five forms and the packed pair: a k/v pair; the pair with int8 scale
planes; the pair with an indexer's keys; state by layer kind (pages + a
ring a slot); a latent row. For each: (a) ``planes()`` lists EVERY array
leaf of the pytree but the table and the lengths, so a rider added to the
class and forgotten in the list fails here; (b) the byte counts agree with
the leaves and with what the deployment prices a slot at; (c) a parcel's
pages come back as they left, or the kind refuses with the table's message;
(d) every row of the refusal table is raised, from the engine, in the words
it has carried since the refusals were written.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.models import kv_state
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

BASE = dict(vocab_size=64, d_model=64, num_layers=2, num_heads=4,
            num_kv_heads=2, mlp_dim=32, max_seq_len=256)
PAGE, MAX_LEN, BUCKET = 128, 256, 16
FORMS = {
    "pair": lambda: get_model("llama_tiny", dtype=jnp.float32),
    "int8": lambda: get_model("llama_tiny_int8kv", dtype=jnp.float32),
    # two 64-wide heads in one 128-lane row
    "packed": lambda: CausalLM(
        DecoderConfig(**dict(BASE, d_model=256)), name="packed",
        dtype=jnp.float32),
    "index": lambda: CausalLM(
        DecoderConfig(**BASE, index_topk=8, index_heads=2, index_head_dim=8),
        name="index", dtype=jnp.float32),
    "by_kind": lambda: CausalLM(
        DecoderConfig(**dict(BASE, num_layers=3), head_dim=24, v_head_dim=16,
                      sliding_window=8, layer_pattern="GLL",
                      sliding_kv_heads=4), name="by_kind",
        dtype=jnp.float32),
    "latent": lambda: CausalLM(
        DecoderConfig(**dict(BASE, num_kv_heads=4), head_dim=48, rope_dim=16,
                      v_head_dim=32, kv_lora_rank=128, q_lora_rank=48),
        name="latent", dtype=jnp.float32),
}
KIND = {"by_kind": "by_kind", "latent": "latent"}       # the others: "pair"


@pytest.fixture(scope="module", params=list(FORMS))
def form(request):
    model = FORMS[request.param]()
    cache = model.make_paged_cache(2, 6, PAGE, MAX_LEN, widest_chunk=BUCKET)
    return request.param, model, cache


def _filled(cache, seed=0):
    """``cache`` with every pool holding small whole numbers (exact in
    every dtype, int8 codes included)."""
    rng = np.random.default_rng(seed)
    leaves, tree = jax.tree_util.tree_flatten(cache)
    return jax.tree_util.tree_unflatten(tree, [
        jnp.asarray(rng.integers(-5, 5, x.shape), x.dtype)
        if x.ndim > 2 else x for x in leaves])


def test_planes_are_every_array_leaf_but_the_table_and_the_lengths(form):
    name, model, cache = form
    assert kv_state.state_kind(model.cfg) == KIND.get(name, "pair")
    planes = cache.planes()
    leaves = jax.tree_util.tree_leaves(cache)
    assert len(planes) == len(leaves) - 2
    rest = [x for x in leaves
            if x is not cache.page_table and x is not cache.lengths]
    assert all(p.array is x for p, x in zip(planes, rest))
    assert [p.name for p in planes] == [
        f for f in type(cache).__dataclass_fields__
        if f not in ("page_table", "lengths")
        and getattr(cache, f) is not None]
    # a ring's pages are the slot's own; everything else the table pages
    assert {p.name for p in planes if p.table == "ring"} == (
        {"ring_k", "ring_v"} if name == "by_kind" else set())
    assert {p.kind for p in planes} == {
        "by_kind": {"full", "ring"}, "latent": {"latent"}}.get(
            name, {"full"})
    # rows of heads are what a parcel carries at the model's own widths
    assert {p.name for p in planes if p.heads} == {
        n for n in ("k", "v", "ring_k", "ring_v")
        if getattr(cache, n) is not None}


def test_the_byte_counts_are_the_leaves_and_the_deployments_price(form):
    name, model, cache = form
    held = sum(x.nbytes for x in jax.tree_util.tree_leaves(cache)
               ) - cache.page_table.nbytes - cache.lengths.nbytes
    assert cache.logical_bytes() == cache.resident_bytes() == held
    assert sum(cache.bytes_by_kind().values()) == held
    # the deployment prices ONE slot's full page run at the same arrays
    one = model.make_paged_cache(1, MAX_LEN // PAGE, PAGE, MAX_LEN,
                                 widest_chunk=BUCKET)
    dep = LLMDeployment(model.name, model=model, page_size=PAGE,
                        prompt_buckets=[BUCKET])
    assert dep.pool_bytes_per_slot(model, MAX_LEN) == one.logical_bytes()
    lines = cache.describe(model.cfg)
    assert lines["resident_bytes"] == held
    assert ("bytes_by_kind" in lines) == (name in KIND)
    assert ("index_pool" in lines) == (name == "index")
    if name != "latent":
        assert lines["heads_per_row"] == (2 if name == "packed" else 1)
        assert lines["pool_shape"] == list(cache.k.shape)
    else:
        assert lines["row_bytes"] * lines["shape"][2] * lines["shape"][
            1] * lines["shape"][0] == held


def test_pages_come_back_as_they_left_or_the_kind_refuses(form):
    name, model, cache = form
    cache, cfg = _filled(cache), model.cfg
    src, dst = np.asarray([1, 3], np.int32), jnp.asarray([0, 2], jnp.int32)
    if name in KIND:
        message = kv_state.CANNOT[name]["parcel"].format(name="model")
        with pytest.raises(ValueError) as read:
            cache.read_pages(src, cfg)
        with pytest.raises(ValueError) as write:
            cache.write_pages(dst, {}, cfg)
        assert str(read.value) == str(write.value) == message
        return
    payload = cache.read_pages(src, cfg)
    assert list(payload) == [p.name for p in cache.planes()]
    # rows of heads travel at the model's own widths, whatever the pool's
    L = cfg.num_layers
    assert payload["k"].shape == payload["v"].shape == (
        L, 2, PAGE, cfg.num_kv_heads, cfg.head_dim)
    back = cache.write_pages(dst, payload, cfg)
    again = back.read_pages(np.asarray(dst), cfg)
    for key in payload:
        assert np.array_equal(payload[key], again[key]), key
    # ... and the pages not written are as they were
    for p, q in zip(cache.planes(), back.planes()):
        assert np.array_equal(p.array[:, 1], q.array[:, 1]), p.name
        assert np.array_equal(p.array[:, 3:], q.array[:, 3:]), p.name


# (kind, the table's row, words of its message as the parent raised it)
ROWS = [
    ("by_kind", "prefix_cache_size", "whose ring is the slot's own"),
    ("by_kind", "session_cache_size", "overwritten by its next tenant"),
    ("by_kind", "host_spill_pages", "it spills the prefix cache, which is "
                                    "refused"),
    ("by_kind", "draft_model", "the position 6 pages back and cannot be "
                               "undone"),
    ("by_kind", "mesh", "the ring's pool has no sharding layout"),
    ("by_kind", "kv_dtype int8", "the ring has no scale planes"),
    ("by_kind", "parcel", "the page fabric moves a stream as the pages of "
                          "its table; with state by layer kind the sliding "
                          "layers' ring is not among them"),
    ("by_kind", "slab", "state by layer kind is the paged cache's: the "
                        "slab cache has one shape for every layer"),
    ("latent", "host_spill_pages", "a spilled page is stored and restored "
                                   "as a k/v pair of heads; a latent page "
                                   "has neither"),
    ("latent", "draft_model", "the absorbed decode kernel folds one row a "
                              "slot"),
    ("latent", "mesh", "a latent row has no head axis to shard"),
    ("latent", "kv_dtype int8", "a latent row has no scale plane, nor has a "
                                "selecting layer's index key beside it"),
    ("latent", "parcel", "the page fabric moves a stream as k/v pages of "
                         "heads; a latent pool has one row a position (and "
                         "one index key where its layers select) and no "
                         "such pair"),
    ("latent", "slab", "a latent layer's rows live in the paged pool "
                       "(PagedKVCache.latent): the slab cache has none"),
]
STATE = {"by_kind": "state by layer kind", "latent": "a latent pool"}


def test_every_row_of_the_table_has_its_case():
    assert [(k, w) for k, w, _ in ROWS] == [
        (k, w) for k in ("by_kind", "latent") for w in kv_state.CANNOT[k]]
    assert kv_state.CANNOT["pair"] == {}


@pytest.mark.parametrize("kind, what, words", ROWS,
                         ids=[f"{k}-{w}" for k, w, _ in ROWS])
def test_the_engine_refuses_what_the_kind_cannot_serve(kind, what, words):
    model = FORMS[kind]()
    params = model.init(jax.random.PRNGKey(0))
    kw = dict(num_slots=2, max_len=MAX_LEN, prompt_buckets=[BUCKET],
              page_size=PAGE)
    build = lambda served=model, **more: DecodeEngine(  # noqa: E731
        served, params, RequestQueue(served.name, max_len=8), **kw, **more)
    whole = words
    if what == "slab":
        # the layer, handed a slab cache: at trace time, from the model
        with pytest.raises(NotImplementedError) as err:
            jax.eval_shape(
                model.decode_step, jax.eval_shape(lambda: params),
                jnp.zeros((2, 1), jnp.int32),
                jax.eval_shape(lambda: model.make_cache(2, 16)),
                jnp.ones((2,), bool))
        assert str(err.value) == whole
        return
    if what == "parcel":
        engine = build()
        for call in (lambda: engine.request_migration("r", lambda p: True),
                     lambda: engine.accept_parcel(None)):
            with pytest.raises(ValueError) as err:
                call()
            assert str(err.value) == f"{model.name}: {whole}"
        return
    with pytest.raises(ValueError) as err:
        if what == "draft_model":
            build(draft_model=model, draft_params=params)
        elif what == "mesh":
            from jax.sharding import Mesh

            build(mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)))
        elif what == "kv_dtype int8":
            build(CausalLM(model.cfg, name=model.name, dtype=jnp.float32,
                           kv_dtype=jnp.int8))
        else:
            build(**{what: 4})
    # an engine option, by its name; ``words`` end the reason
    assert str(err.value).startswith(
        f"{model.name}: {what} cannot be used with {STATE[kind]}: ")
    assert str(err.value).endswith(words)
    # the model's own doors read the same rows
    if what == "mesh":
        with pytest.raises(NotImplementedError, match=words):
            model.paged_cache_pspec()
    if what == "kv_dtype int8":
        with pytest.raises(NotImplementedError, match=words):
            CausalLM(model.cfg, name="i8", dtype=jnp.float32,
                     kv_dtype=jnp.int8).make_paged_cache(
                         2, 4, PAGE, MAX_LEN, widest_chunk=BUCKET)
