"""The language model of Keye-VL-2.0-30B-A3B at tiny widths on the normal
path, against the plain reference the benchmark keeps
(``benchmark/reference/keye.py``, read through ``benchmark/views/keye.py``;
both loaded by path: they import nothing of the program): a learned indexer
(2 heads of 8, ONE index key a position, paged beside k and v) picks the 8
cached positions each query attends; QK-norm per head; softmax top-2 routing
over 8 experts of which this rank holds 2. CPU, float32, seeded weights
(d 64, 4/2 heads of 16, experts of 32, page size 4), compared on LOGITS.
"""

import dataclasses
import importlib.util
import subprocess
import sys
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.engine.decode import (
    DecodeEngine,
    Turn,
    summarize_turns,
)
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import decoder
from ray_dynamic_batching_tpu.models.causal_lm import GPT2_MEDIUM, CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.models.kv_state import PagedKVCache
from ray_dynamic_batching_tpu.models.moe import MoEBlock, RoutingRule
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import sparse_attention as sparse

ROOT = Path(__file__).resolve().parents[1]

TOPK, PAGE, MAX_LEN = 8, 4, 48
E, HELD, TOP_K = 8, 2, 2
TINY = DecoderConfig(
    vocab_size=512, d_model=64, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, mlp_dim=32, max_seq_len=MAX_LEN, rope_theta=1e7,
    rms_eps=1e-6, qk_norm=True, qk_norm_per_head=True, num_experts=E,
    moe_top_k=TOP_K, moe_renormalize=True, moe_first_expert=HELD,
    moe_held_experts=HELD, index_topk=TOPK, index_heads=2, index_head_dim=8,
)
SIZES = {
    "rms_norm_eps": 1e-6, "num_attention_heads": 4,
    "num_key_value_heads": 2, "rope_theta": 1e7, "num_experts_per_tok": TOP_K,
    "norm_topk_prob": True,
    "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 8,
                  "topk": TOPK},
    "expert_parallel": {"first_expert": HELD},
    "program": {"decoder_config": {"num_layers": 2}},
}

# Program and reference both compute in float32 here, so they differ by
# summation order alone (worst gap read: 2e-6 on logits whose spread is 1);
# every wrong piece of arithmetic below moves them by hundredths or more.
TOL = 1e-4


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "keye_" + Path(rel).stem + "_" + Path(rel).parent.name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/keye.py")


@pytest.fixture(scope="module")
def view():
    return _load("benchmark/views/keye.py")


@pytest.fixture(scope="module")
def model():
    return CausalLM(TINY, name="keye_tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model, view):
    """``model.init``'s tree with the q/k norm scales drawn as the view's
    seeding rule says: with scales of one, dropping them would be the same
    function."""
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)

    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        rule = view.seeding(names, tuple(x.shape))
        if rule is not None and names[-1] == "scale":
            k = jax.random.fold_in(
                key, zlib.crc32("/".join(names).encode()) % (2 ** 31))
            return rule[0] + rule[1] * jax.random.normal(k, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(7).integers(1, TINY.vocab_size, 44)


@pytest.fixture(scope="module")
def want(params, view, ref, tokens):
    return np.asarray(ref.logits(view.view(params, SIZES), tokens, SIZES))


def _full(model, params, tokens):
    t = jnp.asarray(tokens, jnp.int32)[None]
    return np.asarray(model.apply(params, t, jnp.ones_like(t))[0])


def _gap(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


# --- the full forward -----------------------------------------------------------
def test_full_forward_matches_the_reference(model, params, tokens, want):
    assert _gap(_full(model, params, tokens), want) < TOL


def test_the_indexer_is_fields_of_the_config_and_of_the_layer_kind(params):
    layer = params["params"]["layer0"]
    assert layer["index_q"]["kernel"].shape == (64, 2, 8)
    assert layer["index_k"]["kernel"].shape == (64, 1, 8)   # ONE key head
    assert layer["index_w"]["kernel"].shape == (64, 2)
    assert [TINY.layer_kind(i).select for i in range(2)] == [TOPK, TOPK]
    assert GPT2_MEDIUM.layer_kind(0).select == 0
    with pytest.raises(ValueError, match="index_heads"):
        dataclasses.replace(TINY, index_heads=0)
    with pytest.raises(ValueError, match="sliding"):
        dataclasses.replace(TINY, sliding_window=16)


# --- chunked prefill through the paged pool, then decode ------------------------
N_PAGES, SLOTS, W = 30, 3, 8
PAGES = np.random.default_rng(1).permutation(N_PAGES)[:MAX_LEN // PAGE]


def _serve(model, params, tokens, prompt):
    """``tokens[:prompt]`` prefilled in W-wide chunks through a page table,
    then decoded one token at a time in a batch of SLOTS slots of which two
    are idle. Returns every position's logits."""
    chunk = jax.jit(model.prefill_chunk_paged,
                    static_argnames=("moe_counters",))
    step = jax.jit(model.decode_step_paged,
                   static_argnames=("moe_counters",))
    cache = model.make_paged_cache(SLOTS, N_PAGES, PAGE, MAX_LEN)
    tables = jnp.asarray([PAGES], jnp.int32)
    logits = {}
    for start in range(0, prompt, W):
        piece = tokens[start:min(start + W, prompt)]
        toks = np.zeros((1, W), np.int32)
        mask = np.zeros((1, W), np.int32)
        toks[0, :len(piece)] = piece
        mask[0, :len(piece)] = 1
        for j in range(len(piece)):
            taken, new = chunk(
                params, jnp.asarray(toks), jnp.asarray(mask), cache, tables,
                jnp.asarray([start], jnp.int32), jnp.asarray([j], jnp.int32))
            logits[start + j] = np.asarray(taken[0])
        cache = cache.replace(k=new.k, v=new.v, index_k=new.index_k)
    sentinel = jnp.full((len(PAGES),), N_PAGES, jnp.int32)
    cache = cache.replace(
        page_table=jnp.stack([sentinel, tables[0], sentinel]),
        lengths=jnp.asarray([0, prompt, 0], jnp.int32))
    active = jnp.asarray([False, True, False])
    for pos in range(prompt, len(tokens)):
        feed = jnp.asarray([0, tokens[pos], 0], jnp.int32)[:, None]
        out, cache = step(params, feed, cache, active)
        logits[pos] = np.asarray(out[1])
    return logits


@pytest.mark.parametrize("block", [16, 3, 6])
@pytest.mark.parametrize("prompt", [3, TOPK - 1, TOPK, 29])
def test_prefill_in_chunks_then_decode_matches_the_reference(
        block, prompt, model, params, tokens, want, monkeypatch):
    """Prompts below, just under, at and well past ``index_topk`` through
    chunks of 8 (the floor's staircase with the selection in it; or, with
    blocks of 3 or 6 of the table's 12 columns, the blocked window that
    stops at the last position attended), then single-token steps beside
    idle slots (the floor: the CPU's backend declines the kernel), all 44
    positions against the reference's ONE full forward."""
    monkeypatch.setattr(sparse, "BLOCK_PAGES", block)
    served = _serve(model, params, tokens, prompt)
    assert set(served) == set(range(len(tokens)))
    assert max(_gap(row, want[pos]) for pos, row in served.items()) < TOL


def test_spec_verify_windows_take_the_floor(model, params, tokens, want):
    """A verify window on a selecting layer is the chunk's staircase: four
    tokens a step from position 20 on, through the floor."""
    cache = model.make_paged_cache(1, N_PAGES, PAGE, MAX_LEN)
    tables = jnp.asarray([PAGES], jnp.int32)
    toks = np.zeros((1, 24), np.int32)
    toks[0, :20] = tokens[:20]
    _, new = model.prefill_chunk_paged(
        params, jnp.asarray(toks), jnp.asarray(toks > 0, jnp.int32), cache,
        tables, jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
    cache = new.replace(page_table=tables,
                        lengths=jnp.asarray([20], jnp.int32))
    logits, _ = model.verify_step_paged(
        params, jnp.asarray(tokens[None, 20:24], jnp.int32), cache,
        jnp.asarray([True]))
    assert _gap(logits[0], want[20:24]) < TOL


def test_the_slab_cache_has_no_index_keys(model, params, tokens):
    t = jnp.asarray(tokens[None, :8], jnp.int32)
    with pytest.raises(NotImplementedError, match="index keys"):
        model.prefill(params, t, jnp.ones_like(t), model.make_cache(1, 16))


# --- the mask form's kernel against the floor, token-exact ----------------------
KP, KH = 128, 128        # a page the kernel takes, a lane-wide head
LENGTHS = [0, 1, 5, 127, 128, 129, 300, 383, 511]


@pytest.mark.parametrize("heads, kv", [(8, 4), (16, 8), (4, 1)])
def test_mask_form_and_floor_agree_at_every_length(heads, kv):
    """The sparse kernel (interpreted) against the gather fallback under
    the same selection, at lengths round every page edge, beside an idle
    slot, with 4 key heads (the per-head fold), 8 (all heads in one
    contraction) and 1."""
    rng = np.random.default_rng(5)
    B, n_pages, NP, topk = len(LENGTHS), 48, 4, 40
    q = jnp.asarray(rng.normal(size=(B, 1, heads, KH)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(2, 2, n_pages, KP, kv, KH)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(n_pages)[:B * NP].reshape(B, NP),
                        jnp.int32).at[:, -1].set(n_pages)   # unallocated
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    select = sparse.Selection(
        q=jnp.asarray(rng.normal(size=(B, 1, 2, 8)), jnp.float32),
        w=jnp.asarray(rng.normal(size=(B, 1, 2)), jnp.float32),
        pool=jnp.asarray(rng.normal(size=(2, n_pages, KP, 128)),
                         jnp.float32), topk=topk)
    from tools.run_kernel_ab import gather_form_decode

    out = {}
    # the floor where the backend has no kernel, the mask form where it has
    for form, backend in ((sparse.FORM_FLOOR, "xla"),
                          (sparse.FORM_MASK, "pallas")):
        attn_ops.set_attention_backend(backend)
        try:
            out[form] = np.asarray(attn_ops.dot_product_attention(
                q, pool[0], pool[1], page_table=table, kv_lengths=lengths,
                layer=1, select=select))
        finally:
            attn_ops.set_attention_backend("auto")
    # the A/B tool's form (the selected rows only) reads the same answer
    out["gather"] = np.asarray(gather_form_decode(
        q, pool[0], pool[1], table, lengths, 1, select))
    for form in (sparse.FORM_MASK, "gather"):
        np.testing.assert_allclose(out[form], out[sparse.FORM_FLOOR],
                                   rtol=2e-5, atol=2e-5)
    # the selection is part of the answer past topk positions ...
    attn_ops.set_attention_backend("xla")
    try:
        dense = np.asarray(attn_ops.dot_product_attention(
            q, pool[0], pool[1], page_table=table, kv_lengths=lengths,
            layer=1))
    finally:
        attn_ops.set_attention_backend("auto")
    assert _gap(dense[3:], out[sparse.FORM_FLOOR][3:]) > 1e-2
    # ... and no part of it below
    np.testing.assert_allclose(dense[:3], out[sparse.FORM_FLOOR][:3],
                               rtol=2e-5, atol=2e-5)


# --- a narrow head block's page read as whole (8, 128) tiles ---------------------
VIEW = "-head page as 8-row tiles"


def _selected_attention(q, k, v, table, lengths, chosen, layer):
    """float32, a slot and a head at a time: softmax over the chosen
    positions <= the slot's length, rows looked up through the table."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    B, _, N, H = q.shape
    ps, K = k.shape[2], k.shape[3]
    out = np.zeros((B, 1, N, H), np.float32)
    for b in range(B):
        pos = np.flatnonzero(np.asarray(chosen[b])[:int(lengths[b]) + 1])
        page, off = np.asarray(table)[b, pos // ps], pos % ps
        for n in range(N):
            s = k[layer, page, off, n // (N // K)] @ q[b, 0, n] * H ** -0.5
            w = np.exp(s - s.max())
            out[b, 0, n] = (w / w.sum()) @ v[layer, page, off, n // (N // K)]
    return out


@pytest.mark.parametrize("heads, kv, page, dtype, fold", [
    (32, 4, 128, jnp.bfloat16, 2),     # the cell's block: 8 query rows a head
    (8, 4, 128, jnp.float32, 2),
    (16, 2, 128, jnp.bfloat16, 4),
    (4, 2, 128, jnp.float32, 4),
    (8, 4, 7, jnp.float32, 1),         # an odd page has no halves
    (4, 2, 6, jnp.float32, 1),         # ... and 6 positions no quarters
    (6, 3, 128, jnp.float32, 1),       # 3 heads make up no 8-row tile
    (16, 8, 128, jnp.bfloat16, 1),     # 8 heads: whole tiles as they lie
], ids=lambda x: getattr(x, "__name__", str(x)))
def test_a_narrow_page_is_read_as_whole_tiles(
        heads, kv, page, dtype, fold, monkeypatch):
    """The mask form's kernel (interpreted) with the pool read through its
    tile view against the SAME kernel with the view refused, bit for bit,
    and against float32 arithmetic: a random selection, permuted page
    tables, a slot on its first page, a slot at the table's last column.
    Where there is no view (an odd page, 3 heads, 8 heads) the kernel runs
    as it did; ``sparse_forms()`` names what each program took."""
    from ray_dynamic_batching_tpu.utils import compile_ledger

    rng = np.random.default_rng(zlib.crc32(f"{heads}/{kv}/{page}".encode()))
    H, NP, n_pages, layer = 128, 4, 24, 1
    lengths = np.asarray([0, page - 1, page + 2, 2 * page + 9, NP * page - 1])
    B = len(lengths)
    q = jnp.asarray(rng.normal(size=(B, 1, heads, H)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(2, n_pages, page, kv, H)), dtype)
            for _ in range(2))
    table = rng.permutation(n_pages)[:B * NP].reshape(B, NP)
    for b in range(B):                  # unallocated past the last live page
        table[b, lengths[b] // page + 1:] = n_pages
    chosen = rng.random((B, NP * page)) < 0.4
    chosen[np.arange(B), lengths] = True            # never an empty row
    kb, G = kv, heads // kv
    assert sparse._page_fold(kb, G, page) == fold

    def kernel():
        return np.asarray(sparse.sparse_paged_decode_attention(
            q, k, v, jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(chosen),
            layer=layer, interpret=True), np.float32)

    got = kernel()
    with monkeypatch.context() as refused:
        refused.setattr(sparse, "_page_fold", lambda *a: 1)
        np.testing.assert_array_equal(got, kernel())
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        got, _selected_attention(q, k, v, table, lengths, chosen, layer),
        rtol=tol, atol=tol)
    if page % 128:
        # not a page the dispatcher hands the kernel: nothing to name
        return
    # the dispatcher's own call, under a program's name
    name = f"narrow_page_{heads}_{kv}"
    select = sparse.Selection(
        q=jnp.asarray(rng.normal(size=(B, 1, 2, 8)), jnp.float32),
        w=jnp.asarray(rng.normal(size=(B, 1, 2)), jnp.float32),
        pool=jnp.asarray(rng.normal(size=(2, n_pages, page, 128)),
                         jnp.float32), topk=40)
    attn_ops.set_attention_backend("pallas")
    try:
        compile_ledger.instrument(name, attn_ops.dot_product_attention)(
            q, k, v, page_table=jnp.asarray(table, jnp.int32),
            kv_lengths=jnp.asarray(lengths, jnp.int32), layer=layer,
            select=select)
    finally:
        attn_ops.set_attention_backend("auto")
    took = [f for f in sparse.sparse_forms() if f.startswith(name + ":")]
    assert len(took) == 1 and sparse.FORM_MASK in took[0]
    assert (f"{kv}{VIEW}" in took[0]) == (fold > 1), took


# --- several live pages an online-softmax update ---------------------------------
FOLD_PAGE, FOLD_TABLE = 128, 8


def _fold_case(case: str, pages: int):
    """Lengths of a batch (one stream of groups across its slots) and the
    live pages a selection leaves wholly unchosen, by slot."""
    ps = FOLD_PAGE
    return {
        "one_page": ([5, ps - 2, 3 * ps + 1], {}),
        "a_fold_exactly": ([pages * ps - 3, 2 * pages * ps - 1, 7], {}),
        "a_fold_and_a_page": ([pages * ps + 4, pages * ps, 6 * ps + 9], {}),
        "a_pages_last_position": ([ps - 1, 3 * ps - 1,
                                   FOLD_TABLE * ps - 1], {}),
        "an_idle_slot": ([0, 5 * ps + 17, 0, 2 * ps], {}),
        "a_page_unchosen": ([4 * ps + 30, 7 * ps + 2, 2 * ps + 1],
                            {0: [0, 3], 1: [5, 6], 2: [1]}),
    }[case]


@pytest.mark.parametrize("pages", [1, 2, 4])
@pytest.mark.parametrize("case", [
    "one_page", "a_fold_exactly", "a_fold_and_a_page",
    "a_pages_last_position", "an_idle_slot", "a_page_unchosen"])
def test_a_fold_of_several_pages_is_the_same_sum(case, pages, monkeypatch):
    """ISSUE 51: the mask form's kernel (interpreted) folding ``pages``
    live pages an online-softmax update against float32 arithmetic a slot
    and a head at a time, and against itself at a page a fold: a slot
    with one live page, with a fold's pages exactly, with one more (a
    short last group, whose tail repeats the last live page behind the
    length bound), a length at a page's last position, an idle slot, and
    a selection that leaves whole live pages unchosen. Entries past the
    last live page are the sentinel."""
    lengths, unchosen = _fold_case(case, pages)
    rng = np.random.default_rng(zlib.crc32(f"{case}/{pages}".encode()))
    ps, NP, H, heads, kv, n_pages, layer = (
        FOLD_PAGE, FOLD_TABLE, 128, 8, 4, 40, 1)
    lengths = np.asarray(lengths)
    B = len(lengths)
    q = jnp.asarray(rng.normal(size=(B, 1, heads, H)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, n_pages, ps, kv, H)),
                        jnp.float32) for _ in range(2))
    table = rng.permutation(n_pages)[:B * NP].reshape(B, NP)
    for b in range(B):
        table[b, lengths[b] // ps + 1:] = n_pages
    chosen = rng.random((B, NP * ps)) < 0.3
    chosen[np.arange(B), lengths] = True            # never an empty row
    for b, gone in unchosen.items():
        for page in gone:
            assert page < lengths[b] // ps          # live, not the last
            chosen[b, page * ps:(page + 1) * ps] = False

    def kernel(n):
        with monkeypatch.context() as picked:
            picked.setattr(sparse, "_fold_pages", lambda *a: n)
            return np.asarray(sparse.sparse_paged_decode_attention(
                q, k, v, jnp.asarray(table, jnp.int32),
                jnp.asarray(lengths, jnp.int32), jnp.asarray(chosen),
                layer=layer, interpret=True), np.float32)

    got = kernel(pages)
    np.testing.assert_allclose(
        got, _selected_attention(q, k, v, table, lengths, chosen, layer),
        rtol=2e-5, atol=2e-5)
    # another order of rescaling, the same sum
    np.testing.assert_allclose(got, kernel(1), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("NP, kv, pages", [
    (8, 4, 4),      # a table four divides
    (6, 4, 2),      # ... two only
    (7, 2, 1),      # ... neither
    (8, 8, 1),      # 8 heads: the shared flat fold keeps a page a fold
])
def test_the_shapes_pick_the_pages_a_fold(NP, kv, pages):
    """The dispatcher's own call: the mask form at the width its shapes
    pick (``tile_math.sparse_fold_pages``) against the floor on ragged
    lengths, and ``sparse_forms()`` saying what the program folds."""
    from ray_dynamic_batching_tpu.utils import compile_ledger

    rng = np.random.default_rng(NP * 16 + kv)
    ps, H, heads, n_pages, layer = FOLD_PAGE, 128, 16, 48, 1
    lengths = np.asarray([0, ps - 1, 3 * ps + 7, NP * ps - 1, 5 * ps])
    B = len(lengths)
    assert sparse._fold_pages(kv, heads // kv, ps, H, 4, NP) == pages
    q = jnp.asarray(rng.normal(size=(B, 1, heads, H)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, n_pages, ps, kv, H)),
                        jnp.float32) for _ in range(2))
    table = rng.permutation(n_pages)[:B * NP].reshape(B, NP)
    for b in range(B):
        table[b, lengths[b] // ps + 1:] = n_pages
    select = sparse.Selection(
        q=jnp.asarray(rng.normal(size=(B, 1, 2, 8)), jnp.float32),
        w=jnp.asarray(rng.normal(size=(B, 1, 2)), jnp.float32),
        pool=jnp.asarray(rng.normal(size=(2, n_pages, ps, 128)),
                         jnp.float32), topk=200)
    name = f"pages_a_fold_{NP}_{kv}"
    out = {}
    for form, backend in ((sparse.FORM_FLOOR, "xla"),
                          (sparse.FORM_MASK, "pallas")):
        attn_ops.set_attention_backend(backend)
        try:
            out[form] = np.asarray(compile_ledger.instrument(
                f"{name}_{form}", attn_ops.dot_product_attention)(
                q, k, v, page_table=jnp.asarray(table, jnp.int32),
                kv_lengths=jnp.asarray(lengths, jnp.int32), layer=layer,
                select=select))
        finally:
            attn_ops.set_attention_backend("auto")
    np.testing.assert_allclose(out[sparse.FORM_MASK], out[sparse.FORM_FLOOR],
                               rtol=2e-5, atol=2e-5)
    took = [f for f in sparse.sparse_forms()
            if f.startswith(f"{name}_{sparse.FORM_MASK}:")]
    assert len(took) == 1 and (
        f"{pages} page{'s' if pages > 1 else ''} a fold, a ring of 3"
        in took[0]), took


def test_mask_form_declines_by_name(monkeypatch):
    q = jnp.zeros((2, 1, 8, 128))
    k = jnp.zeros((1, 4, 128, 4, 128))
    table = jnp.zeros((2, 2), jnp.int32)
    assert "pallas off" in sparse._mask_form_declines(q, k, table, None)
    monkeypatch.setattr(attn_ops, "_BACKEND", "pallas")
    assert sparse._mask_form_declines(q, k, table, None) == ""
    assert "int8" in sparse._mask_form_declines(
        q, k.astype(jnp.int8), table, jnp.zeros((4, 128, 4)))
    assert "page size" in sparse._mask_form_declines(
        q, jnp.zeros((1, 4, 4, 4, 128)), table, None)
    assert "tiling" in sparse._mask_form_declines(
        q, jnp.zeros((1, 4, 128, 1, 128), jnp.bfloat16), table, None)


# --- ties at the edge ------------------------------------------------------------
@pytest.mark.parametrize("scores, topk, live, chosen", [
    ([3, 1, 2, 2, 2, 0], 3, 6, [0, 2, 3]),     # three tie, two fit: lower
    ([1, 1, 1, 1, 1, 1], 2, 6, [0, 1]),        # all tie
    ([5, 4, 3, 2, 1, 0], 4, 6, [0, 1, 2, 3]),  # none
    ([0, 7, 7, 1, 7, 7], 3, 6, [1, 2, 4]),
    ([2, 2, 9, 9, 2, 2], 5, 5, [0, 1, 2, 3, 4]),  # as many live as topk
    ([2, 2, 9, 9, 2, 2], 5, 4, [0, 1, 2, 3]),     # fewer live than topk
])
def test_a_tie_at_the_edge_goes_to_the_lower_position(
        scores, topk, live, chosen, ref):
    s = jnp.asarray([[scores]], jnp.float32)                    # [1, 1, S]
    allowed = (jnp.arange(6) < live)[None, None]
    got = np.flatnonzero(np.asarray(
        sparse.exact_topk_mask(s, allowed, topk))[0, 0])
    assert got.tolist() == chosen and len(got) <= topk
    # the reference's literal rule: a stable sort, the first topk
    masked = np.where(np.asarray(allowed)[0, 0], scores, -np.inf)
    literal = np.sort(np.argsort(-masked, kind="stable")[:topk])
    assert [i for i in literal if masked[i] > -np.inf] == chosen
    # and the gather form's order (lax.top_k: lower index first)
    vals, pos = jax.lax.top_k(jnp.asarray(masked), topk)
    assert sorted(int(p) for p, v in zip(pos, vals)
                  if v > -np.inf) == chosen


# --- controls: each wrong arithmetic must FAIL the tolerance -------------------
@pytest.mark.parametrize("wrong", [
    "no_selection", "topk_one_short", "topk_one_more", "relu_dropped",
    "head_weights_dropped", "index_keys_not_rotated", "index_scale_sign",
    "qk_scale_dropped", "gates_not_renormalised", "all_experts_here"])
def test_wrong_arithmetic_fails_the_tolerance(
        wrong, model, params, tokens, want, monkeypatch):
    cfg, served_params = TINY, params
    if wrong == "no_selection":         # dense attention over the prefix
        cfg = dataclasses.replace(TINY, index_topk=0)
    elif wrong == "topk_one_short":
        cfg = dataclasses.replace(TINY, index_topk=TOPK - 1)
    elif wrong == "topk_one_more":
        cfg = dataclasses.replace(TINY, index_topk=TOPK + 1)
    elif wrong in ("relu_dropped", "head_weights_dropped",
                   "index_scale_sign"):
        def scores(q_i, w_i, k_i):
            s = jnp.einsum("btnh,bsh->btns", q_i, k_i)
            if wrong != "relu_dropped":
                s = jax.nn.relu(s)
            if wrong == "index_scale_sign":       # smallest, not largest
                s = -s
            if wrong == "head_weights_dropped":
                return s.sum(2)
            return jnp.einsum("btns,btn->bts", s, w_i)

        monkeypatch.setattr(sparse, "index_scores", scores)
    elif wrong == "index_keys_not_rotated":
        rope = decoder.apply_rope
        monkeypatch.setattr(
            decoder, "apply_rope", lambda x, pos, theta: (
                x if x.shape[2:] == (1, 8) else rope(x, pos, theta)))
    elif wrong == "qk_scale_dropped":
        served_params = jax.tree_util.tree_map(lambda x: x, params)
        for i in range(TINY.num_layers):
            for n in ("q_norm", "k_norm"):
                leaf = served_params["params"][f"layer{i}"][n]
                leaf["scale"] = jnp.ones_like(leaf["scale"])
    elif wrong == "gates_not_renormalised":
        cfg = dataclasses.replace(TINY, moe_renormalize=False)
    elif wrong == "all_experts_here":      # the neighbour rank's experts
        cfg = dataclasses.replace(TINY, moe_first_expert=0)
    served = CausalLM(cfg, name=wrong, dtype=jnp.float32)
    assert _gap(_full(served, served_params, tokens), want) > 10 * TOL


def test_the_norms_epsilon_is_the_configurations(params, tokens, want):
    """1e-6 as published, not the program's older 1e-5 (7.5e-4 apart)."""
    old = CausalLM(dataclasses.replace(TINY, rms_eps=1e-5), name="eps",
                   dtype=jnp.float32)
    assert _gap(_full(old, params, tokens), want) > 5 * TOL


def test_bfloat16_fails_the_tolerance(params, tokens, want):
    low = CausalLM(TINY, name="keye_tiny_bf16", dtype=jnp.bfloat16)
    assert _gap(_full(low, params, tokens), want) > 10 * TOL


def test_below_topk_positions_the_selection_changes_nothing(
        model, params, tokens, want):
    dense = CausalLM(dataclasses.replace(TINY, index_topk=0),
                     name="keye_dense", dtype=jnp.float32)
    got = _full(dense, params, tokens)
    assert _gap(got[:TOPK], want[:TOPK]) < TOL < _gap(got[TOPK:], want[TOPK:])


# --- the shares add up -------------------------------------------------------------
D_BLOCK, F_BLOCK = 128, 128


def _block(first, held):
    return MoEBlock(
        d_model=D_BLOCK, mlp_dim=F_BLOCK, num_experts=E, top_k=TOP_K,
        rule=RoutingRule("softmax", False, True, 1.0), first_expert=first,
        held_experts=held, dtype=jnp.float32)


def test_the_ranks_shares_add_up_to_the_uncut_layer(ref):
    """Every rank's partial result (its 2 of the 8 experts, under the
    softmax rule) against the reference's whole layer; each rank's alone
    against the reference given that share."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 24, D_BLOCK)), jnp.float32)
    p = _block(0, E).init(jax.random.PRNGKey(5), x)["params"]
    flat = x.reshape(-1, D_BLOCK)
    ones = jnp.ones((D_BLOCK,))
    h = ref._rms(flat, ones, 1e-6).reshape(x.shape)

    def reference(cut, first):
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref._experts(
                flat, ones, p["router"]["kernel"], p["wi"][cut],
                p["wg"][cut], p["wo"][cut], top_k=TOP_K, renorm=True,
                first=first, eps=1e-6)[0])

    whole = reference(slice(None), 0)
    parts = []
    for r in range(E // HELD):
        cut = slice(r * HELD, (r + 1) * HELD)
        part = np.asarray(_block(r * HELD, HELD).apply({"params": dict(
            p, wi=p["wi"][cut], wg=p["wg"][cut], wo=p["wo"][cut])}, h)
        ).reshape(-1, D_BLOCK)
        assert _gap(np.asarray(flat) + part, reference(cut, r * HELD)) < TOL
        parts.append(part)
    assert _gap(np.asarray(flat) + sum(parts), whole) < TOL
    assert _gap(np.asarray(flat) + parts[0], whole) > 100 * TOL


# --- the engine: a page keeps its index keys; counters; snapshot ----------------
E_PAGE, E_PAGES, E_MAX_LEN = 128, 6, 256     # the engine's pages are 128


def _engine(model, params, **kw):
    queue = RequestQueue(model.name, max_len=64)
    engine = DecodeEngine(
        model, params, queue, num_slots=2, max_len=E_MAX_LEN,
        prompt_buckets=[16], paged=True, page_size=E_PAGE,
        kv_pool_pages=E_PAGES, decode_horizon=1, max_admissions_per_step=1,
        default_max_new_tokens=4, **kw)
    return engine, queue


def test_engine_serves_it_and_counts_what_the_indexer_keeps(
        model, params, view, ref, tokens):
    engine, queue = _engine(model, params)
    prompt = [int(t) for t in tokens[:30]]
    req = Request(model=model.name, slo_ms=60_000.0, payload={
        "tokens": prompt, "max_new_tokens": 4})
    queue.add_request(req)
    engine.run_until_idle(timeout_s=300)
    out = list(req.future.result(timeout=5).tokens)
    assert len(out) == 4
    want = np.asarray(ref.logits(view.view(params, SIZES), prompt + out,
                                 SIZES))
    for j, tok in enumerate(out):      # greedy: the reference's own top-1
        row = want[len(prompt) - 1 + j]
        assert row.max() - row[tok] < TOL
    scans = [t for t in engine.turns if t.kind == "turn"]
    # one busy slot at 30 cached positions + its token, one idle slot its
    # one row; two selecting layers; the indexer keeps 8 (1 of the idle's)
    assert scans[0].kv_rows_live == 2 * (31 + 1)
    assert scans[0].kv_rows_selected == 2 * (TOPK + 1)
    summary = engine.turn_summary()
    assert summary["kv_selected_row_share"] == pytest.approx(
        summary["kv_rows_selected"] / summary["kv_rows_live"])
    assert 0.2 < summary["kv_selected_row_share"] < 0.3
    pool = engine.snapshot()["kv_pool"]
    assert pool["index_pool"]["shape"] == [2, E_PAGES, E_PAGE, 128]
    assert pool["index_topk"] == TOPK and pool["select_layers"] == 2
    assert pool["selected_row_share"] == summary["kv_selected_row_share"]
    assert pool["resident_bytes"] > pool["index_pool"]["resident_bytes"] > 0
    assert any("floor" in f for f in pool["sparse_forms"])
    assert model.kv_bytes_per_slot(MAX_LEN) == 2 * MAX_LEN * (
        2 * 2 * 16 * 4 + 8 * 4)


def test_a_page_read_out_and_written_back_keeps_its_index_keys(
        model, params, tokens):
    engine, queue = _engine(model, params)
    queue.add_request(Request(model=model.name, slo_ms=60_000.0, payload={
        "tokens": [int(t) for t in tokens[:30]], "max_new_tokens": 2}))
    engine.run_until_idle(timeout_s=300)
    index = np.asarray(engine._cache.index_k)
    used = sorted({int(p) for p in np.flatnonzero(
        np.abs(index).sum(axis=(0, 2, 3)))})
    assert len(used) == 1            # 32 positions: one page of 128
    parcel = engine._read_pages(used)
    assert set(parcel) == {"k", "v", "index_k"}
    np.testing.assert_array_equal(parcel["index_k"], index[:, used])
    assert np.abs(parcel["index_k"][:, 0, :30, :8]).min() > 0
    free = [p for p in range(E_PAGES) if p not in used][:1]
    engine._write_pages(free, parcel)
    np.testing.assert_array_equal(
        np.asarray(engine._cache.index_k)[:, free], index[:, used])
    np.testing.assert_array_equal(
        np.asarray(engine._cache.k)[:, free],
        np.asarray(engine._cache.k)[:, used])


def test_summarize_turns_of_another_models_ring_has_no_selection_keys():
    ring = [Turn("turn", 0.0, 1.0, 2.0, 3.0, 8, 0, 4, 0, 0, 0, 0, False)] * 3
    out = summarize_turns(ring, num_slots=4)
    assert not [k for k in out if k.startswith("kv_rows")
                or k == "kv_selected_row_share"]
    assert ring[0].kv_rows_live == ring[0].kv_rows_selected == 0


# --- a configuration WITHOUT an indexer is untouched ---------------------------------
def test_a_model_without_an_indexer_has_no_index_pool_and_its_old_program():
    """gpt2-medium's pool has four leaves as before, and its decode and
    chunk programs trace to as many TOP-LEVEL equations as the parent's
    (abd412c: 4,188 / 5,016 with inner ones, 4,216 / 5,051; counted there
    with this function). Since PR 48 its pool holds two heads a row and
    each layer's paged write is a reshape where it was two pads: 96 inner
    equations fewer, on purpose (``tests/test_xing.py::PINNED``)."""
    m = CausalLM(GPT2_MEDIUM, name="g", dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: m.make_paged_cache(16, 128, 128, 1024))
    assert cache.index_k is None
    assert len(jax.tree_util.tree_leaves(cache)) == 4
    assert PagedKVCache.zeros(TINY, 1, 2, 4, 8).index_k.shape == (
        2, 2, 4, 128)
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    assert not [k for k in p["params"]["layer0"] if k.startswith("index")]
    sds = jax.ShapeDtypeStruct
    decode = jax.make_jaxpr(m.decode_step_paged)(
        p, sds((16, 1), jnp.int32), cache, sds((16,), jnp.bool_))
    chunk = jax.make_jaxpr(m.prefill_chunk_paged)(
        p, sds((2, 256), jnp.int32), sds((2, 256), jnp.int32), cache,
        sds((2, 8), jnp.int32), sds((2,), jnp.int32), sds((2,), jnp.int32))
    assert (len(decode.jaxpr.eqns), _equations(decode.jaxpr)) == (4188, 4920)
    assert (len(chunk.jaxpr.eqns), _equations(chunk.jaxpr)) == (4216, 4955)


def _equations(j):
    """Equations of a jaxpr, inner ones counted."""
    n = 0
    for e in j.eqns:
        n += 1
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    n += _equations(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    n += _equations(sub)
    return n


@pytest.mark.parametrize("config, decode_eqns, chunk_eqns", [
    ("olmoe-1b-7b-1chip", (3166, 3784), (3182, 3807)),
    ("k-exaone-236b-ep8-1chip", (1403, 1765), (1412, 1781)),
    ("mistral-7b-v0.3-1chip", (2950, 3468), (2970, 3495)),
])
def test_the_other_cells_programs_trace_as_the_parents(
        config, decode_eqns, chunk_eqns):
    """An expert model, a held share with window layers and per-head norms,
    and a dense RoPE model, at their benchmark files' own widths and
    deployment: the decode program and the widest chunk program trace to as
    many equations as at the parent (abd412c, counted there by this
    function), and the pool's pytree has its four leaves: what a start
    traces and lowers for them is what it was (``rms_eps``, the ``select``
    argument and the index pool add nothing where there is no indexer)."""
    import json

    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / f"{config}.json").read_text())
    llm = cfg["deployment"]["llm"]
    m = CausalLM(DecoderConfig(**cfg["program"]["decoder_config"]),
                 name="m", dtype=jnp.bfloat16)
    B, ps = llm["num_slots"], llm["page_size"]
    W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
    cache = jax.eval_shape(lambda: m.make_paged_cache(
        B, llm["kv_pool_pages"], ps, llm["max_len"]))
    assert len(jax.tree_util.tree_leaves(cache)) == 4
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    decode = jax.make_jaxpr(m.decode_step_paged)(
        p, sds((B, 1), jnp.int32), cache, sds((B,), jnp.bool_))
    chunk = jax.make_jaxpr(m.prefill_chunk_paged)(
        p, sds((2, W), jnp.int32), sds((2, W), jnp.int32), cache,
        sds((2, NP), jnp.int32), sds((2,), jnp.int32), sds((2,), jnp.int32))
    assert (len(decode.jaxpr.eqns), _equations(decode.jaxpr)) == decode_eqns
    assert (len(chunk.jaxpr.eqns), _equations(chunk.jaxpr)) == chunk_eqns


@pytest.mark.parametrize("module", [
    "ray_dynamic_batching_tpu.models.decoder",
    "ray_dynamic_batching_tpu.models.causal_lm",
    "ray_dynamic_batching_tpu.engine.decode",
    "ray_dynamic_batching_tpu.serve.llm",
])
def test_importing_the_program_does_not_import_the_sparse_module(module):
    code = (f"import sys, {module}; "
            "sys.exit('ray_dynamic_batching_tpu.ops.sparse_attention' "
            "in sys.modules)")
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT),
           "PATH": "/usr/bin:/bin"}
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          cwd="/").returncode == 0


# --- the benchmark's counts and readers for the new metrics --------------------
class _FakeTrace:
    """What the readers ask of ``benchmark.trace_reduce.Trace``."""

    devices = {0: [object()]}

    def __init__(self, times):
        self.times = times          # pattern -> (seconds, count)

    def busy_s(self):
        return 2.0

    def op_time(self, pattern, module=None):
        return self.times.get(pattern, (0.0, 0))


@pytest.mark.parametrize("rows, want_bytes, want_flops", [
    (1, 2 * 4 * 128 * 2, 2 * 2 * 32 * 128),
    (2048 * 24, 2048 * 24 * 2048, 2048 * 24 * 16384),
])
def test_selected_rows_cost_their_keys_and_values(
        rows, want_bytes, want_flops):
    counts = _load("benchmark/sparse_counts.py")
    assert counts.selected_rows_bytes(rows, 4, 128) == want_bytes
    assert counts.selected_rows_flops(rows, 32, 128) == want_flops


def _ctx(trace, turns):
    class Engine:
        pass

    eng = Engine()
    eng.turns = __import__("collections").deque(turns)
    return {
        "trace": trace, "trace_host_window": (10.0, 14.0),
        "run": {"t0": 100.0}, "engines": [eng],
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "config": {"program": {"decoder_config": {
            "num_kv_heads": 4, "num_heads": 32, "head_dim": 128,
            "num_layers": 8}}, "deployment": {"llm": {"page_size": 128}}},
    }


def _turn(t_dispatch, t_fetched, substeps, selected):
    return Turn("turn", t_dispatch, t_dispatch, t_fetched, t_fetched,
                substeps, 0, 24, 0, 0, 0, 0, False, kv_pages_live=30,
                kv_rows_live=4 * selected, kv_rows_selected=selected)


@pytest.mark.parametrize("case", ["inside", "astride", "outside", "no_rows",
                                  "no_kernel", "no_trace"])
def test_sparse_decode_roofline_counts_the_rows_inside_the_trace(case):
    sys.path.insert(0, str(ROOT))
    try:
        from benchmark.readers import sparse_decode_roofline as reader
    finally:
        sys.path.remove(str(ROOT))
    trace = _FakeTrace({"paged_decode_attention": (0.5, 100)})
    # the traced window is [110 s, 114 s) on the engine's clock, in ms
    turns = {
        "inside": [_turn(111_000.0, 112_000.0, 8, 1000)],
        "astride": [_turn(109_000.0, 111_000.0, 8, 1000)],   # half inside
        "outside": [_turn(100_000.0, 101_000.0, 8, 1000)],
        "no_rows": [_turn(111_000.0, 112_000.0, 8, 0)],
    }.get(case, [_turn(111_000.0, 112_000.0, 8, 1000)])
    if case == "no_kernel":
        trace = _FakeTrace({})
    ctx = _ctx(None if case == "no_trace" else trace, turns)
    got = reader.read(ctx, op="paged_decode_attention", module="decode_impl")
    if case in ("outside", "no_rows", "no_kernel", "no_trace"):
        assert got is None
        return
    rows = 8 * 1000 * (1.0 if case == "inside" else 0.5)
    assert got == pytest.approx(
        100.0 * rows * 2 * 4 * 128 * 2 / 819e9 / 0.5)
    assert got < 100.0
    # the form's own bytes: every live page of 8 layers, whole
    walked = reader.read(ctx, op="paged_decode_attention",
                         module="decode_impl", bytes="walked")
    pages = 8 * 30 * 8 * (1.0 if case == "inside" else 0.5)
    assert walked == pytest.approx(
        100.0 * pages * 128 * 2 * 4 * 128 * 2 / 819e9 / 0.5)


def _Ev(name, start, end):
    from benchmark.trace_reduce import Event

    return Event(name, start, end, {})


class _OpsTrace:
    """A trace of whole events, as ``sparse_select`` reads one: a k-th key
    search (a loop over unsigned keys with two counts inside), a score
    fusion outside it, a count of that name outside any loop, another
    model's operation."""

    window = (0.0, 10.0)

    def __init__(self):
        self.devices = {0: [
            _Ev("%while.12 = (s32[], u32[24,1]{1,0}, u32[24,18432]{1,0}) "
                "while(%tuple.3), condition=%c, body=%b", 1.0, 1.5),
            _Ev("%fusion.7 = s32[24]{0} fusion(%p)", 1.0, 1.1),
            _Ev("%fusion.7 = s32[24]{0} fusion(%p)", 1.2, 1.3),
            _Ev("%fusion.9 = f32[24,18432]{1,0} fusion(%q)", 2.0, 2.25),
            _Ev("%fusion.8 = s32[24]{0} fusion(%tok)", 3.0, 3.05),
            _Ev("%fusion.5 = bf16[24,2048]{1,0} fusion(%x)", 4.0, 5.0),
        ]}

    def busy_s(self):
        return 2.0

    def in_window(self, events):
        return list(events)

    def op_self_times(self, d):
        from benchmark.trace_reduce import self_times

        return self_times(self.devices[d])


@pytest.mark.parametrize("case", ["loops", "neither", "no_trace"])
def test_sparse_select_share_takes_the_search_loops_whole(case, capsys):
    """The k-th key's loops whole (0.5 s: counts and control) and the score
    fusion (0.25) over 2 s busy, a count-named operation OUTSIDE a loop not
    taken; a model without an indexer reads None."""
    import json

    sys.path.insert(0, str(ROOT))
    try:
        from benchmark.readers import sparse_select as reader
    finally:
        sys.path.remove(str(ROOT))
    args = json.loads((ROOT / "benchmark" / "layer_metrics" /
                       "sparse_select_dev_share_pct.batch.json"
                       ).read_text())["args"]
    tr = None if case == "no_trace" else _OpsTrace()
    if case == "neither":
        tr.devices = {0: tr.devices[0][-1:]}
    got = reader.read({"trace": tr}, **args)
    if case != "loops":
        assert got is None
        return
    out = capsys.readouterr().out
    assert got == pytest.approx(100.0 * (0.5 + 0.25) / 2.0)
    assert "1 loops taken whole" in out
    assert "fusion_f32_24_18432_" in out and "fusion_s32_24_" not in out


@pytest.mark.parametrize("names, want", [
    (["layer0", "q_norm", "scale"], (3.0, 0.3)),       # drawn otherwise
    (["layer0", "k_norm", "scale"], (1.0, 0.1)),       # the view's own
    (["layer0", "attn_norm", "scale"], None),          # the common table's
])
def test_the_logits_tool_redraws_only_the_leaves_it_is_told(names, want):
    """``tools/moe_logits_check.py --seeding q_norm/scale=3.0:0.3``: that
    leaf at another mean and std, every other as the view draws it."""
    from benchmark.views import keye
    from tools.moe_logits_check import seeding_with

    ask = seeding_with(keye.seeding, ["q_norm/scale=3.0:0.3"])
    assert ask(names, (16,)) == want
    assert seeding_with(keye.seeding, []) is keye.seeding


def test_the_cells_files_say_what_the_program_is_given():
    """The configuration file's published keys, its ``decoder_config`` and
    its deployment agree with each other and with the issue's cut."""
    import json

    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "keye-vl2-30b-ep8-1chip.json").read_text())
    dc, llm = cfg["program"]["decoder_config"], cfg["deployment"]["llm"]
    sa = cfg["sa_config"]
    assert (dc["index_topk"], dc["index_heads"], dc["index_head_dim"]) == (
        sa["topk"], sa["indexer_num_heads"], sa["indexer_head_dim"])
    assert (dc["d_model"], dc["num_heads"], dc["num_kv_heads"],
            dc["head_dim"], dc["mlp_dim"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["moe_intermediate_size"])
    assert dc["num_experts"] == cfg["expert_parallel"]["router_width"] == 128
    assert dc["moe_held_experts"] == cfg["num_experts"] == 16
    assert dc["vocab_size"] == cfg["vocab_size"] == 151936 // 8
    assert dc["num_layers"] == cfg["num_hidden_layers"] == 8
    assert dc["rms_eps"] == cfg["rms_norm_eps"] == 1e-6
    assert dc["rope_theta"] == cfg["rope_theta"] == 1e7
    assert set(cfg["reduced"]) == set(cfg["reduced_from"])
    assert llm["kv_pool_pages"] * llm["page_size"] == (
        llm["num_slots"] * llm["max_len"])
    model = CausalLM(DecoderConfig(**dc), name="keye", dtype=jnp.bfloat16)
    pool = jax.eval_shape(lambda: model.make_paged_cache(
        llm["num_slots"], llm["kv_pool_pages"], llm["page_size"],
        llm["max_len"]))
    gb = lambda x: x.size * x.dtype.itemsize / 1e9      # noqa: E731
    assert gb(pool.k) + gb(pool.v) == pytest.approx(7.25, abs=0.01)
    assert gb(pool.index_k) == pytest.approx(0.906, abs=0.001)
    weights = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))) * 2 / 1e9
    assert weights == pytest.approx(1.71, abs=0.01)
    traffic = json.loads((ROOT / "benchmark" / "traffic"
                          / "longdoc-batch.json").read_text())
    assert traffic["clients"] == llm["num_slots"]
    assert (traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"]
            <= llm["max_len"])
