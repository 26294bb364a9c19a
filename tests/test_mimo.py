"""One rank's share of a MiMo-V2-Flash-shaped model on the normal path,
against the plain reference the benchmark keeps
(``benchmark/reference/mimo.py``, read through ``benchmark/views/mimo.py``;
both loaded by path: they import nothing of the program): state BY LAYER
KIND (the full layers' pages with their own KV head count, the window
layers' ring of their window a slot, read through an arithmetic table), keys
wider than values, rotary positions over part of a head with a base a kind,
a learned sink in the window layers' softmax, a value scale, a dense first
layer and sigmoid top-k routing with a selection bias over ALL experts of
which some are held here. CPU, float32, seeded weights, tiny widths that
keep the published inequalities (7 layers GLLLLGL, d 64, 8 heads of 24 for
q and k and 16 for v, 2 KV heads in a full layer and 4 in a window layer,
window 8, 8 experts top-2 of which 4 are held), compared on LOGITS.
"""

import dataclasses
import importlib.util
import json
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
from ray_dynamic_batching_tpu.engine.paging import PageAllocator
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.models.kv_state import PagedKVCache, ring_table
from ray_dynamic_batching_tpu.models.moe import MoEBlock, RoutingRule
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import decode_attention, kind_attention
from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

ROOT = Path(__file__).resolve().parents[1]

WINDOW, E, HELD, TOP_K = 8, 8, 4, 2
PATTERN = "GLLLLGL"
TINY = DecoderConfig(
    vocab_size=256, d_model=64, num_layers=7, num_heads=8, num_kv_heads=2,
    head_dim=24, v_head_dim=16, mlp_dim=32, max_seq_len=1024,
    rope_theta=5e6, rope_dim=8, sliding_window=WINDOW,
    layer_pattern=PATTERN, sliding_kv_heads=4, sliding_rope_theta=1e4,
    sliding_sink=True, value_scale=0.707,
    num_dense_layers=1, dense_mlp_dim=96, num_experts=E, moe_top_k=TOP_K,
    moe_renormalize=True, moe_scoring="sigmoid", moe_selection_bias=True,
    moe_first_expert=HELD, moe_held_experts=HELD,
)
SIZES = {
    "layernorm_epsilon": 1e-5, "head_dim": 24,
    "partial_rotary_factor": 0.334,        # int(24 x 0.334) = 8
    "hybrid_layer_pattern": [int(c == "L") for c in PATTERN],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "rope_theta": 5e6, "swa_rope_theta": 1e4, "sliding_window": WINDOW,
    "attention_value_scale": 0.707, "num_experts_per_tok": TOP_K,
    "routed_scaling_factor": None,
    "expert_parallel": {"first_expert": HELD},
    "program": {"decoder_config": {"num_layers": 7}},
}

# Program and reference both compute in float32 here, so they differ by
# summation order alone (worst gap read: 4e-6 on logits whose spread is 1);
# every wrong piece of arithmetic below moves them by hundredths or more.
TOL = 1e-4


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "mimo_" + Path(rel).stem + "_" + Path(rel).parent.name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/mimo.py")


@pytest.fixture(scope="module")
def view():
    return _load("benchmark/views/mimo.py")


def _seeded(model, view, seed=0):
    """``model.init``'s tree with the sinks and the selection bias drawn as
    the view's seeding rule says: at zero, dropping either would be (nearly)
    the same function."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        rule = view.seeding(names, tuple(x.shape))
        if rule is not None and names[-1] in ("sink", "selection_bias"):
            k = jax.random.fold_in(
                key, zlib.crc32("/".join(names).encode()) % (2 ** 31))
            return rule[0] + rule[1] * jax.random.normal(k, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def model():
    return CausalLM(TINY, name="mimo_tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model, view):
    return _seeded(model, view)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(7).integers(1, TINY.vocab_size, 300)


@pytest.fixture(scope="module")
def other():
    return np.random.default_rng(8).integers(1, TINY.vocab_size, 300)


def _full(model, params, tokens):
    t = jnp.asarray(tokens, jnp.int32)[None]
    return np.asarray(model.apply(params, t, jnp.ones_like(t))[0])


def _gap(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


@pytest.fixture(scope="module")
def want(params, view, ref, tokens):
    return np.asarray(ref.logits(view.view(params, SIZES), tokens, SIZES))


def test_full_forward_matches_the_reference(model, params, tokens, want):
    assert _gap(_full(model, params, tokens), want) < TOL


def test_a_layer_asks_for_its_kind_in_one_place():
    kinds = [TINY.layer_kind(i) for i in range(7)]
    assert [k.window for k in kinds] == [0, 8, 8, 8, 8, 0, 8]
    assert [k.kv_heads for k in kinds] == [0, 4, 4, 4, 4, 0, 4]
    assert [k.rope_theta for k in kinds] == [0, 1e4, 1e4, 1e4, 1e4, 0, 1e4]
    assert [k.sink for k in kinds] == [False] + [True] * 4 + [False, True]
    assert [k.ring for k in kinds] == [k.sink for k in kinds]
    # its place in its own kind's pool
    assert [k.pool_layer for k in kinds] == [0, 0, 1, 2, 3, 1, 4]
    assert (TINY.layers_of(False), TINY.layers_of(True)) == (2, 5)
    # every other model: one pool, the layer's own index
    plain = DecoderConfig(vocab_size=8, d_model=64, num_layers=2,
                          num_heads=4, num_kv_heads=4, mlp_dim=96)
    assert plain.layer_kind(1).pool_layer == -1
    assert plain.v_head_dim == plain.head_dim == 16


# --- chunked prefill through both pools, then batched decode ------------------
SLOTS = 4


def _serve(model, params, tokens, other, page, W, prompt, before=0):
    """In slot 1: first ``before`` tokens of ``other`` (a tenant whose ring
    rows stay behind: nothing is cleared), then ``tokens``: ``prompt`` of
    them prefilled in W-wide chunks through the slot's page-table row and
    its ring table, the rest decoded one token at a time in a batch of
    SLOTS slots of which slot 3 decodes ``other`` and two are idle. Returns
    the logits of every decoded position and of each chunk's last."""
    n_entries = TINY.max_seq_len // page
    n_pages = 2 * n_entries
    chunk = jax.jit(model.prefill_chunk_paged)
    step = jax.jit(model.decode_step_paged)
    cache = model.make_paged_cache(SLOTS, n_pages, page, TINY.max_seq_len,
                                   widest_chunk=W)
    R = cache.ring_pages
    assert cache.ring_k.shape[1] == SLOTS * R
    rng = np.random.default_rng(3)
    tables = np.stack([rng.permutation(n_pages)[:n_entries],
                       np.arange(n_entries)]).astype(np.int32)
    tables[1] = np.setdiff1d(np.arange(n_pages), tables[0])[:n_entries]
    rings = ring_table(np.asarray([1, 3], np.int32), R, n_entries)
    logits = {}

    def fill(rows, upto, keep):
        nonlocal cache
        for start in range(0, upto, W):
            toks = np.zeros((2, W), np.int32)
            mask = np.zeros((2, W), np.int32)
            for r, row in enumerate(rows):
                piece = row[start:min(start + W, upto)]
                toks[r, :len(piece)] = piece
                mask[r, :len(piece)] = 1
            last = int(mask[0].sum()) - 1
            taken, new = chunk(
                params, jnp.asarray(toks), jnp.asarray(mask), cache,
                jnp.asarray(tables), jnp.full((2,), start, jnp.int32),
                jnp.asarray([last, 0], jnp.int32),
                ring_tables=jnp.asarray(rings))
            if keep:
                logits[start + last] = np.asarray(taken[0])
            cache = cache.replace(k=new.k, v=new.v, ring_k=new.ring_k,
                                  ring_v=new.ring_v)

    if before:
        fill([other[:before], other[:before]], before, keep=False)
    fill([tokens[:prompt], other[:prompt]], prompt, keep=True)
    sentinel = np.full((n_entries,), n_pages, np.int32)
    cache = cache.replace(
        page_table=jnp.asarray(
            np.stack([sentinel, tables[0], sentinel, tables[1]])),
        lengths=jnp.asarray([0, prompt, 0, prompt], jnp.int32))
    active = jnp.asarray([False, True, False, True])
    for pos in range(prompt, len(tokens)):
        feed = jnp.asarray([0, tokens[pos], 0, other[pos]],
                           jnp.int32)[:, None]
        out, cache = step(params, feed, cache, active)
        logits[pos] = np.asarray(out[1])
    return logits, R


@pytest.mark.parametrize("backend, page, W, prompt", [
    ("xla", 4, 8, 270), ("pallas", 128, 16, 250)])
def test_chunks_then_batched_decode_through_both_pools_match_the_reference(
        backend, page, W, prompt, model, params, tokens, other, want):
    """A slot that held another tenant's 90 positions (a ring of 5 pages of
    4 wrapped four times; nothing cleared), then ``prompt`` positions
    through chunks of 8 (of 16 under "pallas": past the kernel's 8 rows)
    beside another sequence (the ring wraps 13 more
    times: pages of 4; or once, at 256: pages of 128) and the rest through
    single-token steps beside idle slots: the blocked walk on the CPU, or
    the paged kernel, interpreted, handed each kind's pool, table, head
    count, value width and sink; against the reference's ONE full
    forward."""
    attn_ops.set_attention_backend(backend)
    attn_ops.clear_attention_paths()
    decode_attention.clear_decode_paths()
    try:
        served, R = _serve(model, params, tokens, other, page, W, prompt,
                           before=90)
    finally:
        attn_ops.set_attention_backend("auto")
    assert R == {4: 5, 128: 2}[page]
    assert set(range(prompt, 300)) <= set(served)
    assert max(_gap(row, want[pos]) for pos, row in served.items()) < TOL
    paths = attn_ops.attention_paths()
    # each dispatch says its kind: window, pool heads, v rows, sink
    assert {(p.sliding, p.kv_shape[3], p.sink) for p in paths} == {
        (0, 2, False), (WINDOW, 4, True)}
    assert {p.v_dim for p in paths} == {128}
    assert {p.path for p in paths if p.q_shape[1] > 1} == {
        attn_ops.PATH_BLOCKED}
    if backend == "pallas":
        assert {p.path for p in paths if p.q_shape[1] == 1} == {
            attn_ops.PATH_PAGED_KERNEL}
        took = {(p.sliding, p.table_width, p.kv_heads, p.sink)
                for p in decode_attention.decode_paths()}
        assert took == {(WINDOW, 2, 4, True),
                        (0, TINY.max_seq_len // page, 2, False)}
        # both kinds' blocks are narrow at these tiny head counts (2 and
        # 4 rows a position, a lane tile wide): each walk, the window's
        # two columns behind its sink too, folds a group of pages an
        # update (ISSUE 52; the published widths' 2 and 1:
        # ``tests/test_lint.py -k every_configurations_walk``)
        assert {(p.kv_heads, p.pages, p.depth)
                for p in decode_attention.decode_paths()} == {
            (4, 2, 3), (2, 2, 3)}


# --- knock-outs: each wrong arithmetic must FAIL the tolerance -----------------
@pytest.mark.parametrize("wrong", [
    "no_sink", "value_scale_one", "rotary_over_the_whole_head",
    "thetas_swapped", "window_one_short", "window_one_long",
    "one_kv_head_count", "selection_bias_dropped"])
def test_wrong_arithmetic_fails_the_tolerance(
        wrong, model, params, tokens, want):
    cfg = {
        "no_sink": dict(sliding_sink=False),
        "value_scale_one": dict(value_scale=1.0),
        "rotary_over_the_whole_head": dict(rope_dim=0),
        "thetas_swapped": dict(rope_theta=1e4, sliding_rope_theta=5e6),
        "window_one_short": dict(sliding_window=WINDOW - 1),
        "window_one_long": dict(sliding_window=WINDOW + 1),
        "selection_bias_dropped": dict(moe_selection_bias=False),
        "one_kv_head_count": {},
    }[wrong]
    served_params = params
    if wrong == "one_kv_head_count":
        # the window layers grouped as the full layers are: 2 KV heads
        cfg = dict(sliding_kv_heads=0)
        served_params = jax.tree_util.tree_map(lambda x: x, params)
        for i, c in enumerate(PATTERN):
            if c == "L":
                lp = served_params["params"][f"layer{i}"]
                lp["k"] = {"kernel": lp["k"]["kernel"][:, :2]}
                lp["v"] = {"kernel": lp["v"]["kernel"][:, :2]}
    served = CausalLM(dataclasses.replace(TINY, **cfg), name=wrong,
                      dtype=jnp.float32)
    assert _gap(_full(served, served_params, tokens), want) > 10 * TOL


def test_a_sink_on_the_full_layers_too_fails_the_tolerance(
        model, params, view, ref, tokens, want):
    """The program has no switch for it; the reference does: read with
    ``add_full_attention_sink_bias`` true (the full layers given sinks as
    the window layers' are drawn), it is another function."""
    weights = view.view(params, SIZES)
    rng = np.random.default_rng(5)
    for layer in weights["layers"]:
        layer.setdefault("sink", jnp.asarray(rng.normal(size=(8,)),
                                             jnp.float32))
    sunk = ref.logits(weights, tokens,
                      dict(SIZES, add_full_attention_sink_bias=True))
    assert _gap(sunk, want) > 10 * TOL


def test_bfloat16_fails_the_tolerance(params, tokens, want):
    low = CausalLM(TINY, name="mimo_tiny_bf16", dtype=jnp.bfloat16)
    assert _gap(_full(low, params, tokens), want) > 10 * TOL


# --- the blocked walk and the kernel, side by side ---------------------------
@pytest.mark.parametrize("K, G, sliding, rows", [
    (4, 16, 0, 1), (8, 8, 128, 1), (8, 2, 128, 3), (4, 2, 0, 3)])
def test_kernel_and_blocked_walk_agree_with_a_sink_and_narrow_values(
        K, G, sliding, rows):
    """The published row widths (k 192 held as 256 lanes, v 128) at both
    kinds' head counts, the per-head form (4 heads) and the flat one (8)."""
    B, ps, NP, L, H = 3, 128, 4, 2, 192
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, rows, K * G, H), jnp.float32)
    k = jax.random.normal(ks[1], (L, B * NP, ps, K, 256), jnp.float32)
    k = k.at[..., H:].set(0)
    v = jax.random.normal(ks[2], (L, B * NP, ps, K, 128), jnp.float32)
    sink = 2 * jax.random.normal(ks[3], (K * G,), jnp.float32)
    table = jnp.arange(B * NP, dtype=jnp.int32).reshape(B, NP)[:, ::-1]
    lengths = jnp.asarray([5, 300, 470], jnp.int32)
    why = []
    out = decode_attention.paged_decode_attention(
        q, k, v, table, lengths, layer=1, sliding=sliding, sink=sink,
        v_dim=128, interpret=True, why=why)
    assert out is not None, why
    walk = kind_attention.paged(q, k, v, table, lengths, 1,
                                sliding=sliding, sink=sink)
    assert out.shape == walk.shape == (B, rows, K * G, 128)
    assert _gap(out, walk) < 1e-5
    # ... and the sink is no no-op
    bare = kind_attention.paged(q, k, v, table, lengths, 1, sliding=sliding)
    assert _gap(bare, walk) > 1e-2


def test_the_kernel_still_declines_unequal_pools_it_was_not_told_of():
    q = jnp.zeros((2, 1, 8, 128))
    k = jnp.zeros((1, 4, 128, 4, 256))
    v = jnp.zeros((1, 4, 128, 4, 128))
    why = []
    assert decode_attention.paged_decode_attention(
        q, k, v, jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32),
        why=why) is None
    assert "do not group over" in why[0]


# --- the ranks' parts of an expert layer ---------------------------------------
D_BLOCK, F_BLOCK, RANKS = 32, 16, 4


def _block(first, held):
    return MoEBlock(
        d_model=D_BLOCK, mlp_dim=F_BLOCK, num_experts=E, top_k=TOP_K,
        rule=RoutingRule("sigmoid", True, True, 1.0), first_expert=first,
        held_experts=held, shared_dim=0, dtype=jnp.float32)


def test_the_ranks_shares_add_up_to_the_uncut_layer(ref):
    """8 experts over 4 ranks of 2: every rank's partial result against the
    reference given that share, and their sum against the reference's whole
    layer (no shared expert to count once)."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 24, D_BLOCK)), jnp.float32)
    p = _block(0, E).init(jax.random.PRNGKey(5), x)["params"]
    p = dict(p, selection_bias=jnp.asarray(
        0.1 * rng.normal(size=(E,)), jnp.float32))
    w = {"ln2_g": jnp.ones((D_BLOCK,)), "w_router": p["router"]["kernel"],
         "router_bias": p["selection_bias"], "we_up": p["wi"],
         "we_gate": p["wg"], "we_down": p["wo"]}
    flat = x.reshape(-1, D_BLOCK)
    h = ref._rms(flat, w["ln2_g"], 1e-5).reshape(x.shape)
    held = E // RANKS
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.experts(flat, w, top_k=TOP_K, scale=1.0, first=0,
                                  eps=1e-5)
        parts = []
        for r in range(RANKS):
            cut = slice(r * held, (r + 1) * held)
            part = np.asarray(_block(r * held, held).apply(
                {"params": dict(p, wi=p["wi"][cut], wg=p["wg"][cut],
                                wo=p["wo"][cut])}, h)).reshape(-1, D_BLOCK)
            mine, _, _ = ref.experts(
                flat, dict(w, we_up=w["we_up"][cut],
                           we_gate=w["we_gate"][cut],
                           we_down=w["we_down"][cut]),
                top_k=TOP_K, scale=1.0, first=r * held, eps=1e-5)
            assert _gap(np.asarray(flat) + part, mine) < TOL
            parts.append(part)
    assert _gap(np.asarray(flat) + sum(parts), whole) < TOL
    assert _gap(np.asarray(flat) + parts[0], whole) > 100 * TOL


# --- bytes: the arrays, the counts, the allocator -------------------------------
def test_pool_bytes_are_the_arrays_and_the_allocator_counts_full_pages_only(
        model):
    page, max_len, slots = 128, 1024, 4
    n = max_len // page
    cache = model.make_paged_cache(slots, slots * n, page, max_len,
                                   widest_chunk=8)
    R = cache.ring_pages
    assert R == 2                  # window 8 + 8 rows on pages of 128
    assert cache.k.shape == (2, slots * n, page, 2, 128)        # full: 2 x 2
    assert cache.v.shape == cache.k.shape
    assert cache.ring_k.shape == (5, slots * R, page, 4, 128)   # window: 5 x 4
    assert cache.k_scale is None and cache.index_k is None
    one = model.make_paged_cache(1, n, page, max_len, widest_chunk=8)
    arrays = sum(x.nbytes for x in (one.k, one.v, one.ring_k, one.ring_v))
    dep = LLMDeployment("mimo_tiny", model=model, page_size=page,
                        prompt_buckets=[8])
    assert dep.pool_bytes_per_slot(model, max_len) == arrays
    # what the model NEEDS: a full layer a position, a window layer its
    # window, at the heads' true widths (rows here are padded 24 -> 128)
    need = 4 * (24 + 16) * (2 * max_len * 2 + 5 * WINDOW * 4)
    assert model.kv_bytes_per_slot(max_len) == need
    assert arrays == 4 * 2 * 128 * page * (2 * n * 2 + 5 * R * 4)
    # the published widths: 6 KiB a position held in the pool, 6 ring pages
    big = DecoderConfig(
        vocab_size=8, d_model=4096, num_layers=7, num_heads=64,
        num_kv_heads=4, head_dim=192, v_head_dim=128, mlp_dim=8,
        sliding_window=128, layer_pattern=PATTERN, sliding_kv_heads=8)
    pool = jax.eval_shape(lambda: PagedKVCache.zeros(
        big, 40, 5760, 128, 18432, widest_chunk=512))
    assert (pool.k.shape, pool.v.shape) == (
        (2, 5760, 128, 4, 256), (2, 5760, 128, 4, 128))
    assert pool.ring_k.shape == (5, 40 * 6, 128, 8, 256)
    assert pool.ring_v.shape == (5, 40 * 6, 128, 8, 128)
    held = sum(np.prod(x.shape) * 2 for x in (pool.k, pool.v))
    assert held == 6 * 1024 * 5760 * 128
    # the ring table is arithmetic: column c of slot b -> b * R + c % R
    table = ring_table(np.asarray([0, 3], np.int32), 6, 144)
    assert table.shape == (2, 144)
    assert table[1, :8].tolist() == [18, 19, 20, 21, 22, 23, 18, 19]
    assert table.max() == 23 and table[0].max() == 5


@pytest.mark.parametrize("option, value", [
    ("prefix_cache_size", 4), ("session_cache_size", 4),
    ("host_spill_pages", 4), ("draft", True), ("int8", True)])
def test_what_cannot_work_with_a_ring_is_refused_when_built(
        option, value, model, params):
    kw = dict(num_slots=2, max_len=256, prompt_buckets=[8], page_size=128)
    served = model
    if option == "draft":
        kw.update(draft_model=model, draft_params=params)
    elif option == "int8":
        served = CausalLM(TINY, name="mimo_i8", dtype=jnp.float32,
                          kv_dtype=jnp.int8)
    else:
        kw[option] = value
        if option == "host_spill_pages":
            kw["prefix_cache_size"] = 0
    with pytest.raises(ValueError, match="state by layer kind"):
        DecodeEngine(served, params, RequestQueue(served.name, max_len=8),
                     **kw)


@pytest.mark.parametrize("buckets, pages", [
    ([8], 2), ([8, 128], 3), ([128, 256, 512], 4)])
def test_a_slots_ring_is_sized_for_the_engines_widest_chunk(
        buckets, pages, model, params):
    """No key says it: the ring holds what the rows of the widest prompt
    bucket attend between them (window 8 on pages of 128; the table's 4
    columns at most), and the deployment prices the same arrays."""
    engine = DecodeEngine(
        model, params, RequestQueue(model.name, max_len=8), num_slots=2,
        max_len=512, prompt_buckets=buckets, page_size=128)
    assert engine.snapshot()["kv_pool"]["ring_pages_per_slot"] == pages
    cache = engine._cache
    assert cache.ring_pages == pages
    dep = LLMDeployment("mimo_tiny", model=model, page_size=128,
                        prompt_buckets=buckets)
    assert dep.pool_bytes_per_slot(model, 512) == sum(
        x.nbytes for x in (cache.k, cache.v, cache.ring_k, cache.ring_v)
    ) // 2


def test_other_refusals_name_their_reason(model):
    # state is by layer kind where the kinds' heads or k and v's widths
    # differ, and nowhere else: nothing switches it
    base = dict(vocab_size=8, d_model=64, num_layers=2, num_heads=4,
                num_kv_heads=2, mlp_dim=8)
    assert TINY.kv_by_kind
    assert not DecoderConfig(**base, sliding_window=8).kv_by_kind
    assert DecoderConfig(**base, sliding_window=8,
                         sliding_kv_heads=4).kv_by_kind
    for kinds in (dict(sliding_kv_heads=4), dict(v_head_dim=8)):
        with pytest.raises(ValueError, match="no layer slides"):
            DecoderConfig(**base, **kinds)
    with pytest.raises(ValueError, match="pass widest_chunk"):
        model.make_paged_cache(2, 4, 128, 256)
    with pytest.raises(NotImplementedError, match="paged cache's"):
        m = CausalLM(TINY, name="slab", dtype=jnp.float32)
        p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: m.make_cache(2, 16))
        jax.eval_shape(m.decode_step, p, jnp.zeros((2, 1), jnp.int32),
                       cache, jnp.ones((2,), bool))


# --- the engine -------------------------------------------------------------------
def test_engine_serves_it_past_ring_wraps_and_a_slots_reuse(
        model, params, view, ref, tokens, other):
    """Through ``DecodeEngine``: ONE slot, so the second request reuses the
    first's (its ring as the first left it); prompts of 290 and 270 in
    chunks of 8 over a ring of 2 pages of 128 (each wraps once), greedy
    tokens against the reference's top-1."""
    queue = RequestQueue(model.name, max_len=64)
    engine = DecodeEngine(
        model, params, queue, num_slots=1, max_len=512,
        prompt_buckets=[8], paged=True, page_size=128, kv_pool_pages=4,
        decode_horizon=2, max_admissions_per_step=1,
        default_max_new_tokens=6, prefill_token_budget=64)
    assert engine._allocator.free_pages == 4      # full-layer pages only
    prompts = [[int(t) for t in tokens[:290]], [int(t) for t in other[:270]]]
    for prompt in prompts:
        req = Request(model=model.name, slo_ms=60_000.0, payload={
            "tokens": prompt, "max_new_tokens": 6})
        queue.add_request(req)
        engine.run_until_idle(timeout_s=600)
        out = list(req.future.result(timeout=5).tokens)
        assert len(out) == 6
        want = np.asarray(ref.logits(view.view(params, SIZES), prompt + out,
                                     SIZES))
        for j, tok in enumerate(out):
            row = want[len(prompt) - 1 + j]
            assert row.max() - row[tok] < TOL
        assert engine._allocator.free_pages == 4  # all handed back
    scans = [t for t in engine.turns if t.kind == "turn"]
    # one busy slot at 270-296 positions: 3 of its table's 4 pages live in
    # a full layer; a window layer walks 1 or 2 of its 2 ring columns
    assert {t.kv_full_pages_live for t in scans} == {3}
    summary = engine.turn_summary()
    assert summary["kv_full_live_page_share"] == pytest.approx(3 / 4)
    snap = engine.snapshot()["kv_pool"]
    assert snap["ring_pages_per_slot"] == 2
    assert snap["layer_table_widths"] == [4, 2, 2, 2, 2, 4, 2]
    cache = engine._cache
    assert snap["bytes_by_kind"] == {
        "full": cache.k.nbytes + cache.v.nbytes,
        "ring": cache.ring_k.nbytes + cache.ring_v.nbytes}
    assert snap["resident_bytes"] == sum(snap["bytes_by_kind"].values())
    with pytest.raises(ValueError, match="page fabric"):
        engine.request_migration("r", lambda parcel: True)


def test_summarize_turns_of_a_one_pool_ring_has_no_per_kind_keys():
    plain = [Turn("turn", 0.0, 1.0, 2.0, 3.0, 8, 0, 4, 0, 0, 0, 0, False,
                  kv_pages_live=4)] * 3
    out = summarize_turns(plain, num_slots=4, table_entries=4)
    assert "kv_full_pages_live" not in out
    assert plain[0].kv_full_pages_live == 0


# --- this model's own programs, pinned as the others' are ------------------------
def _equations(j):
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


def test_the_cells_own_programs_trace_to_a_pinned_size():
    """``mimo-v2-flash-ep16-1chip`` at its file's widths and deployment: the
    decode program and the widest chunk program, counted as
    ``tests/test_keye.py`` counts the other cells'; the pool's pytree has
    its four leaves and the ring's two."""
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "mimo-v2-flash-ep16-1chip.json").read_text())
    llm = cfg["deployment"]["llm"]
    m = CausalLM(DecoderConfig(**cfg["program"]["decoder_config"]),
                 name="m", dtype=jnp.bfloat16)
    B, ps = llm["num_slots"], llm["page_size"]
    W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
    cache = jax.eval_shape(lambda: m.make_paged_cache(
        B, llm["kv_pool_pages"], ps, llm["max_len"], widest_chunk=W))
    assert len(jax.tree_util.tree_leaves(cache)) == 6
    assert cache.ring_pages == 6
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    decode = jax.make_jaxpr(m.decode_step_paged)(
        p, sds((B, 1), jnp.int32), cache, sds((B,), jnp.bool_))
    chunk = jax.make_jaxpr(
        lambda *a: m.prefill_chunk_paged(*a[:-1], ring_tables=a[-1]))(
        p, sds((2, W), jnp.int32), sds((2, W), jnp.int32), cache,
        sds((2, NP), jnp.int32), sds((2,), jnp.int32), sds((2,), jnp.int32),
        sds((2, NP), jnp.int32))
    assert (len(decode.jaxpr.eqns), _equations(decode.jaxpr)) == DECODE_EQNS
    assert (len(chunk.jaxpr.eqns), _equations(chunk.jaxpr)) == CHUNK_EQNS


# counted on the CPU (the blocked walk stands where the chip takes the kernel)
DECODE_EQNS = (1726, 2976)
CHUNK_EQNS = (1733, 2990)


@pytest.mark.parametrize("module", [
    "ray_dynamic_batching_tpu.models.decoder",
    "ray_dynamic_batching_tpu.models.causal_lm",
    "ray_dynamic_batching_tpu.engine.decode",
    "ray_dynamic_batching_tpu.serve.llm",
])
def test_importing_the_program_does_not_import_the_kind_module(module):
    import os
    import subprocess
    import sys

    code = (f"import sys, {module}; "
            "sys.exit('ray_dynamic_batching_tpu.ops.kind_attention' "
            "in sys.modules)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300).returncode == 0


def test_a_model_with_one_pool_has_no_ring():
    m = CausalLM(DecoderConfig(
        vocab_size=8, d_model=64, num_layers=2, num_heads=4, num_kv_heads=2,
        mlp_dim=8, sliding_window=8, layer_pattern="LG"), name="g",
        dtype=jnp.float32)
    cache = jax.eval_shape(lambda: m.make_paged_cache(2, 4, 128, 256))
    assert cache.ring_k is None and cache.ring_v is None
    assert len(jax.tree_util.tree_leaves(cache)) == 4
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    assert "sink" not in p["params"]["layer0"]
    assert PageAllocator(4).free_pages == 4
