"""One rank's share of a K-EXAONE-shaped model on the normal path, against
the plain reference the benchmark keeps (``benchmark/reference/kexaone.py``,
read through ``benchmark/views/kexaone.py``; both loaded by path: they import
nothing of the program): sliding-window layers beside full ones in one paged
pool, rotary positions on the sliding layers only, QK-norm per head,
``head_dim`` a field of its own, a dense first layer, sigmoid top-k routing
with a selection bias over ALL experts of which some are held here, and a
shared expert. CPU, float32, seeded weights, tiny widths (4 layers, d 64,
16/8 heads of 16, 16 experts top-4 of which 4 are held), compared on LOGITS.
"""

import dataclasses
import importlib.util
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models.causal_lm import (
    K_EXAONE_236B,
    CausalLM,
    routing_counters,
)
from ray_dynamic_batching_tpu.models.decoder import (
    DecoderConfig,
    paged_window_mask,
)
from ray_dynamic_batching_tpu.models.moe import MoEBlock, RoutingRule
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import decode_attention, tile_math
from ray_dynamic_batching_tpu.ops import moe as moe_ops

ROOT = Path(__file__).resolve().parents[1]

WINDOW, PAGE, MAX_LEN = 128, 128, 512
E, HELD, TOP_K = 16, 4, 4
RANKS = E // HELD
TINY = DecoderConfig(
    vocab_size=512, d_model=64, num_layers=4, num_heads=16, num_kv_heads=8,
    head_dim=16, mlp_dim=128, max_seq_len=MAX_LEN, rope_theta=1e6,
    qk_norm=True, qk_norm_per_head=True, sliding_window=WINDOW,
    layer_pattern="LLLG", rope_sliding_only=True, num_dense_layers=1,
    dense_mlp_dim=256, num_experts=E, moe_top_k=TOP_K, moe_renormalize=True,
    moe_scoring="sigmoid", moe_selection_bias=True, moe_gate_scale=2.5,
    moe_first_expert=HELD, moe_held_experts=HELD, moe_shared_experts=1,
)
SIZES = {
    "rms_norm_eps": 1e-5, "num_attention_heads": 16,
    "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1e6},
    "num_experts_per_tok": TOP_K, "routed_scaling_factor": 2.5,
    "sliding_window": WINDOW,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "expert_parallel": {"first_expert": HELD},
    "program": {"decoder_config": {"num_layers": 4}},
}

# Program and reference both compute in float32 here, so they differ by
# summation order alone (worst gap read: 8e-6 on logits whose spread is 1);
# every wrong piece of arithmetic below moves them by hundredths or more.
TOL = 1e-4


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "kexaone_" + Path(rel).stem + "_" + Path(rel).parent.name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/kexaone.py")


@pytest.fixture(scope="module")
def view():
    return _load("benchmark/views/kexaone.py")


def _seeded(model, view, seed=0):
    """``model.init``'s tree with the q/k norm scales and the selection
    bias drawn as the view's seeding rule says: with scales of one and a
    bias of zero, dropping either would be the same function."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        rule = view.seeding(names, tuple(x.shape))
        if rule is not None and names[-1] in ("scale", "selection_bias"):
            k = jax.random.fold_in(
                key, zlib.crc32("/".join(names).encode()) % (2 ** 31))
            return rule[0] + rule[1] * jax.random.normal(k, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def model():
    return CausalLM(TINY, name="kexaone_tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model, view):
    return _seeded(model, view)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(7).integers(1, TINY.vocab_size, 300)


def _full(model, params, tokens):
    t = jnp.asarray(tokens, jnp.int32)[None]
    return np.asarray(model.apply(params, t, jnp.ones_like(t))[0])


def _gap(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


@pytest.fixture(scope="module")
def want(params, view, ref, tokens):
    return np.asarray(ref.logits(view.view(params, SIZES), tokens, SIZES))


# --- the full forward, and (f) the head's own width ---------------------------
def test_full_forward_matches_the_reference(model, params, tokens, want):
    assert _gap(_full(model, params, tokens), want) < TOL


def test_head_dim_is_a_field_not_the_quotient(model, params):
    """(f) 16 heads of 16 on d 64: the quotient would be 4."""
    assert TINY.head_dim == 16 != TINY.d_model // TINY.num_heads
    layer = params["params"]["layer0"]
    assert layer["q"]["kernel"].shape == (64, 16, 16)
    assert layer["k"]["kernel"].shape == (64, 8, 16)
    assert layer["q_norm"]["scale"].shape == (16,)       # one head's width
    assert model.make_cache(2, 32).k.shape == (4, 2, 32, 8, 16)
    assert model.kv_bytes_per_slot(32) == 2 * 4 * 32 * 8 * 16 * 4
    # 0 still means the quotient, and the published preset has its own
    assert DecoderConfig(vocab_size=8, d_model=64, num_layers=1, num_heads=4,
                         num_kv_heads=4, mlp_dim=8).head_dim == 16
    assert K_EXAONE_236B.head_dim == 128 != 6144 // 64


def test_a_layer_asks_for_its_kind_in_one_place():
    kinds = [TINY.layer_kind(i) for i in range(8)]
    assert [k.window for k in kinds] == [128, 128, 128, 0] * 2
    assert [k.rope for k in kinds] == [True, True, True, False] * 2
    assert [k.sparse for k in kinds] == [False] + [True] * 7
    assert [k.mlp_dim for k in kinds] == [256] + [128] * 7
    dense = DecoderConfig(vocab_size=8, d_model=64, num_layers=2,
                          num_heads=4, num_kv_heads=4, mlp_dim=96)
    assert dense.layer_kind(1) == dataclasses.replace(
        kinds[3], mlp_dim=96, sparse=False, rope=True)
    k = K_EXAONE_236B
    assert [k.layer_kind(i).window for i in range(4)] == [128, 128, 128, 0]
    assert (k.layer_kind(0).mlp_dim, k.layer_kind(1).mlp_dim) == (18432, 2048)


# --- (b) chunked prefill through the paged pool, then batched decode ----------
N_PAGES, SLOTS, W = 12, 4, 64
PROMPT = 290          # crosses the window (128), two page edges, four chunk
PAGES_A = [3, 7, 1, 9]          # edges; the last chunk holds 34 tokens
PAGES_B = [4, 8, 0, 11]
OTHER = 150


def _serve(model, params, tokens, other):
    """``tokens`` (and ``other`` beside it) prefilled in W-wide chunks
    through page tables, then decoded one token at a time in a batch of
    SLOTS slots of which two are inactive. Returns the logits of the last
    chunk's positions and of every decoded one, from ``first`` on."""
    chunk = jax.jit(model.prefill_chunk_paged,
                    static_argnames=("moe_counters",))
    step = jax.jit(model.decode_step_paged,
                   static_argnames=("moe_counters",))
    cache = model.make_paged_cache(SLOTS, N_PAGES, PAGE, MAX_LEN)
    tables = jnp.asarray([PAGES_A, PAGES_B], jnp.int32)
    rows = [np.asarray(tokens[:PROMPT]), np.asarray(other[:OTHER])]
    logits, first = {}, (PROMPT - 1) // W * W
    for start in range(0, PROMPT, W):
        toks = np.zeros((2, W), np.int32)
        mask = np.zeros((2, W), np.int32)
        for r, row in enumerate(rows):
            piece = row[start:start + W]
            toks[r, :len(piece)] = piece
            mask[r, :len(piece)] = 1
        starts = jnp.full((2,), start, jnp.int32)
        # every position of the last chunk and the last of each other one
        takes = (range(int(mask[0].sum())) if start == first else [W - 1])
        for j in takes:
            taken, new_cache = chunk(
                params, jnp.asarray(toks), jnp.asarray(mask), cache, tables,
                starts, jnp.asarray([j, 0], jnp.int32))
            logits[start + j] = np.asarray(taken[0])
        cache = cache.replace(k=new_cache.k, v=new_cache.v)
    sentinel = jnp.full((len(PAGES_A),), N_PAGES, jnp.int32)
    cache = cache.replace(
        page_table=jnp.stack([sentinel, tables[0], sentinel, tables[1]]),
        lengths=jnp.asarray([0, PROMPT, 0, OTHER], jnp.int32))
    active = jnp.asarray([False, True, False, True])
    for pos in range(PROMPT, len(tokens)):
        feed = jnp.asarray(
            [0, tokens[pos], 0, other[pos - PROMPT + OTHER]],
            jnp.int32)[:, None]
        out, cache = step(params, feed, cache, active)
        logits[pos] = np.asarray(out[1])
    return logits


@pytest.fixture(scope="module")
def other():
    return np.random.default_rng(8).integers(1, TINY.vocab_size, 200)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prefill_in_chunks_then_batched_decode_matches_the_reference(
        backend, model, params, tokens, other, want):
    """290 positions through five chunks of 64 beside another sequence
    (the chunk program's staircase with its lower edge), 10 through
    single-token steps beside idle slots (the paged kernel, interpreted,
    handed the window's table columns; or the gather fallback's mask),
    against the reference's ONE full forward."""
    attn_ops.set_attention_backend(backend)
    decode_attention.clear_decode_paths()
    try:
        served = _serve(model, params, tokens, other)
    finally:
        attn_ops.set_attention_backend("auto")
    assert set(range(256, 300)) <= set(served)
    assert max(_gap(row, want[pos]) for pos, row in served.items()) < TOL
    if backend == "pallas":
        took = {(p.sliding, p.table_width)
                for p in decode_attention.decode_paths()}
        assert took == {(WINDOW, 2), (0, MAX_LEN // PAGE)}


# --- controls: each wrong arithmetic must FAIL the tolerance -------------------
def _ones(params, *names):
    out = jax.tree_util.tree_map(lambda x: x, params)
    for i in range(TINY.num_layers):
        for n in names:
            leaf = out["params"][f"layer{i}"][n]
            leaf["scale"] = jnp.ones_like(leaf["scale"])
    return out


@pytest.mark.parametrize("wrong", [
    "rope_on_full_layers", "window_dropped", "window_one_short",
    "qk_scale_dropped", "qk_norm_over_the_projection",
    "selection_bias_dropped", "gates_not_scaled", "gates_not_renormalised",
    "softmax_scores", "all_experts_here", "shared_expert_dropped"])
def test_wrong_arithmetic_fails_the_tolerance(
        wrong, model, params, tokens, want):
    """(c), (e): the tolerance tells each piece of the block's arithmetic."""
    cfg, served_params = TINY, params
    if wrong == "rope_on_full_layers":
        cfg = dataclasses.replace(TINY, rope_sliding_only=False)
    elif wrong == "window_dropped":
        cfg = dataclasses.replace(TINY, sliding_window=0,
                                  rope_sliding_only=False,
                                  pos="rope")
        # (positions on every layer is a second difference; the next case
        # moves the window's edge alone)
    elif wrong == "window_one_short":
        cfg = dataclasses.replace(TINY, sliding_window=WINDOW - 1)
    elif wrong == "qk_scale_dropped":
        served_params = _ones(params, "q_norm", "k_norm")
    elif wrong == "qk_norm_over_the_projection":
        cfg = dataclasses.replace(TINY, qk_norm_per_head=False)
        served_params = jax.tree_util.tree_map(lambda x: x, params)
        for i in range(TINY.num_layers):
            lp = served_params["params"][f"layer{i}"]
            lp["q_norm"] = {"scale": jnp.tile(lp["q_norm"]["scale"], 16)}
            lp["k_norm"] = {"scale": jnp.tile(lp["k_norm"]["scale"], 8)}
    elif wrong == "selection_bias_dropped":
        cfg = dataclasses.replace(TINY, moe_selection_bias=False)
    elif wrong == "gates_not_scaled":
        cfg = dataclasses.replace(TINY, moe_gate_scale=1.0)
    elif wrong == "gates_not_renormalised":
        cfg = dataclasses.replace(TINY, moe_renormalize=False)
    elif wrong == "softmax_scores":
        cfg = dataclasses.replace(TINY, moe_scoring="softmax")
    elif wrong == "all_experts_here":      # the neighbour rank's experts
        cfg = dataclasses.replace(TINY, moe_first_expert=0)
    elif wrong == "shared_expert_dropped":
        served_params = jax.tree_util.tree_map(lambda x: x, params)
        for i in range(1, TINY.num_layers):
            moe = served_params["params"][f"layer{i}"]["moe"]
            moe["shared_down"] = {
                "kernel": jnp.zeros_like(moe["shared_down"]["kernel"])}
    served = CausalLM(cfg, name=wrong, dtype=jnp.float32)
    assert _gap(_full(served, served_params, tokens), want) > 10 * TOL


def test_bfloat16_fails_the_tolerance(params, tokens, want):
    low = CausalLM(TINY, name="kexaone_tiny_bf16", dtype=jnp.bfloat16)
    assert _gap(_full(low, params, tokens), want) > 10 * TOL


# --- (c) the routing rule ------------------------------------------------------
def test_sigmoid_rule_selects_by_biased_scores_and_weighs_by_plain_ones(ref):
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(64, E)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(E,)), jnp.float32)
    rule = RoutingRule(scoring="sigmoid", selection_bias=True,
                       renormalize=True, scale=2.5)
    gates, idx, scores = rule.route(logits, bias, TOP_K)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-5)
    by_bias = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :TOP_K]
    by_score = np.argsort(-s, axis=-1)[:, :TOP_K]
    assert [set(r) for r in np.asarray(idx)] == [set(r) for r in by_bias]
    # the bias changes the choice (else this test could not tell it)
    assert sum(set(a) != set(b) for a, b in zip(by_bias, by_score)) > 16
    chosen = np.take_along_axis(s, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates), 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)
    # ... and is the reference's own rule (identity router: h = logits)
    r_idx, weight, _ = ref.route(logits, jnp.eye(E), bias, TOP_K, 2.5)
    assert [set(r) for r in np.asarray(r_idx)] == [set(r) for r in by_bias]
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weight), np.asarray(idx), axis=-1),
        np.asarray(gates), rtol=1e-5)


def test_softmax_rule_is_what_it_was():
    logits = jnp.asarray(np.random.default_rng(4).normal(size=(8, E)),
                         jnp.float32)
    gates, idx, scores = RoutingRule(renormalize=False).route(logits, None, 3)
    top, want_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 3)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(top))
    with pytest.raises(ValueError, match="scoring"):
        RoutingRule(scoring="tanh")


def test_the_reference_measures_each_experts_distance_from_the_edge(ref):
    """Selection scores 0.9 > 0.8 > 0.7 > 0.695 > 0.38 ... with top-3: the
    third and the fourth are 0.005 apart, every other expert further."""
    select = np.linspace(0.05, 0.5, E)[::-1].copy()
    select[:4] = [0.9, 0.8, 0.7, 0.695]
    logit = np.log(select / (1 - select))[None]          # sigmoid's inverse
    _, _, edge = ref.route(jnp.asarray(logit, jnp.float32), jnp.eye(E),
                           jnp.zeros((E,)), 3, 2.5)
    edge = np.asarray(edge)[0]
    np.testing.assert_allclose(edge[:4], [0.205, 0.105, 0.005, 0.005],
                               atol=1e-5)
    np.testing.assert_allclose(edge[4:], 0.7 - select[4:], atol=1e-5)


def test_the_reference_excuses_only_undecided_positions(
        ref, view, params, tokens, want):
    """``reference_check.undecided_score_gap``: rows where a held expert
    lies nearer the chosen set's edge than that come back flat (zeros: any
    token passes a comparison of margins), the others as computed; unset,
    or with the caller taking the distances itself, nothing is touched."""
    weights = view.view(params, SIZES)

    def sizes(gap):
        return dict(SIZES, reference_check={"undecided_score_gap": gap})

    edges = []
    np.testing.assert_array_equal(want, np.asarray(
        ref.logits(weights, tokens, sizes(1e9), edges=edges)))
    nearest = np.min([np.asarray(e) for e in edges], axis=0)
    assert len(edges) == 3 and nearest.shape == (300,) and (nearest > 0).all()
    for gap in (0.0, float(np.median(nearest)), 1e9):
        out = np.asarray(ref.logits(weights, tokens, sizes(gap)))
        flat = nearest < gap
        assert (out[flat] == 0).all()
        np.testing.assert_array_equal(out[~flat], want[~flat])
    assert flat.all() and 100 < (nearest < np.median(nearest)).sum() < 200


# --- (a) the shares add up ------------------------------------------------------
D_BLOCK, F_BLOCK = 128, 128     # widths the grouped kernel takes


def _block(first, held):
    return MoEBlock(
        d_model=D_BLOCK, mlp_dim=F_BLOCK, num_experts=E, top_k=TOP_K,
        rule=RoutingRule("sigmoid", True, True, 2.5), first_expert=first,
        held_experts=held, shared_dim=F_BLOCK, dtype=jnp.float32)


@pytest.fixture(scope="module")
def whole_layer(ref):
    """An UNCUT expert layer's weights (all E experts), its input, and the
    reference's result for the whole layer."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 24, D_BLOCK)), jnp.float32)
    p = _block(0, E).init(jax.random.PRNGKey(5), x)["params"]
    p = dict(p, selection_bias=jnp.asarray(
        0.1 * rng.normal(size=(E,)), jnp.float32))
    w = {"ln2_g": jnp.ones((D_BLOCK,)), "w_router": p["router"]["kernel"],
         "router_bias": p["selection_bias"], "we_up": p["wi"],
         "we_gate": p["wg"], "we_down": p["wo"],
         "ws_gate": p["shared_gate"]["kernel"],
         "ws_up": p["shared_up"]["kernel"],
         "ws_down": p["shared_down"]["kernel"]}
    flat = x.reshape(-1, D_BLOCK)
    # the block takes the NORMED stream; the reference norms it itself
    h = ref._rms(flat, w["ln2_g"], 1e-5)
    with jax.default_matmul_precision("highest"):
        whole, idx, _ = ref.experts(flat, w, top_k=TOP_K, scale=2.5,
                                    first=0, eps=1e-5)
    return p, w, h.reshape(x.shape), flat, np.asarray(whole), np.asarray(idx)


def _rank(p, r):
    cut = slice(r * HELD, (r + 1) * HELD)
    return dict(p, wi=p["wi"][cut], wg=p["wg"][cut], wo=p["wo"][cut])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_the_ranks_shares_add_up_to_the_uncut_layer(
        backend, whole_layer, ref):
    """Every rank's partial result (its 4 of the 16 experts, on the grouped
    path), the shared expert counted once, against the reference's whole
    layer; and each rank's alone against the reference given that share."""
    p, w, h, flat, whole, _ = whole_layer
    moe_ops.set_moe_backend(backend)
    moe_ops.clear_moe_paths()
    try:
        parts = [np.asarray(_block(r * HELD, HELD).apply(
            {"params": _rank(p, r)}, h)).reshape(-1, D_BLOCK)
            for r in range(RANKS)]
    finally:
        moe_ops.set_moe_backend("auto")
    assert {m.path for m in moe_ops.moe_paths()} == {
        moe_ops.PATH_KERNEL if backend == "pallas" else moe_ops.PATH_XLA}
    assert {(m.experts, m.rows) for m in moe_ops.moe_paths()} == {
        (HELD, 48 * TOP_K)}
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref._swiglu(
            ref._rms(flat, w["ln2_g"], 1e-5), w["ws_gate"], w["ws_up"],
            w["ws_down"]))
        for r, part in enumerate(parts):
            cut = slice(r * HELD, (r + 1) * HELD)
            mine, _, _ = ref.experts(
                flat, dict(w, we_up=w["we_up"][cut], we_gate=w["we_gate"][cut],
                           we_down=w["we_down"][cut]),
                top_k=TOP_K, scale=2.5, first=r * HELD, eps=1e-5)
            assert _gap(np.asarray(flat) + part, mine) < TOL
    total = np.asarray(flat) + sum(parts) - (RANKS - 1) * shared
    assert _gap(total, whole) < TOL
    # a rank alone is NOT the layer (else the sum would prove nothing)
    assert _gap(np.asarray(flat) + parts[0], whole) > 100 * TOL


def test_a_rank_with_no_row_at_all_gives_the_shared_expert_alone(
        whole_layer, ref):
    """One token whose 4 experts all live elsewhere: no group has a row,
    the grouped kernel has no real work item, and nothing undefined leaks
    out of its buffer."""
    p, w, h, flat, _, idx = whole_layer
    t = next(t for t in range(len(idx)) if not (set(idx[t]) & set(range(4))))
    one = h.reshape(-1, D_BLOCK)[t][None, None]
    moe_ops.set_moe_backend("pallas")
    try:
        got = np.asarray(_block(0, HELD).apply({"params": _rank(p, 0)}, one))
    finally:
        moe_ops.set_moe_backend("auto")
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref._swiglu(
            one[0], w["ws_gate"], w["ws_up"], w["ws_down"]))
    assert np.isfinite(got).all() and _gap(got[0], shared) < TOL


def test_counters_count_held_rows_and_all_pairs():
    idx = jnp.asarray([[[0, 5, 9, 15], [4, 5, 6, 7]],
                       [[4, 4, 1, 2], [8, 9, 10, 11]]])       # [B, T, k]
    valid = jnp.asarray([[1, 1], [1, 0]])
    got = routing_counters({"a": idx, "b": idx}, valid, first_expert=4,
                           held_experts=4)
    # real tokens' picks among experts 4..7: 5 | 4 5 6 7 | 4 4 -> 7 rows on
    # 4 experts, expert 4 thrice; 3 real tokens x 4 choices; two layers
    np.testing.assert_array_equal(np.asarray(got), [14, 8, 3, 24])
    every = routing_counters({"a": idx}, valid, 0, E)
    assert int(every[0]) == int(every[3]) == 12


# --- (d) the window's table columns and lower bound -----------------------------
LENGTHS = [0, 1, 127, 128, 129, 255, 256, 257, 383, 384, MAX_LEN - 1,
           MAX_LEN]


def test_window_columns_follow_the_lengths():
    ln = np.asarray(LENGTHS)
    # the slot's token sits at position ``length`` and sees back 128
    # positions, itself included: the oldest is length - 127
    np.testing.assert_array_equal(
        tile_math.window_first_page(ln, WINDOW, PAGE),
        np.maximum(ln - 127, 0) // PAGE)
    assert tile_math.window_first_page(129, WINDOW, PAGE) == 0
    assert tile_math.window_first_page(255, WINDOW, PAGE) == 1
    assert tile_math.window_table_width(WINDOW, 1, PAGE, 32) == 2
    # 129 positions touch 2 pages at most; 130 can touch 3
    assert tile_math.window_table_width(WINDOW, 2, PAGE, 32) == 2
    assert tile_math.window_table_width(WINDOW, 3, PAGE, 32) == 3
    assert tile_math.window_table_width(WINDOW, 1, 64, 32) == 3
    assert tile_math.window_table_width(WINDOW, 1, PAGE, 1) == 1
    assert tile_math.window_table_width(0, 1, PAGE, 32) == 32
    # every attended position lies inside the columns handed over
    for rows in (1, 2, 4):
        width = tile_math.window_table_width(WINDOW, rows, PAGE, 4)
        for n in LENGTHS:
            first = int(tile_math.window_first_page(n, WINDOW, PAGE))
            mask = np.asarray(paged_window_mask(
                jnp.asarray([n]), MAX_LEN, rows, WINDOW))[0, 0]
            seen = np.flatnonzero(mask.any(axis=0))
            assert seen.min() // PAGE >= first
            assert seen.max() // PAGE < first + width


def test_window_mask_has_both_edges():
    m = np.asarray(paged_window_mask(jnp.asarray([300]), MAX_LEN, 2, WINDOW))
    assert np.flatnonzero(m[0, 0, 0]).tolist() == list(range(173, 301))
    assert np.flatnonzero(m[0, 0, 1]).tolist() == list(range(174, 302))
    full = np.asarray(paged_window_mask(jnp.asarray([300]), MAX_LEN, 1))
    assert full[0, 0, 0].sum() == 301


@pytest.mark.parametrize("heads, kv, rows", [
    (16, 8, 1),      # flat heads, 2 rows a head: the benchmark's form
    (16, 8, 3),      # ... a spec-verify window of 3 on top
    (4, 2, 1),       # per head
])
def test_kernel_and_fallback_agree_at_every_length(heads, kv, rows):
    """The paged kernel (interpreted), handed the window's columns, against
    the gather fallback under the one mask rule, at lengths around every
    edge: 1, the window, a page, the table's end."""
    rng = np.random.default_rng(5)
    B, H, n_pages = len(LENGTHS), 16, 40
    q = jnp.asarray(rng.normal(size=(B, rows, heads, H)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(2, 2, n_pages, PAGE, kv, 128)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(n_pages)[:B * 3].reshape(B, 3),
                        jnp.int32)
    # the last column unallocated where the slot is short
    table = jnp.concatenate(
        [table, jnp.full((B, 1), n_pages, jnp.int32)], axis=1)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    out = {}
    for backend in ("xla", "pallas"):
        attn_ops.set_attention_backend(backend)
        try:
            out[backend] = {s: np.asarray(attn_ops.dot_product_attention(
                q, pool[0], pool[1], page_table=table, kv_lengths=lengths,
                layer=1, sliding=s)) for s in (WINDOW, 0)}
        finally:
            attn_ops.set_attention_backend("auto")
    for s in (WINDOW, 0):
        np.testing.assert_allclose(out["pallas"][s], out["xla"][s],
                                   rtol=2e-5, atol=2e-5)
    # past the window the two kinds of layer differ
    assert _gap(out["xla"][WINDOW][4:], out["xla"][0][4:]) > 1e-2
    if rows == 1:     # inside the window they are one function
        np.testing.assert_allclose(
            out["xla"][WINDOW][:3], out["xla"][0][:3], rtol=1e-6, atol=1e-6)


# --- the engine: counters, page counts, snapshot ---------------------------------
def test_engine_serves_it_and_counts_by_layer_kind(
        model, params, view, ref, tokens):
    queue = RequestQueue(model.name, max_len=64)
    engine = DecodeEngine(
        model, params, queue, num_slots=4, max_len=MAX_LEN,
        prompt_buckets=[64], paged=True, page_size=PAGE, kv_pool_pages=12,
        decode_horizon=1, max_admissions_per_step=1,
        default_max_new_tokens=4)
    prompt = [int(t) for t in tokens[:200]]
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
    chunks = [t for t in engine.turns if t.kind == "chunk" and t.moe_pairs]
    # 3 sparse layers x top-4 a real token; held rows are some of them
    assert [t.moe_pairs for t in scans] == [3 * TOP_K] * len(scans)
    assert chunks[-1].moe_pairs == 3 * TOP_K * (200 - 192)
    assert all(0 <= t.moe_rows <= t.moe_pairs for t in engine.turns)
    # one busy slot at length 200+: a full layer finds 2 live entries of its
    # 4, a sliding layer 2 (positions 73.. on) of the 2 it walks; three idle
    # slots one each. Mean over layers (3 sliding, 1 full) either way: 5.
    assert scans[0].kv_pages_live == 5
    summary = engine.turn_summary()
    assert summary["kv_pages_scanned"] == pytest.approx(
        4 * (3 * 2 + 4) / 4 * sum(t.substeps for t in scans))
    assert summary["moe_held_rows_share"] == pytest.approx(
        sum(t.moe_rows for t in engine.turns)
        / sum(t.moe_pairs for t in engine.turns))
    snap = engine.snapshot()
    assert snap["kv_pool"]["layer_windows"] == [128, 128, 128, 0]
    assert snap["kv_pool"]["layer_table_widths"] == [2, 2, 2, 4]
    assert snap["moe"]["held_experts"] == [HELD, 2 * HELD]
    assert snap["moe"]["num_experts"] == E
    assert snap["moe"]["routing"] == (
        "sigmoid + selection bias, renormalised, x 2.5")
    assert snap["moe"]["held_rows_share"] == summary["moe_held_rows_share"]


def test_grouped_matmul_column_tile_follows_the_contracted_width():
    # the widths it was sized at keep their tile
    assert tile_math.moe_tile_cols(2048, 1024, 2, 2) == 512
    assert tile_math.moe_tile_cols(1024, 2048, 1, 2) == 512
    # two double-buffered [6144, 512] blocks are 24 MiB: 256 (12 MiB)
    assert tile_math.moe_tile_cols(6144, 2048, 2, 2) == 256
    assert tile_math.moe_tile_cols(2048, 6144, 1, 2) == 512
    assert tile_math.moe_tile_cols(128, 128, 2, 4) == 128
    for k, n, w in ((6144, 2048, 2), (2048, 6144, 1), (2048, 1024, 2)):
        tn = tile_math.moe_tile_cols(k, n, w, 2)
        assert 2 * w * k * tn * 2 <= tile_math.VMEM_BLOCK_BUDGET_BYTES
