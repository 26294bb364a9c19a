"""One rank's share of an LFM2-shaped model on the normal path, against the
plain reference the benchmark keeps (``benchmark/reference/lfm2.py``, read
through ``benchmark/views/lfm2.py``; both loaded by path: they import nothing
of the program): three layers of four are gated short convolutions that keep
a fixed STATE a slot and no pages (``PagedKVCache.conv_state``), beside GQA
layers with a QK-norm per head in the packed paged pool; two dense layers,
then sigmoid top-k routing with a selection bias over ALL experts of which
some are held here, no shared expert, a tied head. CPU, float32, seeded
weights, tiny widths (8 layers CCGC CCGC, d 64, 8/2 heads of 64 — a 4:1
group, two KV heads in ONE pool row —, 16 experts top-4 of which 4 are held),
compared on LOGITS.
"""

import dataclasses
import importlib.util
import os
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
from ray_dynamic_batching_tpu.models import kv_state
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.models.moe import MoEBlock, RoutingRule
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import decode_attention

ROOT = Path(__file__).resolve().parents[1]

PAGE, MAX_LEN, D, K_TAPS = 128, 512, 64, 3
E, HELD, TOP_K = 16, 4, 4
RANKS = E // HELD
LAYERS = 8
TINY = DecoderConfig(
    vocab_size=512, d_model=D, num_layers=LAYERS, num_heads=8,
    num_kv_heads=2, head_dim=64, mlp_dim=128, max_seq_len=MAX_LEN,
    rope_theta=1e6, rms_eps=1e-5, qk_norm=True, qk_norm_per_head=True,
    layer_pattern="CCGC", conv_kernel=K_TAPS, tie_embeddings=True,
    num_dense_layers=2, dense_mlp_dim=256, num_experts=E, moe_top_k=TOP_K,
    moe_renormalize=True, moe_scoring="sigmoid",
    moe_selection_bias=True, moe_gate_scale=1.0, moe_first_expert=HELD,
    moe_held_experts=HELD,
)
SIZES = {
    "norm_eps": 1e-5, "num_attention_heads": 8, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1e6}, "num_experts_per_tok": TOP_K,
    "routed_scaling_factor": 1,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 2,
    "expert_parallel": {"first_expert": HELD},
    "program": {"decoder_config": {"num_layers": LAYERS}},
}

# Program and reference both compute in float32 here, so they differ by
# summation order alone (worst gap read: 2e-5 on logits whose spread is 1);
# every wrong piece of arithmetic below moves them by hundredths or more,
# and a bfloat16 run of the same float32 weights by tenths.
TOL = 2e-4


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "lfm2_" + Path(rel).stem + "_" + Path(rel).parent.name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/lfm2.py")


@pytest.fixture(scope="module")
def view():
    return _load("benchmark/views/lfm2.py")


def _seeded(model, view, seed=0):
    """``model.init``'s tree with the gains, the taps and the selection
    bias drawn as the view's seeding rule says: with gains of one and a bias
    of zero, dropping either would be the same function."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        rule = view.seeding(names, tuple(x.shape))
        if rule is not None and names[-1] in (
                "scale", "selection_bias", "conv_taps"):
            k = jax.random.fold_in(
                key, zlib.crc32("/".join(names).encode()) % (2 ** 31))
            return rule[0] + rule[1] * jax.random.normal(k, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def model():
    return CausalLM(TINY, name="lfm2_tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model, view):
    return _seeded(model, view)


@pytest.fixture(scope="module")
def weights(params, view):
    return view.view(params, SIZES)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(7).integers(1, TINY.vocab_size, 300)


def _full(model, params, tokens):
    t = jnp.asarray(tokens, jnp.int32)[None]
    return np.asarray(model.apply(params, t, jnp.ones_like(t))[0])


def _gap(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


@pytest.fixture(scope="module")
def want(weights, ref, tokens):
    return np.asarray(ref.logits(weights, tokens, SIZES))


# --- the full forward, and what tells each piece of the arithmetic ------------
def test_full_forward_matches_the_reference(model, params, tokens, want):
    assert _gap(_full(model, params, tokens), want) < TOL


def test_a_layer_asks_for_its_kind_in_one_place():
    kinds = [TINY.layer_kind(i) for i in range(LAYERS)]
    assert [k.conv for k in kinds] == [True, True, False, True] * 2
    # a layer's place among the layers of its own kind
    assert [k.pool_layer for k in kinds] == [0, 1, 0, 2, 3, 4, 1, 5]
    assert [k.sparse for k in kinds] == [False] * 2 + [True] * 6
    assert [k.mlp_dim for k in kinds] == [256] * 2 + [128] * 6
    assert not any(k.ring or k.latent or k.window for k in kinds)
    assert (TINY.conv_layers, TINY.pool_layers) == (6, 2)
    with pytest.raises(ValueError, match="needs its taps"):
        dataclasses.replace(TINY, conv_kernel=0)
    with pytest.raises(ValueError, match="taps need a layer"):
        dataclasses.replace(TINY, layer_pattern="G")
    with pytest.raises(ValueError, match="letters are L"):
        dataclasses.replace(TINY, sliding_window=128, layer_pattern="CCLC")
    with pytest.raises(ValueError, match="not built beside them"):
        dataclasses.replace(TINY, index_topk=8, index_heads=2,
                            index_head_dim=16)


@pytest.mark.parametrize("wrong", [
    "taps_reversed", "one_tap_dropped", "gates_swapped", "qk_gain_dropped",
    "op_norm_gain_dropped", "rope_dropped", "selection_bias_dropped",
    "gates_not_renormalised", "softmax_scores", "all_experts_here",
    "a_conv_layer_attends", "head_untied"])
def test_wrong_arithmetic_fails_the_tolerance(
        wrong, model, params, tokens, want):
    cfg = TINY
    p = jax.tree_util.tree_map(lambda x: x, params)
    layers = [p["params"][f"layer{i}"] for i in range(LAYERS)]
    convs = [lp for lp in layers if "conv_taps" in lp]
    if wrong == "taps_reversed":
        for lp in convs:
            lp["conv_taps"] = lp["conv_taps"][::-1]
    elif wrong == "one_tap_dropped":
        for lp in convs:
            lp["conv_taps"] = lp["conv_taps"].at[0].set(0.0)
    elif wrong == "gates_swapped":       # [B | C | x] read as [C | B | x]
        for lp in convs:
            w = lp["conv_in"]["kernel"]
            lp["conv_in"] = {"kernel": jnp.concatenate(
                [w[:, D:2 * D], w[:, :D], w[:, 2 * D:]], axis=1)}
    elif wrong == "qk_gain_dropped":
        for lp in layers:
            for n in ("q_norm", "k_norm"):
                if n in lp:
                    lp[n] = {"scale": jnp.ones_like(lp[n]["scale"])}
    elif wrong == "op_norm_gain_dropped":
        for lp in convs:
            lp["attn_norm"] = {"scale": jnp.ones_like(
                lp["attn_norm"]["scale"])}
    elif wrong == "rope_dropped":
        cfg = dataclasses.replace(TINY, pos="learned")
        p["params"]["pos_embed"] = {"embedding": jnp.zeros((MAX_LEN, D))}
    elif wrong == "selection_bias_dropped":
        cfg = dataclasses.replace(TINY, moe_selection_bias=False)
    elif wrong == "gates_not_renormalised":
        cfg = dataclasses.replace(TINY, moe_renormalize=False)
    elif wrong == "softmax_scores":
        cfg = dataclasses.replace(TINY, moe_scoring="softmax")
    elif wrong == "all_experts_here":      # the neighbour rank's experts
        cfg = dataclasses.replace(TINY, moe_first_expert=0)
    elif wrong == "a_conv_layer_attends":
        served = CausalLM(dataclasses.replace(
            TINY, layer_pattern="CGGC"), name=wrong, dtype=jnp.float32)
        got = _full(served, served.init(jax.random.PRNGKey(0)), tokens)
        assert _gap(got, want) > 10 * TOL
        return
    elif wrong == "head_untied":
        cfg = dataclasses.replace(TINY, tie_embeddings=False)
        p["params"]["lm_head"] = {"kernel": jax.random.normal(
            jax.random.PRNGKey(9), (D, TINY.vocab_size)) / 8.0}
    served = CausalLM(cfg, name=wrong, dtype=jnp.float32)
    assert _gap(_full(served, p, tokens), want) > 10 * TOL


def test_bfloat16_fails_the_tolerance(params, tokens, want):
    low = CausalLM(TINY, name="lfm2_tiny_bf16", dtype=jnp.bfloat16)
    assert _gap(_full(low, params, tokens), want) > 10 * TOL


def test_the_programs_gates_are_the_published_rules_within_its_eps(ref):
    """The published rule divides by the chosen scores' sum + 1e-6 and the
    reference does; the program's one arm divides by the sum itself (four
    sigmoid scores: 5e-7 of it, under float32's own rounding of the
    quotient's neighbours and three orders under ``TOL``): no second arm."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(64, E)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(E,)), jnp.float32)
    rule = RoutingRule("sigmoid", True, True, 1.0)
    gates, idx, scores = rule.route(logits, bias, TOP_K)
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(gates), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
        rtol=2e-6)
    # ... and the reference's own rule (identity router: h = logits)
    r_idx, weight, _ = ref.route(logits, jnp.eye(E), bias, TOP_K, 1.0)
    assert [set(r) for r in np.asarray(r_idx)] == [
        set(r) for r in np.asarray(idx)]
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weight), np.asarray(idx), axis=-1),
        np.asarray(gates), rtol=2e-6)


# --- the mixer alone: the state at a chunk's TRUE length ----------------------
def test_the_state_after_a_padded_chunk_is_the_state_at_the_true_length():
    from ray_dynamic_batching_tpu.models import short_conv

    rng = np.random.default_rng(5)
    g, T = 5, 8
    z = jnp.asarray(rng.normal(size=(g, T, D)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(g, K_TAPS - 1, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K_TAPS, D)), jnp.float32)
    lens = jnp.asarray([T, 5, 2, 1, 0], jnp.int32)
    c, new = short_conv.chunk(z, state, lens, w)
    z, state, w, c, new = (np.asarray(a) for a in (z, state, w, c, new))
    # rows: c_t = w0 z_{t-2} + w1 z_{t-1} + w2 z_t, the state before row 0
    ext = np.concatenate([state, z], axis=1)
    np.testing.assert_allclose(
        c, w[0] * ext[:, :T] + w[1] * ext[:, 1:T + 1] + w[2] * ext[:, 2:],
        rtol=1e-5, atol=1e-6)
    # the state: the last two of (incoming state | the REAL rows), exactly
    for b, n in enumerate([T, 5, 2, 1, 0]):
        np.testing.assert_array_equal(new[b], ext[b, n:n + K_TAPS - 1])
    np.testing.assert_array_equal(new[1], z[1, 3:5])       # not z[1, 6:8]
    np.testing.assert_array_equal(new[3], [state[3, 1], z[3, 0]])
    np.testing.assert_array_equal(new[4], state[4])
    # the pad rows are not in it: other values there, the same state
    z2 = np.where(np.arange(T)[None, :, None] < np.asarray(lens)[:, None, None],
                  z, 99.0)
    _, again = short_conv.chunk(jnp.asarray(z2), jnp.asarray(state), lens,
                                jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(again), new)
    # a decode row is the chunk of one row: it moves on, or stays
    adv = jnp.asarray([1, 0, 1, 0, 1], jnp.int32)
    c1, moved = short_conv.decode_row(
        jnp.asarray(z[:, :1]), jnp.asarray(state), adv, jnp.asarray(w))
    c2, moved2 = short_conv.chunk(
        jnp.asarray(z[:, :1]), jnp.asarray(state), adv, jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(moved2))
    np.testing.assert_array_equal(np.asarray(moved)[1], state[1])
    np.testing.assert_array_equal(np.asarray(moved)[0], [state[0, 1], z[0, 0]])


def test_a_padded_chunks_program_leaves_the_state_of_the_exact_one(
        model, params, tokens):
    """The chunk PROGRAM at a bucket of 64 holding 41 real rows: the slot's
    state is bit for bit the same whatever token ids lie in the padding,
    and that of the same rows in a chunk of exactly 41 (another program:
    its products sum in another order, 3e-6 apart; rows 62 and 63 of the
    padding would be units apart)."""
    chunk = jax.jit(model.prefill_chunk_paged,
                    static_argnames=("moe_counters",))
    table = jnp.asarray([[0, 1, 2, 3]], jnp.int32)

    def state_after(width, pad_token):
        toks = np.full((1, width), pad_token, np.int32)
        mask = np.zeros((1, width), np.int32)
        toks[0, :41], mask[0, :41] = tokens[:41], 1
        cache = model.make_paged_cache(2, 4, PAGE, MAX_LEN)
        # the slot's last tenant left something: the program zeroes it
        cache = cache.replace(conv_state=cache.conv_state + 7.0)
        _, new = chunk(params, jnp.asarray(toks), jnp.asarray(mask), cache,
                       table, jnp.zeros((1,), jnp.int32),
                       jnp.asarray([40], jnp.int32),
                       state_slots=jnp.asarray([1], jnp.int32))
        return np.asarray(new.conv_state)

    exact = state_after(41, 0)
    assert exact.shape == (6, 2, K_TAPS - 1, D)
    assert (exact[:, 0] == 7.0).all()           # the other slot: untouched
    assert np.abs(exact[:, 1]).max() > 0 and not (exact[:, 1] == 7.0).any()
    padded = state_after(64, 0)
    np.testing.assert_array_equal(state_after(64, 311), padded)
    np.testing.assert_array_equal(padded[:, 0], exact[:, 0])
    assert _gap(padded, exact) < 2e-5


# --- the engine's programs against the reference's full forward ----------------
class _Tap:
    """A ``sample_fn`` that keeps every row of logits the engine's programs
    sample from (a chunk group's take rows, each decode substep's slots)
    and takes the greedy token."""

    def __init__(self):
        self.rows = []

    def __call__(self, logits):
        jax.debug.callback(
            lambda x: self.rows.append(np.asarray(x)), logits, ordered=True)
        return jnp.argmax(logits, axis=-1)

    def nearest(self, row) -> float:
        """The least distance of a kept row from ``row``."""
        kept = np.concatenate(self.rows, axis=0)
        return float(np.abs(kept - row[None]).max(axis=-1).min())


def _engine(model, params, **kw):
    tap = _Tap()
    queue = RequestQueue(model.name, max_len=64)
    opts = dict(num_slots=2, max_len=MAX_LEN, prompt_buckets=[32, 64],
                page_size=PAGE, kv_pool_pages=8, decode_horizon=8,
                ttft_horizon=8, max_admissions_per_step=2,
                default_max_new_tokens=8, prefill_token_budget=128,
                sample_fn=tap)
    opts.update(kw)
    return DecodeEngine(model, params, queue, **opts), queue, tap


def _submit(queue, model, prompt, n_new):
    req = Request(model=model.name, slo_ms=60_000.0, payload={
        "tokens": [int(t) for t in prompt], "max_new_tokens": n_new})
    queue.add_request(req)
    return req


def _served_rows_match(tap, ref, weights, prompt, out) -> float:
    """Every token of ``out`` was sampled from the reference's logits at
    its position: the largest, over those positions, of the least distance
    between the reference's row and a row the programs sampled from."""
    prompt = [int(t) for t in prompt]
    want = np.asarray(ref.logits(weights, prompt + out[:-1], SIZES))
    worst = 0.0
    for j, tok in enumerate(out):
        row = want[len(prompt) - 1 + j]
        assert int(row.argmax()) == tok
        worst = max(worst, tap.nearest(row))
    return worst


CASES = {
    # one chunk, its bucket of 64 padded by 23
    "one_padded_chunk": [41],
    # three chunks of 64 of which the last holds ONE real row
    "three_chunks_last_of_one_row": [129],
    # two trains in ONE group of bucket 32, of unequal length
    "a_group_of_two_unequal_trains": [20, 31],
}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_through_the_engine_matches_the_reference(
        case, backend, model, params, weights, ref, tokens):
    """Chunked prefill through ``_chunk_group_paged_impl`` (the state
    zeroed, carried chunk to chunk and taken at the true length), then 8-
    substep scans through ``_decode_impl`` (the state carried beside the
    pool), against the reference's ONE full forward; under ``pallas`` the
    attention layers' decode reads are the paged kernel, interpreted, over
    two heads a pool row at a 4:1 group."""
    attn_ops.set_attention_backend(backend)
    decode_attention.clear_decode_paths()
    attn_ops.clear_attention_paths()
    try:
        engine, queue, tap = _engine(model, params)
        prompts = [tokens[11 * i:11 * i + n]
                   for i, n in enumerate(CASES[case])]
        reqs = [_submit(queue, model, p, 12) for p in prompts]
        engine.run_until_idle(timeout_s=600)
    finally:
        attn_ops.set_attention_backend("auto")
    for prompt, req in zip(prompts, reqs):
        out = list(req.future.result(timeout=5).tokens)
        assert len(out) == 12
        assert _served_rows_match(tap, ref, weights, prompt, out) < TOL
    chunks = [t for t in engine.turns if t.kind == "chunk"]
    if case == "three_chunks_last_of_one_row":
        assert [(t.state_resets, t.state_carries) for t in chunks] == [
            (1, 0), (0, 1), (0, 1)]
    elif case == "a_group_of_two_unequal_trains":
        assert [(t.state_resets, t.state_carries) for t in chunks] == [(2, 0)]
    else:
        assert [(t.state_resets, t.state_carries) for t in chunks] == [(1, 0)]
    assert all(t.state_resets == t.state_carries == 0
               for t in engine.turns if t.kind == "turn")
    paged = [p for p in attn_ops.attention_paths()
             if p.path != "short_conv" and p.q_shape[1] == 1]
    convs = [p for p in attn_ops.attention_paths() if p.path == "short_conv"]
    assert paged and convs
    # the slots' states in a scan, the rows' in a chunk group
    assert {p.kv_shape for p in convs} <= {
        (6, g, K_TAPS - 1, D) for g in (1, 2)}
    assert "3 taps in XLA, a state a slot" in convs[0].describe()
    if backend == "pallas":
        assert {p.heads_per_row for p in paged} == {2}
        assert {p.path for p in paged} == {attn_ops.PATH_PAGED_KERNEL}
        assert {p.q_shape[2:] for p in paged} == {(8, 64)}     # 8 q heads
        assert {p.kv_shape for p in paged} == {(2, 8, PAGE, 1, 128)}
        assert {d.heads_per_row for d in decode_attention.decode_paths()
                } == {2}
        # ... whose ONE row a position is a narrow block: the kernel folds
        # two live pages an online-softmax update (ISSUE 52)
        assert {d.pages for d in decode_attention.decode_paths()} == {2}


def test_the_counter_names_the_pages_a_fold(model, params, tokens):
    """``snapshot()["kv_pool"]["decode_paths"]`` says, for each program
    that read pages through the kernel, the live pages a fold and the
    ring's depth: two and three for this model's attention layers."""
    attn_ops.set_attention_backend("pallas")
    decode_attention.clear_decode_paths()
    try:
        engine, queue, _ = _engine(model, params)
        req = _submit(queue, model, tokens[:20], 3)
        engine.run_until_idle(timeout_s=600)
        lines = engine.snapshot()["kv_pool"]["decode_paths"]
    finally:
        attn_ops.set_attention_backend("auto")
        decode_attention.clear_decode_paths()
    assert len(req.future.result(timeout=5).tokens) == 3
    assert lines and all(line.startswith("decode_step: 2 heads a pool row")
                         for line in lines)
    assert all("a loop over the live pages, 2 pages a fold, a ring of 3, "
               in line for line in lines)


def test_a_slot_reused_after_a_longer_request_is_reset(
        model, params, weights, ref, tokens):
    """ONE slot: a long request, then a short one in the slot it left. The
    second's logits are the reference's (a conv layer reads its state
    unconditionally at position 0: without the reset they are not), and
    the same as a fresh engine's even where the state between the two held
    garbage."""
    engine, queue, tap = _engine(model, params, num_slots=1)
    long_req = _submit(queue, model, tokens[:150], 10)
    engine.run_until_idle(timeout_s=600)
    assert len(long_req.future.result(timeout=5).tokens) == 10
    assert float(jnp.abs(engine._cache.conv_state).max()) > 0
    engine._cache = engine._cache.replace(
        conv_state=engine._put(engine._cache.conv_state * 0 + 1e4))
    tap.rows.clear()
    short = _submit(queue, model, tokens[200:241], 10)
    engine.run_until_idle(timeout_s=600)
    out = list(short.future.result(timeout=5).tokens)
    assert _served_rows_match(tap, ref, weights, tokens[200:241], out) < TOL
    fresh, queue2, tap2 = _engine(model, params, num_slots=1)
    again = _submit(queue2, model, tokens[200:241], 10)
    fresh.run_until_idle(timeout_s=600)
    assert list(again.future.result(timeout=5).tokens) == out
    np.testing.assert_array_equal(
        np.concatenate(tap.rows), np.concatenate(tap2.rows))
    # the long one's 3 chunks are 1 reset + 2 carries, the short one's 1
    snap = engine.snapshot()["kv_pool"]
    assert (snap["state_resets"], snap["state_carries"]) == (2, 2)
    snap = fresh.snapshot()["kv_pool"]
    assert (snap["state_resets"], snap["state_carries"]) == (1, 0)


def test_an_evicted_and_resumed_stream_is_an_uninterrupted_one(
        model, params, weights, ref, tokens):
    """A stream capacity-finished after 5 tokens (what the pool's eviction
    does to its victim) and sent again as prompt + those 5: the re-prefill
    rebuilds the conv state, and the rest of the answer is the
    uninterrupted stream's, logits and tokens."""
    prompt = [int(t) for t in tokens[30:100]]
    whole_engine, queue, _ = _engine(model, params, num_slots=1)
    whole = _submit(queue, model, prompt, 12)
    whole_engine.run_until_idle(timeout_s=600)
    whole_out = list(whole.future.result(timeout=5).tokens)

    engine, queue, tap = _engine(model, params, num_slots=1,
                                 decode_horizon=1, ttft_horizon=1)
    first = _submit(queue, model, prompt, 12)
    engine._admit()
    engine._drain_prefill()
    for _ in range(4):
        engine._step(horizon=1)
    victim = engine._eviction_victim(exclude=-1)
    assert victim == 0
    engine._finish(victim, "capacity")
    cut = first.future.result(timeout=5)
    assert cut.finish_reason == "capacity"
    head = list(cut.tokens)
    assert head == whole_out[:5]
    tap.rows.clear()
    resumed = _submit(queue, model, prompt + head, 7)
    engine.run_until_idle(timeout_s=600)
    tail = list(resumed.future.result(timeout=5).tokens)
    assert head + tail == whole_out
    assert _served_rows_match(tap, ref, weights, prompt + head, tail) < TOL


def test_an_inactive_slots_state_does_not_move_through_an_8_substep_scan(
        model, params, tokens):
    engine, queue, _ = _engine(model, params, num_slots=4, kv_pool_pages=16)
    reqs = [_submit(queue, model, tokens[40 * i:40 * i + 33], 40)
            for i in range(3)]
    engine._admit()
    engine._drain_prefill()
    before = np.asarray(engine._cache.conv_state)
    assert all(np.abs(before[:, b]).max() > 0 for b in range(3))
    assert not before[:, 3].any()
    # slot 1 sits the scan out: the program is told so, as for a slot that
    # is free, whatever token its row of the upload holds
    engine._active_mask[1] = False
    engine._step(horizon=8)
    after = np.asarray(engine._cache.conv_state)
    np.testing.assert_array_equal(after[:, 1], before[:, 1])
    np.testing.assert_array_equal(after[:, 3], before[:, 3])
    for b in (0, 2):
        assert not np.array_equal(after[:, b], before[:, b])
    assert int(engine._len_host[1]) == 33 and int(engine._len_host[0]) == 41
    scan = [t for t in engine.turns if t.kind == "turn"][-1]
    assert (scan.substeps, scan.active) == (8, 2)
    del reqs


# --- the state's module --------------------------------------------------------
def test_the_pool_holds_the_attention_layers_and_the_plane_the_rest(model):
    engine, _, _ = _engine(model, model.init(jax.random.PRNGKey(0)))
    cache = engine._cache
    assert cache.k.shape == (2, 8, PAGE, 1, 128)       # 2 layers of 8
    assert cache.conv_state.shape == (6, 2, K_TAPS - 1, D)
    planes = {p.name: (p.table, p.kind, p.heads) for p in cache.planes()}
    assert planes == {"k": ("pages", "full", True),
                      "v": ("pages", "full", True),
                      "conv_state": ("slot", "state", False)}
    assert kv_state.state_kind(TINY) == "conv"
    state_bytes = 6 * 2 * (K_TAPS - 1) * D * 4
    pool_bytes = 2 * 2 * 8 * PAGE * 128 * 4
    assert cache.bytes_by_kind() == {"full": pool_bytes, "state": state_bytes}
    assert cache.resident_bytes() == cache.logical_bytes() == (
        pool_bytes + state_bytes)
    # the planner's figure: 2 layers x 2 heads x 64 x k and v a position,
    # and the state whatever the length
    assert model.kv_bytes_per_slot(PAGE) == (
        2 * PAGE * 2 * 2 * 64 * 4 + state_bytes // 2)
    assert model.kv_bytes_per_slot(2 * PAGE) - model.kv_bytes_per_slot(
        PAGE) == 2 * PAGE * 2 * 2 * 64 * 4
    snap = engine.snapshot()["kv_pool"]
    assert snap["kind"] == "conv" and snap["pool_layers"] == 2
    assert snap["heads_per_row"] == 2
    assert snap["conv_state"] == {
        "shape": [6, 2, K_TAPS - 1, D], "dtype": "float32",
        "bytes_per_slot": state_bytes // 2}
    assert snap["bytes_by_kind"] == {"full": pool_bytes,
                                     "state": state_bytes}
    assert snap["resident_bytes"] == pool_bytes + state_bytes
    assert snap["layer_windows"] == [0, 0]      # the layers that walk pages
    # the published model's arithmetic, off shapes alone
    big = CausalLM(dataclasses.replace(
        TINY, vocab_size=65536, d_model=2048, num_layers=40, num_heads=32,
        num_kv_heads=8, mlp_dim=1536, dense_mlp_dim=11776, num_experts=64,
        moe_held_experts=8, moe_first_expert=0, max_seq_len=4096),
        name="lfm2_shapes", dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: big.make_paged_cache(64, 2048, 128, 4096))
    assert shapes.k.shape == (10, 2048, 128, 4, 128)   # 10 of 40, 2 a row
    assert shapes.conv_state.shape == (30, 64, 2, 2048)
    assert shapes.logical_bytes() == 5_368_709_120 + 15_728_640
    assert big.kv_bytes_per_slot(1) == 20 * 1024 + 30 * 2 * 2048 * 2


@pytest.mark.parametrize("option,kw,match", [
    ("prefix_cache_size", {"prefix_cache_size": 4}, "snapshot of the state"),
    ("session_cache_size", {"session_cache_size": 4}, "next tenant"),
    ("host_spill_pages", {"host_spill_pages": 4}, "spills the prefix cache"),
    ("draft_model", {"draft_model": object(), "draft_params": {}},
     "cannot be moved back"),
    ("mesh", {"mesh": object()}, "no sharding layout"),
])
def test_the_engine_refuses_by_name_what_a_conv_state_cannot_serve(
        option, kw, match, model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match=match) as err:
        DecodeEngine(model, shapes, RequestQueue(model.name, max_len=8),
                     num_slots=2, max_len=MAX_LEN, page_size=PAGE, **kw)
    assert f"{option} cannot be used with a conv state a slot" in str(
        err.value)


def test_the_model_refuses_int8_kv_a_slab_a_parcel_and_a_mesh(model):
    assert set(kv_state.CANNOT["conv"]) == {
        "prefix_cache_size", "session_cache_size", "host_spill_pages",
        "draft_model", "mesh", "kv_dtype int8", "parcel", "slab"}
    int8 = CausalLM(TINY, name="i8", dtype=jnp.float32, kv_dtype=jnp.int8)
    with pytest.raises(NotImplementedError, match="no scale plane"):
        int8.make_paged_cache(2, 4, PAGE, MAX_LEN)
    with pytest.raises(ValueError, match="kv_dtype int8 cannot be used"):
        DecodeEngine(int8, {}, RequestQueue("i8", max_len=8), num_slots=2,
                     max_len=MAX_LEN, page_size=PAGE)
    with pytest.raises(NotImplementedError, match="no sharding layout"):
        model.paged_cache_pspec()
    with pytest.raises(NotImplementedError, match="slab cache has none"):
        p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: model.make_cache(2, 16))
        jax.eval_shape(model.decode_step, p, jnp.zeros((2, 1), jnp.int32),
                       cache, jnp.ones((2,), bool))
    engine, _, _ = _engine(model, model.init(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="page fabric"):
        engine.request_migration("r", lambda parcel: True)
    with pytest.raises(ValueError, match="page fabric"):
        engine._read_pages([0])


# --- the shares add up ----------------------------------------------------------
D_BLOCK, F_BLOCK = 128, 128     # widths the grouped kernel takes


def _block(first, held):
    return MoEBlock(
        d_model=D_BLOCK, mlp_dim=F_BLOCK, num_experts=E, top_k=TOP_K,
        rule=RoutingRule("sigmoid", True, True, 1.0),
        first_expert=first, held_experts=held, dtype=jnp.float32)


def test_the_ranks_shares_add_up_to_the_uncut_layer(ref):
    """Every rank's partial result (its 4 of the 16 experts) summed, against
    the reference's WHOLE layer (no shared expert to count once); and each
    rank's alone against the reference given that share."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 24, D_BLOCK)), jnp.float32)
    p = _block(0, E).init(jax.random.PRNGKey(5), x)["params"]
    p = dict(p, selection_bias=jnp.asarray(
        0.1 * rng.normal(size=(E,)), jnp.float32))
    w = {"ffn_norm_g": jnp.ones((D_BLOCK,)), "w_router": p["router"]["kernel"],
         "router_bias": p["selection_bias"], "we_up": p["wi"],
         "we_gate": p["wg"], "we_down": p["wo"]}
    flat = x.reshape(-1, D_BLOCK)
    # the block takes the NORMED stream; the reference norms it itself
    h = ref._rms(flat, w["ffn_norm_g"], 1e-5).reshape(x.shape)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.experts(flat, w, top_k=TOP_K, scale=1.0, first=0,
                                  eps=1e-5)
    parts = []
    for r in range(RANKS):
        cut = slice(r * HELD, (r + 1) * HELD)
        mine = dict(p, wi=p["wi"][cut], wg=p["wg"][cut], wo=p["wo"][cut])
        part = np.asarray(_block(r * HELD, HELD).apply(
            {"params": mine}, h)).reshape(-1, D_BLOCK)
        with jax.default_matmul_precision("highest"):
            alone, _, _ = ref.experts(
                flat, dict(w, we_up=w["we_up"][cut], we_gate=w["we_gate"][cut],
                           we_down=w["we_down"][cut]),
                top_k=TOP_K, scale=1.0, first=r * HELD, eps=1e-5)
        assert _gap(np.asarray(flat) + part, alone) < TOL
        parts.append(part)
    assert _gap(np.asarray(flat) + sum(parts), whole) < TOL
    # a rank alone is NOT the layer (else the sum would prove nothing)
    assert _gap(np.asarray(flat) + parts[0], whole) > 10 * TOL


# --- every other model -------------------------------------------------------------
def test_a_model_without_conv_layers_carries_no_state_and_no_counts():
    m = CausalLM(DecoderConfig(
        vocab_size=8, d_model=64, num_layers=2, num_heads=4, num_kv_heads=2,
        mlp_dim=8), name="g", dtype=jnp.float32)
    p = m.init(jax.random.PRNGKey(0))
    assert not {"conv_in", "conv_taps", "conv_out"} & set(
        p["params"]["layer0"])
    cache = jax.eval_shape(lambda: m.make_paged_cache(2, 4, 128, 256))
    assert cache.conv_state is None
    assert "conv_state" not in {pl.name for pl in cache.planes()}
    assert kv_state.state_kind(m.cfg) == "pair"
    assert m.cfg.conv_layers == 0 and m.cfg.pool_layers == 2
    assert not m.cfg.layer_kind(0).conv and m.cfg.layer_kind(1).pool_layer < 0
    engine = DecodeEngine(m, p, RequestQueue("g", max_len=8), num_slots=2,
                          max_len=256, prompt_buckets=[16], page_size=128)
    # the chunk group's upload is as wide as it was: no slot column
    assert sum(engine._chunk_group_widths(16)) == 2 * 16 + 2 + 6 + 2 + 2 * 16
    plain = [Turn("chunk", 0.0, 1.0, 2.0, 3.0, 0, 16, 1, 1, 0, 0, 0,
                  False)] * 3
    out = summarize_turns(plain, num_slots=2)
    assert not {"state_resets", "state_carries",
                "state_carried_chunk_share"} & set(out)
    assert "state_resets" not in engine.snapshot()["kv_pool"]
    hybrid = [plain[0]._replace(state_resets=2), plain[0]._replace(
        state_carries=1)] * 2
    out = summarize_turns(hybrid, num_slots=2)
    assert (out["state_resets"], out["state_carries"]) == (4, 2)
    assert out["state_carried_chunk_share"] == pytest.approx(1 / 3)


@pytest.mark.parametrize("module", [
    "ray_dynamic_batching_tpu.models.decoder",
    "ray_dynamic_batching_tpu.models.causal_lm",
    "ray_dynamic_batching_tpu.engine.decode",
    "ray_dynamic_batching_tpu.serve.llm",
])
def test_importing_the_program_imports_no_conv_module(module):
    code = (f"import sys, {module}; "
            "sys.exit('ray_dynamic_batching_tpu.models.short_conv' "
            "in sys.modules)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300).returncode == 0


def test_serving_a_model_without_conv_layers_traces_nothing_of_the_mixer():
    """A dense model's chunk and decode programs, traced in a process of
    its own: the module is never loaded."""
    code = """
import sys, jax, jax.numpy as jnp
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM, TINY_LM
m = CausalLM(TINY_LM, name="t", dtype=jnp.float32)
p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
c = jax.eval_shape(lambda: m.make_paged_cache(2, 4, 128, 256))
jax.eval_shape(m.decode_step_paged, p, jnp.zeros((2, 1), jnp.int32), c,
               jnp.ones((2,), bool))
z = jnp.zeros((1, 16), jnp.int32)
jax.eval_shape(m.prefill_chunk_paged, p, z, z, c,
               jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
               jnp.zeros((1,), jnp.int32))
sys.exit('ray_dynamic_batching_tpu.models.short_conv' in sys.modules)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300).returncode == 0


# --- the A/B tool ---------------------------------------------------------------
def test_the_ab_tools_explicit_shifts_are_the_kept_taps_and_it_wants_a_tpu():
    from ray_dynamic_batching_tpu.models import short_conv
    from tools import short_conv_ab

    rng = np.random.default_rng(6)
    for B, T in ((2, 16), (5, 1)):
        z = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
        state = jnp.asarray(rng.normal(size=(B, K_TAPS - 1, D)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K_TAPS, D)), jnp.float32)
        kept = short_conv.taps(jnp.concatenate([state, z], axis=1), w)
        np.testing.assert_allclose(
            np.asarray(short_conv_ab.explicit_shifts(z, state, w)),
            np.asarray(kept), rtol=1e-5, atol=1e-6)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run([sys.executable, "-m", "tools.short_conv_ab"],
                          env=env, cwd=ROOT, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 2 and "no TPU" in proc.stderr


# --- the benchmark's draw of the weights ------------------------------------------
def test_the_views_draw_lets_the_later_layers_write_a_quarter(view):
    """``benchmark/views/lfm2.py::seeding``: layers 0 and 1 (the dense ones)
    write at their fan-in rule and lay the stream down; from layer 2 on every
    OUTPUT projection (``conv_out``, ``o``, the experts' ``wo``) is drawn at
    ``LATER_WRITES`` of it, so that the 38 together add about the base's own
    size and the stack does not grow what bfloat16 rounds away; what a layer
    READS with (``conv_in``, q/k/v, the experts' up and gate, the router) and
    the dense layers keep the common table's draw (``None``) or their own."""
    import math

    D_, F_, H_ = 2048, 1536, 64
    assert (view.LATER_FROM, view.LATER_WRITES, view.QK_GAIN) == (2, 0.25, 1.6)
    for layer, share in ((0, 1.0), (1, 1.0), (2, 0.25), (39, 0.25)):
        at = ["params", f"layer{layer}"]
        assert view.seeding(at + ["conv_out", "kernel"], (D_, D_)) == (
            0.0, share / math.sqrt(D_))
        assert view.seeding(at + ["o", "kernel"], (32, H_, D_)) == (
            0.0, share / math.sqrt(32 * H_))
        assert view.seeding(at + ["moe", "wo"], (8, F_, D_)) == (
            0.0, share / math.sqrt(F_))
        # read with, not written with
        assert view.seeding(at + ["moe", "wi"], (8, D_, F_)) == (
            0.0, 1.0 / math.sqrt(D_))
        for name in ("conv_in", "q", "k", "v", "mlp_down", "mlp_up"):
            assert view.seeding(at + [name, "kernel"], (D_, D_)) is None
        assert view.seeding(at + ["conv_taps"], (3, D_)) == (
            0.0, 1.0 / math.sqrt(3))
    assert view.seeding(["params", "tok_embed", "embedding"], (8, D_)) is None
    assert view.seeding(["params", "final_norm", "scale"], (D_,)) == (1.0, 0.1)
    # a query attends a handful of keys, not hundreds: the attention layers
    # then write enough for a comparison of margins to see them
    for name in ("q_norm", "k_norm"):
        assert view.seeding(["params", "layer2", name, "scale"], (H_,)) == (
            1.6, 0.1)
    assert view.seeding(["params", "layer2", "attn_norm", "scale"],
                        (D_,)) == (1.0, 0.1)


def test_the_tool_that_reads_the_draws_runs_at_a_small_size():
    """``tools/lfm2_numerics.py`` is what the view's two draws and the
    configuration's gap were read with on the chip (PERF.md, PR 50). Here
    its code alone, on the CPU at d 256 and 8 layers: the served rows lie
    near the reference's, the taps reversed are caught on every row, and
    the draw it is given is the one it reports."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lfm2_numerics", "--small", "--slots",
         "2", "--prompt", "140", "--decode", "4", "--variants",
         "0.25:0.25:1.6", "--skip", "off,swap,cpu,f8"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    assert "== variant conv_out 0.25 experts 0.25 o 0.25 qk 1.6" in out
    assert "served (kernels on): 10 rows" in out
    assert "rows beyond 0.15: 0;" in out
    taps = next(ln for ln in out.splitlines()
                if "taps in the other order" in ln)
    assert "rows beyond 0.15: 100.0% of 10" in taps


# --- the one width a device metric reads -----------------------------------------
def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("program", ["decode_step", "chunk"])
def test_only_the_conv_mixers_first_product_is_three_hiddens_wide(program):
    """``short_conv_in_proj_dev_share_pct.batch`` finds its operations in a
    device trace by ONE width, 3 x hidden_size (the trace carries no scope).
    Held here to the programs' own equations at the published widths (four
    layers, shapes alone: nothing is compiled or run): every equation that
    makes an array that wide is the mixer's ``conv_in`` under the
    ``short_conv`` scope, one product a conv layer, and the metric file's
    pattern is that width."""
    import json

    bench = ROOT / "benchmark"
    cfg = json.loads((bench / "configs"
                      / "lfm2-24b-a2b-ep8-1chip.json").read_text())
    wide = 3 * cfg["hidden_size"]
    spec = json.loads((bench / "layer_metrics" / (
        "short_conv_in_proj_dev_share_pct.batch.json")).read_text())
    assert spec["args"] == {"op": f"_{wide}_$"}
    dc = dict(cfg["program"]["decoder_config"], num_layers=4)
    m = CausalLM(DecoderConfig(**dc), name="widths", dtype=jnp.bfloat16)
    shape = jax.ShapeDtypeStruct
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    B, W = 4, 128
    cache = jax.eval_shape(lambda: m.make_paged_cache(B, 8, PAGE, 256))
    if program == "decode_step":
        made = jax.make_jaxpr(m.decode_step_paged)(
            p, shape((B, 1), jnp.int32), cache, shape((B,), jnp.bool_))
    else:
        made = jax.make_jaxpr(
            lambda *a: m.prefill_chunk_paged(*a[:-1], state_slots=a[-1]))(
            p, shape((2, W), jnp.int32), shape((2, W), jnp.int32), cache,
            shape((2, 2), jnp.int32), shape((2,), jnp.int32),
            shape((2,), jnp.int32), shape((2,), jnp.int32))
    products = []
    for eqn in _equations(made.jaxpr):
        for out in eqn.outvars:
            if getattr(out.aval, "shape", ())[-1:] == (wide,):
                where = str(eqn.source_info.name_stack)
                assert "short_conv/conv_in" in where, (eqn.primitive, where)
                if eqn.primitive.name == "dot_general":
                    products.append(where.split("/")[1])
    assert products == ["layer0", "layer1", "layer3"]       # C C G C


# --- what the reference excuses ---------------------------------------------------
def test_the_reference_excuses_undecided_choices_and_nothing_else(
        ref, weights, tokens, want):
    """``reference_check.undecided_score_gap``: rows where a held expert
    lies nearer the chosen set's edge than that come back flat (zeros: any
    token passes a comparison of margins). The others come back as
    computed; unset, or with the caller taking the distances itself,
    nothing is touched, and no other key excuses a row (a lead of the best
    token over the next once did: a row is not excused for being close)."""
    def sizes(**check):
        return dict(SIZES, reference_check=check)

    edges = []
    np.testing.assert_array_equal(want, np.asarray(ref.logits(
        weights, tokens, sizes(undecided_score_gap=1e9), edges=edges)))
    nearest = np.min([np.asarray(e) for e in edges], axis=0)
    assert len(edges) == 6 and nearest.shape == (300,) and (nearest > 0).all()
    for gap in (0.0, float(np.median(nearest)), 1e9):
        out = np.asarray(ref.logits(weights, tokens,
                                    sizes(undecided_score_gap=gap)))
        flat = nearest < gap
        assert (out[flat] == 0).all()
        np.testing.assert_array_equal(out[~flat], want[~flat])
    np.testing.assert_array_equal(want, np.asarray(ref.logits(
        weights, tokens, sizes(undecided_token_lead=1e9))))
