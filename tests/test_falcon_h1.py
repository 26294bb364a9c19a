"""A Falcon-H1-shaped model on the normal path, against the plain reference
the benchmark keeps (``benchmark/reference/falcon_h1.py``, read through
``benchmark/views/falcon_h1.py``; both loaded by path: they import nothing of
the program): EVERY layer a Mamba-2 state-space mixer beside GQA attention on
the same normed input, their outputs summed; a MATRIX state a head a slot in
float32 (``PagedKVCache.ssm_state``) and the conv's last inputs
(``conv_state``) next to the pages; twelve fixed multipliers. CPU, float32,
seeded weights, a tiny size in which the conv's width (96) is not ``d_model``
(64), ``head_dim`` (128) is not ``d_model // num_heads`` (6), the state-space
groups are 2 and FIVE query heads share a KV head (10 / 2), compared on
LOGITS. The reference's scan is the token-by-token recurrence; the program's
chunk form is blocked (blocks of 32 here, 128 as published).
"""

import dataclasses
import importlib.util
import json
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
    KV_POOL_BYTES,
    DecodeEngine,
    Turn,
    summarize_turns,
)
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import kv_state
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import decode_attention

ROOT = Path(__file__).resolve().parents[1]

PAGE, MAX_LEN, D = 128, 512, 64
H, P, N, G, K_TAPS, BLOCK = 4, 8, 16, 2, 4, 32
D_SSM, WIDTH = H * P, H * P + 2 * G * N
LAYERS = 3
# the published multipliers' sizes, none of them 1
MULT = dict(
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    key_multiplier=0.011048543456039804, attention_in_multiplier=1.25,
    attention_out_multiplier=0.0375, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284))
TINY = DecoderConfig(
    vocab_size=512, d_model=D, num_layers=LAYERS, num_heads=10,
    num_kv_heads=2, head_dim=128, mlp_dim=128, max_seq_len=MAX_LEN,
    rope_theta=1e11, rms_eps=1e-5, layer_pattern="H", conv_kernel=K_TAPS,
    conv_bias=True, ssm_state=N, ssm_heads=H, ssm_head_dim=P, ssm_groups=G,
    ssm_chunk=BLOCK, **MULT)
SIZES = dict(
    rms_norm_eps=1e-5, mamba_n_heads=H, mamba_d_head=P, mamba_d_state=N,
    mamba_n_groups=G, num_attention_heads=10, num_key_value_heads=2,
    head_dim=128, rope_theta=1e11,
    program={"decoder_config": {"num_layers": LAYERS}}, **MULT)
# Every one of the twelve scalars, by where it lives in the configuration.
TWELVE = ([(k, None) for k in MULT if not k.endswith("s")]
          + [("ssm_multipliers", i) for i in range(5)]
          + [("mlp_multipliers", i) for i in range(2)])

# Program and reference both compute in float32 here, so they differ by
# summation order alone — the blocked scan against the token-by-token one
# included (worst gap read: 1e-5 on logits whose spread is 1); every wrong
# piece of arithmetic below moves them by hundredths or more, and a bfloat16
# run of the same float32 weights by tenths.
TOL = 2e-4


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "fh1_" + Path(rel).stem + "_" + Path(rel).parent.name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/falcon_h1.py")


@pytest.fixture(scope="module")
def view():
    return _load("benchmark/views/falcon_h1.py")


def _seeded(model, view, seed=0):
    """``model.init``'s tree with every leaf the view has a rule for drawn
    by it (the kernels that a multiplier follows, the gains, the taps and
    their bias, ``D``); ``A_log`` and ``dt_bias`` keep the family's initial
    values, which ``models/ssm.py`` draws."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        rule = view.seeding(names, tuple(x.shape))
        if rule is None or names[-1] in ("ssm_A_log", "ssm_dt_bias"):
            return x
        k = jax.random.fold_in(
            key, zlib.crc32("/".join(names).encode()) % (2 ** 31))
        return rule[0] + rule[1] * jax.random.normal(k, x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def model():
    return CausalLM(TINY, name="falcon_h1_tiny", dtype=jnp.float32)


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
    assert want.std() > 0.5                 # logits of unit size, not 1/128
    assert _gap(_full(model, params, tokens), want) < TOL


def test_a_layer_is_both_mixers_and_asks_for_its_kind_in_one_place():
    kinds = [TINY.layer_kind(i) for i in range(LAYERS)]
    assert all(k.ssm and not k.conv and not k.ring for k in kinds)
    # its own index in BOTH planes
    assert [k.pool_layer for k in kinds] == [0, 1, 2]
    assert (TINY.conv_layers, TINY.pool_layers) == (LAYERS, LAYERS)
    assert (TINY.d_ssm, TINY.conv_width) == (D_SSM, WIDTH) == (32, 96)
    assert TINY.conv_width != TINY.d_model
    assert TINY.head_dim != TINY.d_model // TINY.num_heads
    assert kv_state.state_kind(TINY) == "ssm"
    with pytest.raises(ValueError, match="every layer of its model"):
        dataclasses.replace(TINY, layer_pattern="HG")
    with pytest.raises(ValueError, match="needs its state's sizes"):
        dataclasses.replace(TINY, ssm_state=0)
    with pytest.raises(ValueError, match="conv_kernel >= 2"):
        dataclasses.replace(TINY, conv_kernel=0)
    with pytest.raises(ValueError, match="whole ssm_groups"):
        dataclasses.replace(TINY, ssm_groups=3)
    with pytest.raises(ValueError, match="five ssm_multipliers"):
        dataclasses.replace(TINY, ssm_multipliers=(1.0, 1.0))
    with pytest.raises(ValueError, match="not built beside it"):
        dataclasses.replace(TINY, num_experts=4)
    with pytest.raises(ValueError, match="not built beside it"):
        dataclasses.replace(TINY, qk_norm=True)
    # a model of conv layers keeps its own plane D wide
    lfm = DecoderConfig(vocab_size=8, d_model=64, num_layers=4, num_heads=4,
                        num_kv_heads=2, mlp_dim=8, layer_pattern="CCGC",
                        conv_kernel=3)
    assert (lfm.conv_width, lfm.conv_layers, lfm.pool_layers) == (64, 3, 1)


@pytest.mark.parametrize("name,index", TWELVE)
def test_each_of_the_twelve_multipliers_moves_the_logits(
        name, index, model, params, tokens, want):
    value = 1.0
    if index is not None:
        value = list(getattr(TINY, name))
        value[index] = 1.0
    served = CausalLM(dataclasses.replace(TINY, **{name: value}),
                      name="m1", dtype=jnp.float32)
    assert _gap(_full(served, params, tokens[:150]), want[:150]) > 100 * TOL


@pytest.mark.parametrize("wrong", [
    "conv_bias_dropped", "taps_reversed", "D_dropped", "norm_gain_dropped",
    "B_and_C_swapped", "one_group_for_all_heads",
    "dt_bias_dropped", "A_positive_log", "no_ssm_branch", "no_attention",
    "rope_theta_1e4", "head_dim_from_hidden"])
def test_wrong_arithmetic_fails_the_tolerance(
        wrong, model, params, weights, ref, tokens, want):
    toks = tokens[:150]
    cfg = TINY
    p = jax.tree_util.tree_map(lambda x: x, params)
    layers = [p["params"][f"layer{i}"] for i in range(LAYERS)]
    if wrong == "conv_bias_dropped":
        cfg = dataclasses.replace(TINY, conv_bias=False)
        for lp in layers:
            del lp["conv_bias"]
    elif wrong == "taps_reversed":
        for lp in layers:
            lp["conv_taps"] = lp["conv_taps"][::-1]
    elif wrong == "D_dropped":
        for lp in layers:
            lp["ssm_D"] = jnp.zeros_like(lp["ssm_D"])
    elif wrong == "dt_bias_dropped":
        for lp in layers:
            lp["ssm_dt_bias"] = jnp.zeros_like(lp["ssm_dt_bias"])
    elif wrong == "A_positive_log":       # A = -exp(-A_log)
        for lp in layers:
            lp["ssm_A_log"] = -lp["ssm_A_log"]
    elif wrong == "B_and_C_swapped":      # [z | x | C | B | dt]
        for lp in layers:
            w = lp["ssm_in"]["kernel"]
            b0, c0 = 2 * D_SSM, 2 * D_SSM + G * N
            lp["ssm_in"] = {"kernel": jnp.concatenate(
                [w[:, :b0], w[:, c0:c0 + G * N], w[:, b0:c0],
                 w[:, c0 + G * N:]], axis=1)}
    elif wrong == "one_group_for_all_heads":
        # the second group's B and C read as the first's
        for lp in layers:
            w = lp["ssm_in"]["kernel"]
            for start in (2 * D_SSM, 2 * D_SSM + G * N):
                w = w.at[:, start + N:start + 2 * N].set(
                    w[:, start:start + N])
            lp["ssm_in"] = {"kernel": w}
            lp["conv_taps"] = lp["conv_taps"].at[:, D_SSM + N:D_SSM + 2 * N].set(
                lp["conv_taps"][:, D_SSM:D_SSM + N])
        got = _full(model, p, toks)
        assert _gap(got, want[:150]) > 10 * TOL
        return
    elif wrong == "norm_gain_dropped":
        for lp in layers:
            lp["ssm_norm_scale"] = jnp.ones_like(lp["ssm_norm_scale"])
    elif wrong in ("no_ssm_branch", "no_attention"):
        without = ("ssm",) if wrong == "no_ssm_branch" else ("attention",)
        bad = np.asarray(ref.logits(weights, toks, SIZES, without=without))
        assert _gap(bad, want[:150]) > 1000 * TOL
        return
    elif wrong == "rope_theta_1e4":
        cfg = dataclasses.replace(TINY, rope_theta=1e4)
    elif wrong == "head_dim_from_hidden":
        # a reference that took d_model // heads for the rotary half
        bad = np.asarray(ref.logits(
            weights, toks, dict(SIZES, rope_theta=1e11 ** 0.5)))
        assert _gap(bad, want[:150]) > 10 * TOL
        return
    served = CausalLM(cfg, name=wrong, dtype=jnp.float32)
    assert _gap(_full(served, p, toks), want[:150]) > 10 * TOL


def test_bfloat16_fails_the_tolerance(params, tokens, want):
    low = CausalLM(TINY, name="falcon_h1_tiny_bf16", dtype=jnp.bfloat16)
    assert _gap(_full(low, params, tokens), want) > 10 * TOL


# --- the mixer's two forms against the recurrence --------------------------------
def _recurrence(x, dt, A, Bm, Cm, S):
    """The published recurrence, a row at a time, in float64 numpy:
    ``x`` [T, H, P], ``dt`` [T, H], ``Bm``/``Cm`` [T, G, N], ``S`` [H, P,
    N]. Returns (y [T, H, P] without ``D x``, the state after each row)."""
    Hg = x.shape[1] // Bm.shape[1]
    ys, states = [], []
    S = S.astype(np.float64)
    for t in range(x.shape[0]):
        Bh, Ch = (np.repeat(a[t], Hg, axis=0) for a in (Bm, Cm))
        S = (np.exp(dt[t] * A)[:, None, None] * S
             + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :])
        ys.append((S * Ch[:, None, :]).sum(-1))
        states.append(S)
    return np.stack(ys), states


def _scan_inputs(seed, b, T):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, T, H, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (b, T, H))).astype(
        np.float32)
    A = -rng.uniform(1.0, 16.0, H).astype(np.float32)
    Bm, Cm = (rng.normal(size=(b, T, G, N)).astype(np.float32)
              for _ in range(2))
    S0 = rng.normal(size=(b, H, P, N)).astype(np.float32)
    return x, dt, A, Bm, Cm, S0


@pytest.mark.parametrize("T,block,lens", [
    (96, 32, [96, 71, 1, 0]),      # three blocks, a true length inside one
    (64, 128, [64, 33, 5, 0]),     # a bucket narrower than the block
    (80, 32, [80, 64, 32, 17]),    # a width that is no whole blocks
])
def test_the_blocked_chunk_form_is_the_recurrence_from_a_state_in(
        T, block, lens):
    """A non-zero state in, true lengths short of the padding: the rows'
    outputs up to the true length and the state handed out are the
    token-by-token recurrence's (float64), the state AT THE TRUE LENGTH;
    whatever lies in the padding is not in it."""
    from ray_dynamic_batching_tpu.models import ssm

    x, dt, A, Bm, Cm, S0 = _scan_inputs(3, len(lens), T)
    real = np.arange(T)[None, :] < np.asarray(lens)[:, None]
    dt_real = np.where(real[..., None], dt, 0.0).astype(np.float32)
    y, S = ssm.chunk_scan(*(jnp.asarray(a) for a in (
        x, dt_real, A, Bm, Cm, S0)), block)
    y, S = np.asarray(y), np.asarray(S)
    for b, n in enumerate(lens):
        if not n:       # nothing real in the row: the state as it came in
            np.testing.assert_array_equal(S[b], S0[b])
            continue
        want_y, states = _recurrence(x[b, :n], dt[b, :n], A, Bm[b, :n],
                                     Cm[b, :n], S0[b])
        np.testing.assert_allclose(y[b, :n], want_y, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(S[b], states[-1], rtol=2e-4, atol=2e-4)
    # other values in the padding: the same state, bit for bit
    x2 = np.where(real[..., None, None], x, 99.0).astype(np.float32)
    B2 = np.where(real[..., None, None], Bm, -7.0).astype(np.float32)
    _, again = ssm.chunk_scan(*(jnp.asarray(a) for a in (
        x2, dt_real, A, B2, Cm, S0)), block)
    np.testing.assert_array_equal(np.asarray(again), S)


def test_a_decode_step_is_one_step_of_the_recurrence_and_an_idle_row_none():
    from ray_dynamic_batching_tpu.models import ssm

    x, dt, A, Bm, Cm, S0 = _scan_inputs(5, 4, 1)
    adv = np.asarray([1, 0, 1, 0], np.int32)
    y, S = ssm.decode_update(*(jnp.asarray(a) for a in (
        x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], S0, adv)))
    y, S = np.asarray(y), np.asarray(S)
    for b in range(4):
        want_y, states = _recurrence(x[b], dt[b], A, Bm[b], Cm[b], S0[b])
        if adv[b]:
            np.testing.assert_allclose(S[b], states[0], rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(y[b], want_y[0], rtol=1e-4, atol=1e-4)
        else:
            np.testing.assert_array_equal(S[b], S0[b])       # bit for bit
    # ... and the chunk form of ONE row is the same step
    y1, S1 = ssm.chunk_scan(*(jnp.asarray(a) for a in (
        x, dt, A, Bm, Cm, S0)), 128)
    np.testing.assert_allclose(np.asarray(S1)[0], S[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y1)[0, 0], y[0], rtol=1e-4,
                               atol=1e-4)


def test_a_padded_chunks_program_leaves_the_state_of_the_exact_one(
        model, params, tokens):
    """The chunk PROGRAM at a bucket of 64 holding 41 real rows: both
    planes of the slot's state are bit for bit the same whatever token ids
    lie in the padding, and those of the same rows in a chunk of exactly 41
    (another program: its products sum in another order)."""
    chunk = jax.jit(model.prefill_chunk_paged,
                    static_argnames=("moe_counters",))
    table = jnp.asarray([[0, 1, 2, 3]], jnp.int32)

    def state_after(width, pad_token):
        toks = np.full((1, width), pad_token, np.int32)
        mask = np.zeros((1, width), np.int32)
        toks[0, :41], mask[0, :41] = tokens[:41], 1
        cache = model.make_paged_cache(2, 4, PAGE, MAX_LEN)
        # the slot's last tenant left something: the program zeroes it
        cache = cache.replace(conv_state=cache.conv_state + 7.0,
                              ssm_state=cache.ssm_state + 7.0)
        _, new = chunk(params, jnp.asarray(toks), jnp.asarray(mask), cache,
                       table, jnp.zeros((1,), jnp.int32),
                       jnp.asarray([40], jnp.int32),
                       state_slots=jnp.asarray([1], jnp.int32))
        return np.asarray(new.conv_state), np.asarray(new.ssm_state)

    conv, state = state_after(41, 0)
    assert conv.shape == (LAYERS, 2, K_TAPS - 1, WIDTH)
    assert state.shape == (LAYERS, 2, H, P, N) and state.dtype == np.float32
    for plane in (conv, state):
        assert (plane[:, 0] == 7.0).all()        # the other slot: untouched
        assert np.abs(plane[:, 1]).max() > 0 and not (plane[:, 1] == 7.0).any()
    conv_p, state_p = state_after(64, 0)
    conv_q, state_q = state_after(64, 311)
    np.testing.assert_array_equal(conv_q, conv_p)
    np.testing.assert_array_equal(state_q, state_p)
    assert _gap(conv_p, conv) < 2e-5 and _gap(state_p, state) < 2e-5


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
    # three chunks of 64 of which the last is padded (151 = 64 + 64 + 23)
    "three_chunks_a_padded_last_one": [151],
    # two trains in ONE group of bucket 32, of unequal length
    "a_group_of_two_unequal_trains": [20, 31],
}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_through_the_engine_matches_the_reference(
        case, backend, model, params, weights, ref, tokens):
    """Chunked prefill through ``_chunk_group_paged_impl`` (both planes
    zeroed, carried chunk to chunk and taken at the true length), then
    8-substep scans through ``_decode_impl`` (one step of the recurrence a
    substep beside the paged read), against the reference's ONE full
    forward with its token-by-token scan; under ``pallas`` the decode reads
    are the paged kernel, interpreted, FIVE query heads a KV head."""
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
    want_chunks = {"three_chunks_a_padded_last_one": [(1, 0), (0, 1), (0, 1)],
                   "a_group_of_two_unequal_trains": [(2, 0)],
                   "one_padded_chunk": [(1, 0)]}[case]
    assert [(t.state_resets, t.state_carries) for t in chunks] == want_chunks
    assert all(t.ssm_state_bytes == 0 for t in chunks)
    per_slot_step = 2 * LAYERS * H * P * N * 4
    scans = [t for t in engine.turns if t.kind == "turn"]
    assert scans and all(
        t.ssm_state_bytes == t.active * t.substeps * per_slot_step
        for t in scans)
    paths = attn_ops.attention_paths()
    paged = [p for p in paths if p.path not in ("ssm", "short_conv")
             and p.q_shape[1] == 1]
    mixers = [p for p in paths if p.path == "ssm"]
    assert paged and mixers
    assert {p.kv_shape for p in mixers} <= {
        (LAYERS, g, H, P, N) for g in (1, 2)}
    assert {p.kv_dtype for p in mixers} == {"float32"}
    steps = [p for p in mixers if p.q_shape[1] == 1]
    assert steps and "one step of the recurrence" in steps[0].describe()
    assert any("a blocked scan" in p.describe() for p in mixers)
    if backend == "pallas":
        assert {p.path for p in paged} == {attn_ops.PATH_PAGED_KERNEL}
        assert {p.q_shape[2:] for p in paged} == {(10, 128)}   # 5 a KV head
        assert {p.kv_shape for p in paged} == {(LAYERS, 8, PAGE, 2, 128)}
        # ... given to the kernel as 2 x 8 rows (10 are no whole sublane
        # tiles), so the narrow block folds flat, two live pages an update
        assert {d.pages for d in decode_attention.decode_paths()} == {2}


def test_a_slot_reused_after_a_longer_request_starts_from_zero(
        model, params, weights, ref, tokens):
    """ONE slot: a long request, then a short one in the slot it left, with
    garbage written over both planes in between. The second's logits are the
    reference's (the mixer reads its state unconditionally at position 0:
    without the reset they are not), the same as a fresh engine's."""
    engine, queue, tap = _engine(model, params, num_slots=1)
    long_req = _submit(queue, model, tokens[:150], 10)
    engine.run_until_idle(timeout_s=600)
    assert len(long_req.future.result(timeout=5).tokens) == 10
    assert float(jnp.abs(engine._cache.ssm_state).max()) > 0
    engine._cache = engine._cache.replace(
        conv_state=engine._put(engine._cache.conv_state * 0 + 1e4),
        ssm_state=engine._put(engine._cache.ssm_state * 0 + 1e4))
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


def test_a_state_not_carried_across_a_chunk_edge_is_not_the_reference(
        model, params, weights, ref, tokens, want):
    """What the harness's fourth control serves on purpose: a reference
    whose state-space state (and conv inputs) stop at position 64, as a
    program without the hand-over would compute. Equal before the edge,
    far beyond the tolerance after it."""
    bad = np.asarray(ref.logits(weights, tokens[:150], SIZES,
                                cut_state_at=64))
    assert _gap(bad[:64], want[:64]) < 1e-5
    assert _gap(bad[64:], want[64:150]) > 1000 * TOL


def test_an_idle_slots_state_is_bit_identical_after_an_8_substep_scan(
        model, params, tokens):
    engine, queue, _ = _engine(model, params, num_slots=4, kv_pool_pages=16)
    reqs = [_submit(queue, model, tokens[40 * i:40 * i + 33], 40)
            for i in range(3)]
    engine._admit()
    engine._drain_prefill()
    before = (np.asarray(engine._cache.conv_state),
              np.asarray(engine._cache.ssm_state))
    for plane in before:
        assert all(np.abs(plane[:, b]).max() > 0 for b in range(3))
        assert not plane[:, 3].any()
    # slot 1 sits the scan out: the program is told so, as for a slot that
    # is free, whatever token its row of the upload holds
    engine._active_mask[1] = False
    engine._step(horizon=8)
    after = (np.asarray(engine._cache.conv_state),
             np.asarray(engine._cache.ssm_state))
    for was, now in zip(before, after):
        np.testing.assert_array_equal(now[:, 1], was[:, 1])
        np.testing.assert_array_equal(now[:, 3], was[:, 3])
        for b in (0, 2):
            assert not np.array_equal(now[:, b], was[:, b])
    scan = [t for t in engine.turns if t.kind == "turn"][-1]
    assert (scan.substeps, scan.active) == (8, 2)
    assert scan.ssm_state_bytes == 2 * 8 * 2 * LAYERS * H * P * N * 4
    del reqs


# --- the state's module, its counters and its spans ------------------------------
def test_the_planes_weigh_their_own_dtype(model):
    bf16 = CausalLM(TINY, name="falcon_h1_tiny_b", dtype=jnp.bfloat16)
    engine, _, _ = _engine(bf16, bf16.init(jax.random.PRNGKey(0)))
    cache = engine._cache
    assert cache.k.shape == (LAYERS, 8, PAGE, 2, 128)
    assert cache.k.dtype == cache.conv_state.dtype == jnp.bfloat16
    assert cache.conv_state.shape == (LAYERS, 2, K_TAPS - 1, WIDTH)
    assert cache.ssm_state.shape == (LAYERS, 2, H, P, N)
    assert cache.ssm_state.dtype == jnp.float32     # not the model's
    planes = {p.name: (p.table, p.kind, p.heads) for p in cache.planes()}
    assert planes == {"k": ("pages", "full", True),
                      "v": ("pages", "full", True),
                      "conv_state": ("slot", "state", False),
                      "ssm_state": ("slot", "state", False)}
    conv_bytes = LAYERS * 2 * (K_TAPS - 1) * WIDTH * 2
    ssm_bytes = LAYERS * 2 * H * P * N * 4
    pool_bytes = 2 * LAYERS * 8 * PAGE * 2 * 128 * 2
    assert cache.bytes_by_kind() == {"full": pool_bytes,
                                     "state": conv_bytes + ssm_bytes}
    assert cache.resident_bytes() == cache.logical_bytes() == (
        pool_bytes + conv_bytes + ssm_bytes)
    # the planner's figure: k and v a position, the state whatever the length
    row = LAYERS * 2 * 2 * 128 * 2
    assert bf16.kv_bytes_per_slot(PAGE) == (
        PAGE * row + (conv_bytes + ssm_bytes) // 2)
    assert bf16.kv_bytes_per_slot(2 * PAGE) - bf16.kv_bytes_per_slot(
        PAGE) == PAGE * row
    snap = engine.snapshot()["kv_pool"]
    assert snap["kind"] == "ssm" and snap["pool_layers"] == LAYERS
    assert snap["ssm_state"] == {
        "shape": [LAYERS, 2, H, P, N], "dtype": "float32",
        "bytes_per_slot": ssm_bytes // 2}
    assert snap["conv_state"] == {
        "shape": [LAYERS, 2, K_TAPS - 1, WIDTH], "dtype": "bfloat16",
        "bytes_per_slot": conv_bytes // 2}
    assert snap["bytes_by_kind"]["state"] == conv_bytes + ssm_bytes
    assert snap["ssm_state_bytes_moved"] == 0
    assert KV_POOL_BYTES.get(tags={"model": bf16.name, "kind": "state"}) == (
        conv_bytes + ssm_bytes)
    # the published model's arithmetic, off shapes alone: 25.2 MB a slot
    big = CausalLM(dataclasses.replace(
        TINY, vocab_size=261120, d_model=5120, num_layers=6, num_heads=20,
        num_kv_heads=4, mlp_dim=21504, ssm_state=256, ssm_heads=32,
        ssm_head_dim=128, ssm_chunk=128, max_seq_len=1024),
        name="falcon_shapes", dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: big.make_paged_cache(64, 512, 128, 1024))
    assert shapes.k.shape == (6, 512, 128, 4, 128)
    assert shapes.conv_state.shape == (6, 64, 3, 5120)
    assert shapes.ssm_state.shape == (6, 64, 32, 128, 256)
    assert big.cfg.conv_width == 5120 == big.cfg.d_model    # by coincidence
    assert shapes.logical_bytes() == (
        805_306_368 + 6 * 64 * 3 * 5120 * 2 + 1_610_612_736)
    assert big.kv_bytes_per_slot(1) == (
        12 * 1024 + 6 * 3 * 5120 * 2 + 6 * 32 * 128 * 256 * 4)
    p = jax.eval_shape(big.init, jax.random.PRNGKey(0))["params"]["layer0"]
    assert p["ssm_in"]["kernel"].shape == (5120, 9248)
    assert p["q"]["kernel"].shape == (5120, 20, 128)        # 5120 -> 2560
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(p))
    assert round(n / 1e6, 1) == 430.1                       # the issue's sum


def test_the_ring_sums_the_state_bytes_its_scans_moved():
    chunk = Turn("chunk", 0.0, 1.0, 2.0, 3.0, 0, 16, 1, 1, 0, 0, 0, False)
    scan = Turn("turn", 0.0, 1.0, 2.0, 3.0, 8, 0, 3, 0, 0, 0, 0, False)
    out = summarize_turns([chunk, scan], num_slots=4)
    assert "ssm_state_bytes" not in out
    out = summarize_turns(
        [chunk, scan._replace(ssm_state_bytes=1000),
         scan._replace(ssm_state_bytes=24)], num_slots=4)
    assert out["ssm_state_bytes"] == 1024


@pytest.mark.parametrize("program", ["decode_step", "chunk"])
def test_the_mixers_spans_and_the_one_width_the_metric_reads(program):
    """The five ``jax.named_scope`` spans are in the programs, and
    ``ssm_in_proj_dev_share_pct.batch`` finds its operations in a device
    trace by ONE width, 9,248 (the trace carries no scope): held here to the
    programs' own equations at the published widths (two layers, shapes
    alone: nothing is compiled or run): every equation that makes an array
    that wide is under the ``ssm_in_proj`` scope, one product a layer."""
    bench = ROOT / "benchmark"
    cfg = json.loads((bench / "configs"
                      / "falcon-h1-34b-1chip.json").read_text())
    wide = (2 * cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"]
            * cfg["mamba_d_state"] + cfg["mamba_n_heads"])
    spec = json.loads((bench / "layer_metrics" / (
        "ssm_in_proj_dev_share_pct.batch.json")).read_text())
    assert spec["args"] == {"op": f"_{wide}_$"} and wide == 9248
    dc = dict(cfg["program"]["decoder_config"], num_layers=2)
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
    from tests.test_lfm2 import _equations

    products, scopes = [], set()
    for eqn in _equations(made.jaxpr):
        where = str(eqn.source_info.name_stack)
        scopes.update(s for s in (
            "ssm_in_proj", "ssm_conv", "ssm_chunk_scan", "ssm_state_update",
            "ssm_gate_norm") if f"/{s}" in where)
        for out in eqn.outvars:
            if getattr(out.aval, "shape", ())[-1:] == (wide,):
                assert "/ssm_in_proj" in where, (eqn.primitive, where)
                if eqn.primitive.name == "dot_general":
                    products.append(where.split("/")[1])
    assert products == ["layer0", "layer1"]
    assert scopes == {"ssm_in_proj", "ssm_conv", "ssm_gate_norm",
                      "ssm_state_update" if program == "decode_step"
                      else "ssm_chunk_scan"}


def test_the_benchmarks_count_is_the_programs_counter():
    """``benchmark/ssm_counts.py`` counts the MODEL's work from the
    configuration file's published keys; the engine's ``Turn.ssm_state_bytes``
    counts what its plane holds: the same bytes a slot a substep."""
    counts = _load("benchmark/ssm_counts.py")
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "falcon-h1-34b-1chip.json").read_text())
    assert counts.state_bytes_per_slot_layer(cfg) == 32 * 128 * 256 * 4
    assert counts.state_step_bytes(cfg) == 6 * 32 * 128 * 256 * 4 * 2
    m = CausalLM(DecoderConfig(**cfg["program"]["decoder_config"]),
                 name="counts", dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: m.make_paged_cache(64, 512, 128, 1024))
    ssm = cache.ssm_state
    per_slot = 2 * ssm.dtype.itemsize * int(np.prod(ssm.shape)) // 64
    assert counts.state_step_bytes(cfg) == per_slot
    # a scan of 8 substeps over 64 active slots
    assert counts.scan_bytes(cfg, active=64, substeps=8) == 8 * 64 * per_slot


# --- refusals -----------------------------------------------------------------------
@pytest.mark.parametrize("option,kw,match", [
    ("prefix_cache_size", {"prefix_cache_size": 4}, "snapshot of the state"),
    ("session_cache_size", {"session_cache_size": 4}, "next tenant"),
    ("host_spill_pages", {"host_spill_pages": 4}, "spills the prefix cache"),
    ("draft_model", {"draft_model": object(), "draft_params": {}},
     "cannot be moved back"),
    ("mesh", {"mesh": object()}, "no sharding layout"),
])
def test_the_engine_refuses_by_name_what_a_state_space_state_cannot_serve(
        option, kw, match, model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match=match) as err:
        DecodeEngine(model, shapes, RequestQueue(model.name, max_len=8),
                     num_slots=2, max_len=MAX_LEN, page_size=PAGE, **kw)
    assert f"{option} cannot be used with a state-space state a slot" in str(
        err.value)


def test_the_model_refuses_int8_kv_a_slab_a_parcel_and_a_mesh(model):
    assert set(kv_state.CANNOT["ssm"]) == set(kv_state.CANNOT["conv"]) == {
        "prefix_cache_size", "session_cache_size", "host_spill_pages",
        "draft_model", "mesh", "kv_dtype int8", "parcel", "slab"}
    assert all("state-space" in why or "refused" in why
               for why in kv_state.CANNOT["ssm"].values())
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


# --- nothing of it in another model ----------------------------------------------
def test_a_model_without_the_mixer_carries_no_plane_and_no_counts():
    m = CausalLM(DecoderConfig(
        vocab_size=8, d_model=64, num_layers=2, num_heads=4, num_kv_heads=2,
        mlp_dim=8), name="g", dtype=jnp.float32)
    p = m.init(jax.random.PRNGKey(0))
    assert not {"ssm_in", "ssm_out", "ssm_A_log", "conv_taps"} & set(
        p["params"]["layer0"])
    cache = jax.eval_shape(lambda: m.make_paged_cache(2, 4, 128, 256))
    assert cache.ssm_state is None and cache.conv_state is None
    assert "ssm_state" not in {pl.name for pl in cache.planes()}
    assert not m.cfg.layer_kind(0).ssm
    engine = DecodeEngine(m, p, RequestQueue("g", max_len=8), num_slots=2,
                          max_len=256, prompt_buckets=[16], page_size=128)
    assert engine._ssm_step_bytes == 0
    assert "ssm_state_bytes_moved" not in engine.snapshot()["kv_pool"]
    # a model of conv layers: its plane, not this one
    lfm = CausalLM(DecoderConfig(
        vocab_size=8, d_model=64, num_layers=4, num_heads=4, num_kv_heads=2,
        mlp_dim=8, layer_pattern="CCGC", conv_kernel=3), name="c",
        dtype=jnp.float32)
    cache = jax.eval_shape(lambda: lfm.make_paged_cache(2, 4, 128, 256))
    assert cache.ssm_state is None
    assert cache.conv_state.shape == (3, 2, 2, 64)


@pytest.mark.parametrize("module", [
    "ray_dynamic_batching_tpu.models.decoder",
    "ray_dynamic_batching_tpu.models.causal_lm",
    "ray_dynamic_batching_tpu.engine.decode",
    "ray_dynamic_batching_tpu.serve.llm",
])
def test_importing_the_program_imports_no_ssm_module(module):
    code = (f"import sys, {module}; "
            "sys.exit('ray_dynamic_batching_tpu.models.ssm' in sys.modules)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300).returncode == 0


def test_serving_a_model_of_conv_layers_traces_nothing_of_the_mixer():
    """An LFM2-shaped model's chunk and decode programs, traced in a
    process of its own: ``models/ssm.py`` is never loaded."""
    code = """
import sys, jax, jax.numpy as jnp
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
m = CausalLM(DecoderConfig(vocab_size=8, d_model=64, num_layers=4,
    num_heads=4, num_kv_heads=2, mlp_dim=8, layer_pattern="CCGC",
    conv_kernel=3), name="t", dtype=jnp.float32)
p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
c = jax.eval_shape(lambda: m.make_paged_cache(2, 4, 128, 256))
jax.eval_shape(m.decode_step_paged, p, jnp.zeros((2, 1), jnp.int32), c,
               jnp.ones((2,), bool))
z = jnp.zeros((1, 16), jnp.int32)
jax.eval_shape(lambda *a: m.prefill_chunk_paged(*a[:-1], state_slots=a[-1]),
               p, z, z, c, jnp.zeros((1, 2), jnp.int32),
               jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
               jnp.zeros((1,), jnp.int32))
sys.exit('ray_dynamic_batching_tpu.models.ssm' in sys.modules)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300).returncode == 0


# --- the configuration file and the view -----------------------------------------
def test_the_configuration_file_holds_the_published_keys_and_one_cut():
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "falcon-h1-34b-1chip.json").read_text())
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 72}
    assert cfg["num_hidden_layers"] == 6
    dc = cfg["program"]["decoder_config"]
    built = DecoderConfig(**dc)
    # every program key is the published key's value
    assert (built.d_model, built.num_heads, built.num_kv_heads,
            built.head_dim, built.mlp_dim, built.vocab_size) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["intermediate_size"], cfg["vocab_size"])
    assert (built.ssm_state, built.ssm_heads, built.ssm_head_dim,
            built.ssm_groups, built.ssm_chunk, built.conv_kernel,
            built.conv_bias, built.d_ssm) == (
        cfg["mamba_d_state"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
        cfg["mamba_n_groups"], cfg["mamba_chunk_size"], cfg["mamba_d_conv"],
        cfg["mamba_conv_bias"], cfg["mamba_d_ssm"])
    assert built.rope_theta == cfg["rope_theta"] == 1e11
    assert built.rms_eps == cfg["rms_norm_eps"]
    assert not built.tie_embeddings and not cfg["tie_word_embeddings"]
    for name, index in TWELVE:
        mine, theirs = getattr(built, name), cfg[name]
        if index is not None:
            mine, theirs = mine[index], theirs[index]
        assert mine == theirs, name
    assert built.conv_width == 5120 and built.num_layers == 6
    llm = cfg["deployment"]["llm"]
    assert (llm["num_slots"], llm["max_len"], llm["kv_pool_pages"],
            llm["page_size"]) == (64, 1024, 512, 128)
    check = cfg["reference_check"]
    assert check["prompt_lens"] == [300, 700, 900, 520]
    assert max(check["prompt_lens"]) + check["new_tokens"] <= llm["max_len"]


def test_the_views_draws_give_products_of_unit_size_after_their_multiplier(
        view):
    """Every kernel that a multiplier follows is drawn at its fan-in rule
    divided by that multiplier: the product has unit size AFTER it. The
    view's constants are the configuration file's published values."""
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "falcon-h1-34b-1chip.json").read_text())
    assert view._AFTER == {
        "tok_embed": cfg["embedding_multiplier"],
        "lm_head": cfg["lm_head_multiplier"], "k": cfg["key_multiplier"],
        "o": cfg["attention_out_multiplier"],
        "ssm_out": cfg["ssm_out_multiplier"],
        "mlp_gate": cfg["mlp_multipliers"][0],
        "mlp_down": cfg["mlp_multipliers"][1]}
    assert view._SSM_IN == cfg["ssm_in_multiplier"]
    assert list(view._ZONES) == cfg["ssm_multipliers"]
    D_, hid = 5120, 21504
    cases = {
        ("lm_head", "kernel"): ((D_, 261120), D_, cfg["lm_head_multiplier"]),
        ("k", "kernel"): ((D_, 4, 128), D_, cfg["key_multiplier"]),
        ("o", "kernel"): ((20, 128, D_), 2560,
                          cfg["attention_out_multiplier"]),
        ("ssm_out", "kernel"): ((4096, D_), 4096, cfg["ssm_out_multiplier"]),
        ("mlp_gate", "kernel"): ((D_, hid), D_, cfg["mlp_multipliers"][0]),
        ("mlp_down", "kernel"): ((hid, D_), hid, cfg["mlp_multipliers"][1]),
    }
    for (parent, leaf), (shape, fan_in, mult) in cases.items():
        mean, std = view.seeding(["params", "layer0", parent, leaf], shape)
        assert mean == 0.0
        assert std * mult * fan_in ** 0.5 == pytest.approx(1.0)
    mean, std = view.seeding(["params", "tok_embed", "embedding"],
                             (261120, D_))
    assert std * cfg["embedding_multiplier"] * D_ ** 0.5 == pytest.approx(1.0)
    # the kernels no multiplier follows keep the common table's rule
    for parent in ("q", "v", "mlp_up"):
        assert view.seeding(["params", "layer0", parent, "kernel"],
                            (D_, 8)) is None
    # the decay a token lies between 0.999 and a fifth
    a_mean, a_std = view.seeding(["params", "layer0", "ssm_A_log"], (32,))
    d_mean, d_std = view.seeding(["params", "layer0", "ssm_dt_bias"], (32,))
    assert np.exp(a_mean) == pytest.approx(4.0) and 0.7 < a_std < 0.9
    assert np.log1p(np.exp(d_mean)) == pytest.approx(0.01, rel=1e-3)
    assert view.seeding(["params", "layer0", "ssm_D"], (32,)) == (1.0, 0.1)
    assert view.seeding(["params", "layer0", "conv_taps"], (4, 5120)) == (
        0.0, 0.5)


# --- what the widths forced beside the state ---------------------------------------
def test_a_wide_vocabularys_chunk_makes_one_row_of_logits_a_sequence(
        model, params, tokens, monkeypatch):
    """A row of logits over ``CHUNK_LOGITS_ROW_BYTES`` (Falcon-H1's 261,120
    columns: 1.07 GB for two 512-row chunks): the head reads the taken rows
    alone. The same
    logits as the program that makes every row, and no array ``[.., W, V]``
    in its equations; under the bound (every other configuration) the
    program is the one it was."""
    from ray_dynamic_batching_tpu.models import causal_lm
    from tests.test_lfm2 import _equations

    toks = np.zeros((2, 64), np.int32)
    mask = np.zeros((2, 64), np.int32)
    toks[0, :41], mask[0, :41] = tokens[:41], 1
    toks[1, :64], mask[1, :64] = tokens[100:164], 1
    args = (jnp.asarray(toks), jnp.asarray(mask))
    rest = (jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.asarray([40, 63], jnp.int32))

    def run():
        cache = model.make_paged_cache(2, 8, PAGE, MAX_LEN)
        fn = lambda p, c: model.prefill_chunk_paged(  # noqa: E731
            p, *args, c, *rest, state_slots=jnp.asarray([0, 1], jnp.int32))
        widths = {tuple(o.aval.shape) for e in _equations(
            jax.make_jaxpr(fn)(params, cache).jaxpr) for o in e.outvars
            if getattr(o.aval, "shape", ())[-1:] == (TINY.vocab_size,)}
        return np.asarray(fn(params, cache)[0]), widths

    every, widths = run()
    assert (2, 64, TINY.vocab_size) in widths
    assert 4 * TINY.vocab_size <= causal_lm.CHUNK_LOGITS_ROW_BYTES == 2 ** 19
    monkeypatch.setattr(causal_lm, "CHUNK_LOGITS_ROW_BYTES", 1024)
    taken, widths = run()
    assert (2, 64, TINY.vocab_size) not in widths
    assert (2, 1, TINY.vocab_size) in widths
    np.testing.assert_allclose(taken, every, rtol=1e-5, atol=1e-5)
    # the published vocabulary passes the bound; the widest other one
    # (LFM2's 65,536) does not
    assert 4 * 261120 > 2 ** 19 >= 4 * 65536


def test_four_heads_of_128_are_gathered_through_the_tile_view():
    """``ops/attention.py::_pages``: a pool whose position is 4 rows of 128
    lanes (Falcon-H1's 4 KV heads; LFM2's 8 heads of 64, two a row) is
    gathered through its ``[ps * rows // 8, 8, 128]`` view: the same pages,
    bit for bit. A selecting layer's gather and an 8-row pool keep
    ``pool[layer, safe]``."""
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(3, 6, 16, 4, 128)), jnp.bfloat16)
    safe = jnp.asarray([[5, 0, 2], [1, 1, 4]], jnp.int32)
    got = attn_ops._pages(pool, 1, safe, 4)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(pool[1, safe], np.float32))

    def reshapes(*a, **kw):
        jaxpr = jax.make_jaxpr(lambda p: attn_ops._pages(p, *a, **kw))
        return sum(e.primitive.name == "reshape"
                   for e in jaxpr(pool).jaxpr.eqns)

    assert reshapes(1, safe, 4) == 2               # the view, and back
    assert reshapes(1, safe, 8) == 2               # two heads a row (LFM2)
    assert reshapes(1, safe, 4, select=object()) == 0      # Keye's: as it was
    wide = jnp.zeros((3, 6, 16, 8, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p: attn_ops._pages(p, 1, safe, 8))(wide)
    assert not any(e.primitive.name == "reshape" for e in jaxpr.jaxpr.eqns)


def test_five_query_heads_a_kv_head_are_given_as_whole_sublane_tiles():
    """``ops/decode_attention.py::_group``: a narrow head block's flat fold
    wants its rows in whole sublane tiles. 4 KV heads x 5 are 20 rows: given
    as 4 x 6 (the per-head form they would fall to costs 6.6 us a live page
    against 0.35); every group the benchmark had is returned as it is."""
    group = decode_attention._group
    assert group(5, 4, 4, 1, None, None, 0, 1) == 6          # Falcon-H1
    assert group(5, 2, 2, 1, None, None, 0, 1) == 8          # this file's
    assert group(5, 1, 1, 1, None, None, 0, 1) == 8
    assert group(3, 4, 4, 1, None, None, 0, 1) == 4
    # a power of two, whole tiles or not, and a block of 8: as they were
    for g, kb in ((4, 4), (8, 4), (16, 4), (4, 8), (5, 8), (1, 8), (8, 1),
                  (1, 1), (2, 1), (4, 1), (1, 2), (2, 2), (1, 4)):
        assert group(g, kb, kb, 1, None, None, 0, 1) == g
    assert group(1, 1, 1, 5, None, None, 0, 1) == 1      # a spec window
    # a block that is not all of K, an int8 pool, a sink, a mesh: as it is
    assert group(5, 4, 8, 1, None, None, 0, 1) == 5
    assert group(5, 4, 4, 1, object(), None, 0, 1) == 5
    assert group(5, 4, 4, 1, None, object(), 0, 1) == 5
    assert group(5, 4, 4, 1, None, None, 128, 1) == 5
    assert group(5, 4, 4, 1, None, None, 0, 2) == 5
    x = jnp.arange(2 * 3 * 1 * 5 * 4, dtype=jnp.float32).reshape(2, 3, 1, 5, 4)
    padded = decode_attention._rows(x, 6)
    assert padded.shape == (2, 3, 1, 6, 4)
    assert not np.asarray(padded[:, :, :, 5]).any()
    np.testing.assert_array_equal(decode_attention._rows(padded, 5), x)
    assert decode_attention._rows(x, 5) is x
