"""OLMoE on the normal path, against the plain reference the benchmark keeps
(``benchmark/reference/olmoe.py``, read through ``benchmark/views/olmoe.py``;
both loaded by path: they import nothing of the program): QK-norm over the
whole projection before RoPE, dropless top-k routing with the gates as the
softmax gave them, computed as a grouped matmul over rows sorted by expert.
CPU, float32, seeded weights, a tiny OLMoE (2 layers, d 64, 4 heads, 8
experts top-3), compared on LOGITS."""

import dataclasses
import hashlib
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
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.causal_lm import OLMOE_1B_7B, CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.models.moe import MoEBlock, RoutingRule
from ray_dynamic_batching_tpu.ops import moe as moe_ops

ROOT = Path(__file__).resolve().parents[1]

TINY = DecoderConfig(
    vocab_size=512, d_model=64, num_layers=2, num_heads=4, num_kv_heads=4,
    mlp_dim=128, max_seq_len=256, num_experts=8, moe_top_k=3,
    moe_renormalize=False, qk_norm=True,
)
SIZES = {"rms_norm_eps": 1e-5, "num_attention_heads": 4,
         "num_key_value_heads": 4, "rope_theta": 10000.0,
         "num_experts_per_tok": 3, "norm_topk_prob": False,
         "program": {"decoder_config": {"num_layers": 2}}}

# Program and reference both compute in float32 here (the reference at
# matmul precision "highest", which is the CPU's only one), so they differ
# by summation order alone: on logits whose spread is 1.0 the worst gap
# read 1.7e-6 through the full forward and 1.5e-6 through chunked prefill +
# batched decode. The same model in bfloat16 reads 3.6e-2
# (``test_bfloat16_fails_the_tolerance``) and the wrong pieces of arithmetic
# below 3.8e-2 (gates renormalised) to 0.68 (the norm per head): 1e-4 sits
# two decades from the one side and more from the other.
TOL = 1e-4


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "olmoe_" + Path(rel).stem + "_" + Path(rel).parent.name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/olmoe.py")


@pytest.fixture(scope="module")
def view():
    return _load("benchmark/views/olmoe.py")


def _seeded(model, view, seed=0):
    """``model.init``'s tree with the q/k norm scales drawn as the view's
    seeding rule says (mean 1, std 0.1): with scales of one, a norm after
    RoPE or per head would be the same function."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

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
def model():
    return CausalLM(TINY, name="olmoe_tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model, view):
    return _seeded(model, view)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(7).integers(1, TINY.vocab_size, 29)


def _full(model, params, tokens):
    t = jnp.asarray(tokens, jnp.int32)[None]
    return np.asarray(model.apply(params, t, jnp.ones_like(t))[0])


def _gap(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


# --- (a) the full forward ---------------------------------------------------
def test_full_forward_matches_the_reference(model, params, view, ref, tokens):
    want = ref.logits(view.view(params, SIZES), tokens, SIZES)
    assert _gap(_full(model, params, tokens), want) < TOL


def test_bfloat16_fails_the_tolerance(params, view, ref, tokens):
    """The tolerance is tight enough to tell a lower precision."""
    low = CausalLM(TINY, name="olmoe_tiny_bf16", dtype=jnp.bfloat16)
    want = ref.logits(view.view(params, SIZES), tokens, SIZES)
    assert _gap(_full(low, params, tokens), want) > 10 * TOL


# --- (b) chunked prefill through the paged cache, then batched decode --------
PAGE, N_PAGES, MAX_LEN, SLOTS, W = 8, 32, 64, 4, 8
PROMPT = 21           # chunks at 0, 8, 16: the last holds 5 tokens + 3 pads
PAGES_A = [3, 7, 1, 9, 12, 30, 2, 5]
PAGES_B = [4, 8, 0, 11, 13, 31, 6, 10]


def _serve(model, params, tokens, other, pad_token=0, idle_token=0):
    """``tokens`` (and ``other``, a second sequence beside it) prefilled in
    W-wide chunks through page tables, then decoded one token at a time in
    a batch of SLOTS slots of which two are inactive. Returns the logits of
    every position of ``tokens`` [T, V], and the routing counters of each
    chunk and of each decode step."""
    chunk = jax.jit(model.prefill_chunk_paged,
                    static_argnames=("moe_counters",))
    step = jax.jit(model.decode_step_paged,
                   static_argnames=("moe_counters",))
    cache = model.make_paged_cache(SLOTS, N_PAGES, PAGE, MAX_LEN)
    tables = jnp.asarray([PAGES_A, PAGES_B], jnp.int32)
    rows = [np.asarray(tokens[:PROMPT]), np.asarray(other[:13])]
    logits = np.zeros((len(tokens), model.cfg.vocab_size), np.float32)
    counters = []
    for start in range(0, PROMPT, W):
        toks = np.full((2, W), pad_token, np.int32)
        mask = np.zeros((2, W), np.int32)
        for r, row in enumerate(rows):
            piece = row[start:start + W]
            toks[r, :len(piece)] = piece
            mask[r, :len(piece)] = 1
        starts = jnp.full((2,), start, jnp.int32)
        for j in range(int(mask[0].sum())):
            taken, new_cache, c = chunk(
                params, jnp.asarray(toks), jnp.asarray(mask), cache, tables,
                starts, jnp.asarray([j, 0], jnp.int32), moe_counters=True)
            logits[start + j] = np.asarray(taken[0])
        counters.append(np.asarray(c))
        cache = cache.replace(k=new_cache.k, v=new_cache.v)
    sentinel = jnp.full((len(PAGES_A),), N_PAGES, jnp.int32)
    cache = cache.replace(
        page_table=jnp.stack([sentinel, tables[0], sentinel, tables[1]]),
        lengths=jnp.asarray([0, PROMPT, 0, 13], jnp.int32))
    active = jnp.asarray([False, True, False, True])
    for pos in range(PROMPT, len(tokens)):
        feed = jnp.asarray(
            [idle_token, tokens[pos], idle_token, other[pos - 8]],
            jnp.int32)[:, None]
        out, cache, c = step(params, feed, cache, active, moe_counters=True)
        logits[pos] = np.asarray(out[1])
        counters.append(np.asarray(c))
    return logits, np.stack(counters)


@pytest.fixture(scope="module")
def other():
    return np.random.default_rng(8).integers(1, TINY.vocab_size, 29)


@pytest.fixture(scope="module")
def served(model, params, tokens, other):
    return _serve(model, params, tokens, other)


def test_prefill_in_chunks_then_batched_decode_matches_the_reference(
        served, params, view, ref, tokens):
    """Every position: 21 through three chunks of a padded bucket beside
    another sequence, 8 through single-token steps beside idle slots,
    against the reference's ONE full forward."""
    want = ref.logits(view.view(params, SIZES), tokens, SIZES)
    assert _gap(served[0], want) < TOL


# --- (c) controls: each wrong arithmetic must FAIL the tolerance --------------
def _attention_variant(ref, mode):
    """The reference's attention with the q/k norm misplaced."""
    import math

    def attention(x, w, n_head, n_kv, eps, theta):
        T, D = x.shape
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        for k in ("wq", "wk", "wv"):
            w[k] = w[k].reshape(D, -1)
        w["wo"] = w["wo"].reshape(-1, D)
        H = w["wq"].shape[1] // n_head
        h = ref._rms(x, w["ln1_g"], eps)
        q, k = h @ w["wq"], h @ w["wk"]
        if mode == "per_head":
            q = ref._rms(q.reshape(T, n_head, H),
                         w["q_norm_g"].reshape(n_head, H), eps)
            k = ref._rms(k.reshape(T, n_kv, H),
                         w["k_norm_g"].reshape(n_kv, H), eps)
            q, k = ref._rope(q, theta), ref._rope(k, theta)
        else:  # after RoPE
            q = ref._rope(q.reshape(T, n_head, H), theta).reshape(T, -1)
            k = ref._rope(k.reshape(T, n_kv, H), theta).reshape(T, -1)
            q = ref._rms(q, w["q_norm_g"], eps).reshape(T, n_head, H)
            k = ref._rms(k, w["k_norm_g"], eps).reshape(T, n_kv, H)
        v = (h @ w["wv"]).reshape(T, n_kv, H)
        s = jnp.einsum("tnh,snh->nts", q, k) / math.sqrt(H)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
        a = jnp.einsum("nts,snh->tnh", jax.nn.softmax(s, axis=-1), v)
        return x + a.reshape(T, n_head * H) @ w["wo"]

    return attention


@pytest.mark.parametrize("wrong", [
    "gates_renormalised", "qk_norm_per_head", "qk_norm_after_rope",
    "one_experts_rows_dropped"])
def test_wrong_arithmetic_fails_the_tolerance(
        wrong, model, params, view, ref, tokens, monkeypatch):
    served_model, served_params = model, params
    if wrong == "gates_renormalised":
        served_model = CausalLM(
            dataclasses.replace(TINY, moe_renormalize=True), name="renorm",
            dtype=jnp.float32)
    elif wrong == "one_experts_rows_dropped":
        routing = []
        ref.logits(view.view(params, SIZES), tokens, SIZES, routing)
        busiest = int(np.bincount(np.asarray(routing[0]).ravel()).argmax())
        served_params = jax.tree_util.tree_map(lambda x: x, params)
        moe = served_params["params"]["layer0"]["moe"]
        moe["wo"] = moe["wo"].at[busiest].set(0.0)   # its rows give nothing
    else:
        monkeypatch.setattr(ref, "_attention", _attention_variant(
            ref, "per_head" if wrong == "qk_norm_per_head" else "after"))
    want = ref.logits(view.view(params, SIZES), tokens, SIZES)
    assert _gap(_full(served_model, served_params, tokens), want) > 10 * TOL


# --- (d) the grouped path against every expert on every token -----------------
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("skew", ["all_to_one_expert", "three_of_eight"])
def test_grouped_path_matches_every_expert_then_the_chosen_k(backend, skew):
    """Skewed routing: every token to ONE expert (seven groups empty), and
    every token to the same three of eight. The Pallas kernel runs
    interpreted here, on the same sorted rows and group sizes."""
    D, F, E = 128, 256, 8
    k = 1 if skew == "all_to_one_expert" else 3
    block = MoEBlock(d_model=D, mlp_dim=F, num_experts=E, top_k=k,
                     rule=RoutingRule(renormalize=False),
                     dtype=jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((2, 9, D)), jnp.float32)
    p = block.init(jax.random.PRNGKey(2), x)
    # a router that ignores the token: x[..., 0] is one and only the
    # kernel's row 0 is not zero, so every token's logits are that row
    x = x.at[..., 0].set(1.0)
    logits = np.full(E, -5.0, np.float32)
    logits[[5] if k == 1 else [1, 4, 6]] = [3.0] if k == 1 else [3.0, 2.0, 1.0]
    p["params"]["router"]["kernel"] = jnp.zeros((D, E)).at[0].set(logits)
    moe_ops.set_moe_backend(backend)
    moe_ops.clear_moe_paths()
    try:
        got = block.apply(p, x)
        took = moe_ops.moe_paths()[-1].path
    finally:
        moe_ops.set_moe_backend("auto")
    assert took == (moe_ops.PATH_KERNEL if backend == "pallas"
                    else moe_ops.PATH_XLA)
    w, h = p["params"], x.reshape(-1, D)
    gates = jax.nn.softmax(h @ w["router"]["kernel"], axis=-1)
    top, idx = jax.lax.top_k(gates, k)
    every = jnp.einsum(
        "etf,efd->etd",
        jax.nn.silu(jnp.einsum("td,edf->etf", h, w["wg"]))
        * jnp.einsum("td,edf->etf", h, w["wi"]), w["wo"])
    weight = (jax.nn.one_hot(idx, E) * top[..., None]).sum(1)
    want = jnp.einsum("te,etd->td", weight, every).reshape(x.shape)
    assert len(np.unique(np.asarray(idx))) == k
    assert _gap(got, want) < 1e-5


@pytest.mark.parametrize("how", ["with_mesh", "tensor_parallel_slice"])
def test_the_kernel_declines_under_a_mesh_and_says_why(how):
    """GSPMD cannot partition a Pallas call: under a mesh the block takes
    the XLA form (and the strict backend raises with the reason)."""
    from jax.sharding import Mesh

    from ray_dynamic_batching_tpu.ops.attention import tensor_parallel

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    xs = jnp.zeros((16, 128), jnp.float32)
    w = jnp.zeros((4, 128, 128), jnp.float32)
    sizes = jnp.asarray([4, 4, 4, 4], jnp.int32)
    moe_ops.set_moe_backend("pallas")
    try:
        with (mesh if how == "with_mesh" else tensor_parallel(mesh)):
            with pytest.raises(moe_ops.MoEDeclined, match="mesh"):
                moe_ops.expert_mlp(xs, sizes, w, w, w)
    finally:
        moe_ops.set_moe_backend("auto")


# --- (e) pads and idle slots change no real row and no counter -----------------
def test_pad_tokens_and_idle_slots_change_nothing(
        served, model, params, tokens, other):
    again = _serve(model, params, tokens, other, pad_token=77, idle_token=123)
    assert _gap(served[0], again[0]) < 1e-6
    np.testing.assert_array_equal(served[1], again[1])


def test_counters_count_real_tokens_only(served, params, view, ref, tokens,
                                         other):
    """Each chunk's and each decode step's [rows, experts hit, most rows,
    pairs] (every expert is held: pairs = rows) against counts made by hand from the reference's top-k of the same
    tokens."""
    k, E = TINY.moe_top_k, TINY.num_experts
    routing = {}
    for name, seq in (("a", tokens), ("b", other[:21])):
        routing[name] = []
        ref.logits(view.view(params, SIZES), seq, SIZES, routing[name])

    def count(picks):  # picks: per layer, the chosen experts of real tokens
        took = [np.bincount(np.asarray(p).ravel(), minlength=E)
                for p in picks]
        rows = sum(t.sum() for t in took)
        return [rows, sum((t > 0).sum() for t in took),
                max(t.max() for t in took), rows]

    want = []
    for start in range(0, PROMPT, W):
        want.append(count([
            np.concatenate([la[start:min(start + W, PROMPT)].ravel(),
                            lb[start:min(start + W, 13)].ravel()])
            for la, lb in zip(routing["a"], routing["b"])]))
    for pos in range(PROMPT, len(tokens)):
        want.append(count([
            np.concatenate([la[pos].ravel(), lb[pos - 8].ravel()])
            for la, lb in zip(routing["a"], routing["b"])]))
    np.testing.assert_array_equal(served[1], np.asarray(want))
    assert served[1][-1][0] == 2 * k * TINY.num_layers   # two real rows


# --- (f) the engine's Turn counters ---------------------------------------------
def _engine(model, params, **kw):
    queue = RequestQueue(model.name, max_len=64)
    opts = dict(num_slots=4, max_len=256, prompt_buckets=[16], paged=True,
                page_size=128, kv_pool_pages=8, decode_horizon=1,
                max_admissions_per_step=1, default_max_new_tokens=6)
    opts.update(kw)
    return DecodeEngine(model, params, queue, **opts), queue


def test_turn_counters_match_hand_counts_from_the_reference(
        model, params, view, ref, tokens):
    engine, queue = _engine(model, params)
    prompt = [int(t) for t in tokens[:11]]
    req = Request(model=model.name, slo_ms=60_000.0, payload={
        "tokens": np.asarray(prompt, np.int32), "max_new_tokens": 6})
    queue.add_request(req)
    engine.run_until_idle(timeout_s=120)
    out = list(req.future.result(timeout=5).tokens)
    routing = []
    ref.logits(view.view(params, SIZES), prompt + out, SIZES, routing)
    k, L, E = TINY.moe_top_k, TINY.num_layers, TINY.num_experts
    turns = list(engine.turns)
    chunks = [t for t in turns if t.kind == "chunk"]
    scans = [t for t in turns if t.kind == "turn"]
    assert len(chunks) == 1 and len(scans) == len(out) - 1
    took = [np.bincount(np.asarray(r[:len(prompt)]).ravel(), minlength=E)
            for r in routing]
    assert (chunks[0].moe_rows, chunks[0].moe_experts_hit,
            chunks[0].moe_max_rows) == (
        len(prompt) * k * L, sum(int((t > 0).sum()) for t in took),
        max(int(t.max()) for t in took))
    for t in scans:   # one substep, one real row: k distinct experts a layer
        assert (t.moe_rows, t.moe_experts_hit, t.moe_max_rows) == (
            k * L, k * L, 1)
    summary = engine.turn_summary()
    rows = sum(t.moe_rows for t in turns)
    hit = sum(t.moe_experts_hit for t in turns)
    assert summary["moe_rows_per_expert"] == pytest.approx(rows / hit)
    assert summary["moe_imbalance"] == pytest.approx(
        chunks[0].moe_max_rows / (rows / hit))
    snap = engine.snapshot()["moe"]
    assert snap["rows_per_expert"] == summary["moe_rows_per_expert"]
    # (h) the CPU takes the XLA path and says so
    assert snap["paths"] and all("XLA ragged_dot" in p for p in snap["paths"])
    assert {p.path for p in moe_ops.moe_paths()
            if p.program in ("decode_step", "chunk_prefill")
            } == {moe_ops.PATH_XLA}


def test_summarize_turns_of_a_dense_ring_has_no_expert_keys():
    dense = [Turn("turn", 0.0, 1.0, 2.0, 3.0, 8, 0, 4, 0, 0, 0, 0, False)] * 3
    out = summarize_turns(dense, num_slots=4)
    assert "moe_rows_per_expert" not in out and "moe_imbalance" not in out
    assert dense[0].moe_rows == dense[0].moe_experts_hit == 0


# --- (g) a dense preset's programs are the parent's -----------------------------
# sha256 of the StableHLO text of llama_tiny's decode (h = 2) and paged chunk
# (group 1, width 16) programs, taken from the parent commit (d37a358) on the
# CPU with this file's ``_lowered``: an expert model's counters, and the q/k
# norms, must leave a dense model's programs as they were. ``chunk_prefill``
# was retaken in PR 47, whose commit changes that program ON PURPOSE (its
# per-dispatch state arrives as one packed int32 upload, cut by static
# offsets, where it took six arrays). ``decode_step`` was retaken in PR 56,
# which changes that program ON PURPOSE (a fourth row of the per-dispatch
# state, "use the carry", a ``[B]`` operand of carried tokens selected by it
# before the scan, and the scan's last tokens as a fourth result: the text's
# whole difference from d432e58's, which was 66de37d's byte for byte);
# ``chunk_prefill`` did not move with it.
PARENT = json.loads(
    (Path(__file__).resolve().parent / "data"
     / "dense_program_digests.json").read_text())


def _lowered(engine):
    B, K = engine.num_slots, engine.max_bias_entries
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    shapes = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    p, c = shapes(engine.params), shapes(engine._cache)
    decode = engine._decode_fn.__wrapped__.lower(
        p, c, sds((4, B), i32), 2, sds((4, B), f32), sds((2, B), i32),
        sds((B, K), i32), sds((B, K), f32), shapes(engine._counts),
        sds((B,), i32))
    chunk = engine._chunk_paged_fn.__wrapped__.lower(
        p, sds(engine._new_chunk_group(1, 16)[0].shape, i32), c)
    return {"decode_step": decode, "chunk_prefill": chunk}


@pytest.mark.parametrize("program", ["decode_step", "chunk_prefill"])
def test_a_dense_presets_program_lowers_to_the_parents_text(program):
    dense = get_model("llama_tiny", dtype=jnp.float32)
    engine, _ = _engine(dense, dense.init(jax.random.PRNGKey(0)))
    lowered = _lowered(engine)[program]
    assert hashlib.sha256(
        lowered.as_text().encode()).hexdigest() == PARENT[program]
    out = jax.tree_util.tree_leaves(lowered.out_info)[0]
    # tokens + advanced + lengths rows and nothing else; ids and nothing else
    assert out.shape == ((5, 4) if program == "decode_step" else (1,))
    assert not any("norm" in k and k != "attn_norm" and k != "mlp_norm"
                   for k in engine.params["params"]["layer0"])


def test_an_expert_models_programs_carry_the_counters(model, params):
    engine, _ = _engine(model, params)
    low = _lowered(engine)
    assert jax.tree_util.tree_leaves(
        low["decode_step"].out_info)[0].shape == (5 + 4, 4)
    assert jax.tree_util.tree_leaves(
        low["chunk_prefill"].out_info)[0].shape == (1 + 4,)


def test_the_published_preset_is_registered_with_the_published_sizes():
    m = get_model("olmoe_1b_7b")
    c = m.cfg
    assert c is OLMOE_1B_7B and m.has_experts
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.head_dim,
            c.mlp_dim, c.num_experts, c.moe_top_k, c.vocab_size) == (
        16, 2048, 16, 16, 128, 1024, 64, 8, 50304)
    assert c.qk_norm and not c.moe_renormalize and not c.tie_embeddings
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))["params"]["layer0"]
    assert shapes["q_norm"]["scale"].shape == (2048,)
    assert shapes["moe"]["wi"].shape == (64, 2048, 1024)
    assert shapes["moe"]["wo"].shape == (64, 1024, 2048)
    assert shapes["moe"]["router"]["kernel"].shape == (2048, 64)
