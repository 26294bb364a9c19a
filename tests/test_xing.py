"""One rank's share of a Xing4.0-shaped model on the normal path, against the
plain reference the benchmark keeps (``benchmark/reference/xing.py``, read
through ``benchmark/views/xing.py``; both loaded by path: they import nothing
of the program): LATENT attention (low-rank q and kv paths, a head of a part
without positions and a rotary part whose ONE key a position all heads
share, YaRN) served through a pool of rows with no head axis, absorbed in
decode; a residual path of four STREAMS mixed at every sublayer by maps of
the streams themselves, the stream-to-stream one Sinkhorn-normalised; two
dense layers, then sigmoid top-k routing with a selection bias over ALL
experts of which some are held here, and a shared expert. CPU, float32,
seeded weights, tiny widths that keep the published ratios (4 streams, 2
dense layers + 2 expert layers, d 64, 4 heads of 32 + 16 with values of 32
on a latent of 128 + 16: the rotary part a third of the key, 16 experts
top-4 of which 4 are held, one shared), compared on LOGITS.
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
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.models.kv_state import PagedKVCache
from ray_dynamic_batching_tpu.models.moe import MoEBlock, RoutingRule
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

ROOT = Path(__file__).resolve().parents[1]

E, HELD, TOP_K = 16, 4, 4
NOPE, ROPE, HV, RANK = 32, 16, 32, 128
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}
TINY = DecoderConfig(
    vocab_size=256, d_model=64, num_layers=4, num_heads=4, num_kv_heads=4,
    head_dim=NOPE + ROPE, v_head_dim=HV, rope_dim=ROPE, mlp_dim=32,
    max_seq_len=1024, rms_eps=1e-6, rope_theta=10000.0,
    kv_lora_rank=RANK, q_lora_rank=48,
    rope_yarn_factor=64.0, rope_yarn_original=64, rope_yarn_beta_fast=32.0,
    rope_yarn_beta_slow=1.0, rope_yarn_mscale=1.0,
    rope_yarn_mscale_all_dim=1.0,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=(-30.0, 30.0),
    num_dense_layers=2, dense_mlp_dim=96, num_experts=E, moe_top_k=TOP_K,
    moe_renormalize=True, moe_scoring="sigmoid", moe_selection_bias=True,
    moe_gate_scale=2.0, moe_first_expert=HELD, moe_held_experts=HELD,
    moe_shared_experts=1,
)
SIZES = {
    "rms_norm_eps": 1e-6, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_theta": 10000.0, "rope_scaling": YARN,
    "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE,
    "num_experts_per_tok": TOP_K, "routed_scaling_factor": 2,
    "expert_parallel": {"first_expert": HELD},
    "program": {"decoder_config": {"num_layers": 4}},
}

# Program and reference both compute in float32 here, so they differ by
# summation order alone (worst gap read: 2e-5 on logits whose spread is 1);
# every wrong piece of arithmetic below moves them by hundredths or more.
TOL = 2e-4


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "xing_" + Path(rel).stem + "_" + Path(rel).parent.name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/xing.py")


@pytest.fixture(scope="module")
def view():
    return _load("benchmark/views/xing.py")


def _seeded(model, view, seed=0):
    """``model.init``'s tree with every leaf the view has a rule for drawn by
    it (the maps' static parts, the gains, the low-rank norms' scales, the
    selection bias): at their initial zeros and ones, dropping one would be
    (nearly) the same function."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        rule = view.seeding(names, tuple(x.shape))
        if rule is None:
            return x
        k = jax.random.fold_in(
            key, zlib.crc32("/".join(names).encode()) % (2 ** 31))
        return rule[0] + rule[1] * jax.random.normal(k, x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def model():
    return CausalLM(TINY, name="xing_tiny", dtype=jnp.float32)


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
    kinds = [TINY.layer_kind(i) for i in range(4)]
    assert all(k.latent and not k.window and k.pool_layer == -1
               for k in kinds)
    assert [k.sparse for k in kinds] == [False, False, True, True]
    assert [k.mlp_dim for k in kinds] == [96, 96, 32, 32]
    assert TINY.latent and not TINY.kv_by_kind
    plain = DecoderConfig(vocab_size=8, d_model=64, num_layers=2,
                          num_heads=4, num_kv_heads=4, mlp_dim=96)
    assert not plain.latent and not plain.layer_kind(0).latent
    assert plain.hc_mult == 1


# --- chunked prefill through the latent pool, then batched absorbed decode -----
SLOTS = 4


def _serve(model, params, tokens, other, page, W, prompt, before=0):
    """In slot 1: first ``before`` tokens of ``other`` (a tenant whose rows
    stay behind in the slot's pages: nothing is cleared), then ``tokens``:
    ``prompt`` of them prefilled in W-wide chunks through the slot's
    page-table row, the rest decoded one token at a time in a batch of SLOTS
    slots of which slot 3 decodes ``other`` and two are idle. Returns the
    logits of every decoded position and of each chunk's last."""
    n_entries = TINY.max_seq_len // page
    n_pages = 2 * n_entries
    chunk = jax.jit(model.prefill_chunk_paged)
    step = jax.jit(model.decode_step_paged)
    cache = model.make_paged_cache(SLOTS, n_pages, page, TINY.max_seq_len)
    assert cache.k is None and cache.v is None
    rng = np.random.default_rng(3)
    tables = np.stack([rng.permutation(n_pages)[:n_entries],
                       np.arange(n_entries)]).astype(np.int32)
    tables[1] = np.setdiff1d(np.arange(n_pages), tables[0])[:n_entries]
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
                jnp.asarray([last, 0], jnp.int32))
            if keep:
                logits[start + last] = np.asarray(taken[0])
            cache = cache.replace(latent=new.latent)

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
    return logits


@pytest.mark.parametrize("backend, page, W, prompt", [
    ("xla", 16, 8, 270), ("pallas", 128, 16, 250)])
def test_chunks_then_batched_decode_through_the_latent_pool_match_the_reference(
        backend, page, W, prompt, model, params, tokens, other, want):
    """A slot whose pages held another tenant's 90 positions (nothing
    cleared), then ``prompt`` positions through chunks (keys and values
    EXPANDED from the pool's rows a block of pages at a time) beside another
    sequence, and the rest through single-token ABSORBED steps beside idle
    slots, past page edges (pages of 16: 2 more; of 128: position 256): the
    blocked walk on the CPU, or the latent kernel (and the Sinkhorn kernel),
    interpreted; against the reference's ONE full forward, which caches
    nothing."""
    attn_ops.set_attention_backend(backend)
    attn_ops.clear_attention_paths()
    try:
        served = _serve(model, params, tokens, other, page, W, prompt,
                        before=90)
    finally:
        attn_ops.set_attention_backend("auto")
    assert set(range(prompt, 300)) <= set(served)
    assert max(_gap(row, want[pos]) for pos, row in served.items()) < TOL
    paths = attn_ops.attention_paths()
    # the decode steps alone dispatch: a pool of rows RANK wide as values
    assert {p.v_dim for p in paths} == {RANK}
    assert {p.kv_shape for p in paths} == {
        (4, 2 * TINY.max_seq_len // page, page, 256)}
    assert {p.path for p in paths} == {
        attn_ops.PATH_PAGED_KERNEL if backend == "pallas"
        else attn_ops.PATH_BLOCKED}


# --- knock-outs: each wrong arithmetic must FAIL the tolerance -----------------
def _wrong_reference(ref, wrong):
    """The reference with ONE piece of its arithmetic replaced: patched
    attributes of the loaded module (undone by the caller's monkeypatch)."""
    def post_without_its_two(X, w, **kw):
        pre, post, res = maps(X, w, **kw)
        return pre, post / 2.0, res

    def res_transposed(X, y, post, res):
        return mix(X, y, post, jnp.swapaxes(res, -1, -2))

    def static_maps(X, w, **kw):
        return maps(X, dict(w, a=jnp.zeros_like(w["a"])), **kw)

    def key_unshared(x, inv_freq, gain):
        # the ONE rotary key (2-D: [T, rope]) not the one every head reads
        # at that position: each position is handed its neighbour's
        if x.ndim == 2:
            return rope(jnp.roll(x, 1, axis=0), inv_freq, gain)
        return rope(x, inv_freq, gain)

    maps, mix, rope, sinkhorn = ref.maps, ref.mix, ref._rope, ref.sinkhorn
    return {
        "h_post_without_its_2": ("maps", post_without_its_two),
        "one_sinkhorn_round": ("sinkhorn", lambda m, iters, eps: sinkhorn(
            m, 1, eps)),
        "h_res_transposed": ("mix", res_transposed),
        "static_maps_alone": ("maps", static_maps),
        "rotary_key_unshared": ("_rope", key_unshared),
        "m_squared_left_out": ("_mscale", lambda factor, m: 1.0),
        "yarn_blend_left_out": ("yarn_inv_freq", lambda dim, theta, s: (
            theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))),
    }[wrong]


@pytest.mark.parametrize("wrong", [
    "h_post_without_its_2", "one_sinkhorn_round", "h_res_transposed",
    "static_maps_alone", "rotary_key_unshared", "m_squared_left_out",
    "yarn_blend_left_out"])
def test_wrong_arithmetic_fails_the_tolerance(
        wrong, params, view, tokens, want, monkeypatch):
    """The program has no switch for any of these; the reference is read
    with the piece replaced (a fresh copy of the module: its jitted
    functions close over the patched names), and is another function."""
    fresh = _load("benchmark/reference/xing.py")
    name, patched = _wrong_reference(fresh, wrong)
    monkeypatch.setattr(fresh, name, patched)
    got = fresh.logits(view.view(params, SIZES), tokens, SIZES)
    assert _gap(got, want) > 50 * TOL, wrong


def test_bfloat16_fails_the_tolerance(params, tokens, want):
    low = CausalLM(TINY, name="xing_tiny_bf16", dtype=jnp.bfloat16)
    assert _gap(_full(low, params, tokens), want) > 50 * TOL


def test_the_program_runs_all_the_sinkhorn_rounds_and_the_clamp():
    """20 rounds leave rows and columns summing to one; a raw entry past
    the clamp is held to it (``exp(60)`` would swamp its row and column in
    float32 whatever the rounds do)."""
    from ray_dynamic_batching_tpu.models import hyper_connections as hc

    rng = np.random.default_rng(0)
    raw = rng.normal(size=(16, 40)).astype(np.float32)
    m = [[jnp.exp(jnp.asarray(raw[i * 4 + j]))[None] for j in range(4)]
         for i in range(4)]
    out = np.asarray(jnp.stack([jnp.concatenate(r) for r in hc.sinkhorn(
        m, 20, 1e-6)]))                                  # [4, 4, 40]
    assert np.abs(out.sum(0) - 1).max() < 1e-4
    assert np.abs(out.sum(1) - 1).max() < 1e-4
    once = np.asarray(jnp.stack([jnp.concatenate(r) for r in hc.sinkhorn(
        m, 1, 1e-6)]))
    assert np.abs(once.sum(0) - 1).max() > 1e-2
    # the kernel, interpreted, is the same rounds
    kern = hc._hc_sinkhorn(jnp.exp(jnp.asarray(raw)), iters=20, eps=1e-6,
                           interpret=True)
    assert _gap(kern.reshape(4, 4, 40), out) < 1e-6
    mod = hc.HyperConnection(n=4, iters=20, eps=1e-6, rms_eps=1e-6,
                             clamp=(-30.0, 30.0), dtype=jnp.float32)
    X = jnp.asarray(rng.normal(size=(1, 3, 4, 8)), jnp.float32)
    p = mod.init(jax.random.PRNGKey(0), X)["params"]
    at = lambda v: {"params": dict(  # noqa: E731  (the static part alone)
        p, a=jnp.zeros((3,)), b_res=jnp.zeros((4, 4)).at[0, 0].set(v))}
    _, (_, res) = mod.apply(at(60.0), X)
    _, (_, held) = mod.apply(at(30.0), X)
    _, (_, under) = mod.apply(at(20.0), X)
    assert _gap(res[0][0], held[0][0]) == 0.0
    assert _gap(under[0][1], held[0][1]) > 0.0


# --- the kernel and its fallback, side by side -----------------------------------
@pytest.mark.parametrize("lengths", [
    [0, 200, 0], [0, 639, 127], [3, 128, 255], [0, 511, 256], [1, 2, 640]])
def test_kernel_and_fallback_agree_at_every_length(lengths):
    """The latent kernel, interpreted, against the blocked walk in XLA: a
    length at a page's last position, at its first, an idle slot (length 0:
    its table all sentinel), a slot at the table's end."""
    from ray_dynamic_batching_tpu.ops import latent_attention as la

    rng = np.random.default_rng(0)
    L, P, ps, rank, rope, N = 2, 12, 128, 128, 64, 8
    Wp = la.row_width(rank, rope)
    assert Wp == 256 and la.row_width(512, 64) == 640
    pool = jnp.asarray(rng.normal(size=(L, P, ps, Wp)), jnp.float32)
    pool = pool.at[..., rank + rope:].set(0)
    table = jnp.asarray([[12] * 5, [5, 2, 7, 0, 3], [1, 4, 6, 8, 9]],
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, N, Wp)), jnp.float32)
    q = q.at[..., rank + rope:].set(0)
    lens = jnp.asarray(lengths, jnp.int32)
    want = la.absorbed(q, pool, table, lens, 1, rank=rank, scale=0.1)
    attn_ops.set_attention_backend("pallas")
    try:
        got = la.decode(q, pool, table, lens, 1, rank=rank, scale=0.1)
        with pytest.raises(attn_ops.AttentionDeclined, match="rows a slot"):
            la.decode(jnp.concatenate([q, q], 1), pool, table, lens, 1,
                      rank=rank, scale=0.1)
    finally:
        attn_ops.set_attention_backend("auto")
    assert got.shape == (3, 1, N, rank)
    live = [i for i, n in enumerate(lengths) if i]     # slot 0 idles
    assert _gap(got[jnp.asarray(live)], want[jnp.asarray(live)]) < 1e-5
    # the expanded form of the same rows is the same attention
    w = jnp.asarray(rng.normal(size=(rank, N, 32 + 16)) / 11.0, jnp.float32)
    q_n = jnp.asarray(rng.normal(size=(3, 2, N, 32)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(3, 2, N, rope)), jnp.float32)
    short = jnp.minimum(lens, 600)
    e = la.expanded(q_n, q_r, pool, w, table, short, 1, scale=0.1)
    q_a = jnp.concatenate(
        [jnp.einsum("btnh,rnh->btnr", q_n, w[..., :32]), q_r], -1)
    q_a = jnp.pad(q_a, ((0, 0),) * 3 + ((0, Wp - q_a.shape[-1]),))
    a = jnp.einsum("btnr,rnh->btnh", la.absorbed(
        q_a, pool, table, short, 1, rank=rank, scale=0.1), w[..., 32:])
    assert _gap(a[jnp.asarray(live)], e[jnp.asarray(live)]) < 1e-4


# --- the ranks' parts of an expert layer ---------------------------------------
D_BLOCK, F_BLOCK, RANKS = 32, 16, 8


def _block(first, held, shared):
    return MoEBlock(
        d_model=D_BLOCK, mlp_dim=F_BLOCK, num_experts=E, top_k=TOP_K,
        rule=RoutingRule("sigmoid", True, True, 2.0), first_expert=first,
        held_experts=held, shared_dim=F_BLOCK if shared else 0,
        dtype=jnp.float32)


def test_the_ranks_8_shares_add_up_to_the_uncut_layer(ref):
    """16 experts over 8 ranks of 2: every rank's partial result against
    the reference given that share, and their sum against the reference's
    whole layer, the shared expert counted ONCE (rank 0's)."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 24, D_BLOCK)), jnp.float32)
    p = _block(0, E, True).init(jax.random.PRNGKey(5), x)["params"]
    p = dict(p, selection_bias=jnp.asarray(
        0.1 * rng.normal(size=(E,)), jnp.float32))
    w = {"w_router": p["router"]["kernel"],
         "router_bias": p["selection_bias"], "we_up": p["wi"],
         "we_gate": p["wg"], "we_down": p["wo"],
         "ws_gate": p["shared_gate"]["kernel"],
         "ws_up": p["shared_up"]["kernel"],
         "ws_down": p["shared_down"]["kernel"]}
    flat = x.reshape(-1, D_BLOCK)
    held = E // RANKS
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.experts(flat, w, top_k=TOP_K, scale=2.0, first=0)
        parts = []
        for r in range(RANKS):
            cut = slice(r * held, (r + 1) * held)
            mine = {k: v for k, v in p.items() if r == 0
                    or not k.startswith("shared_")}
            part = np.asarray(_block(r * held, held, r == 0).apply(
                {"params": dict(mine, wi=p["wi"][cut], wg=p["wg"][cut],
                                wo=p["wo"][cut])}, x)).reshape(-1, D_BLOCK)
            theirs, _, _ = ref.experts(
                flat, dict(w, we_up=w["we_up"][cut],
                           we_gate=w["we_gate"][cut],
                           we_down=w["we_down"][cut]),
                top_k=TOP_K, scale=2.0, first=r * held, shared=r == 0)
            assert _gap(part, theirs) < TOL
            parts.append(part)
    assert _gap(sum(parts), whole) < TOL
    assert _gap(parts[0], whole) > 100 * TOL


# --- bytes: the arrays, the counts ---------------------------------------------------
def test_pool_bytes_are_the_arrays(model):
    page, max_len, slots = 128, 1024, 4
    n = max_len // page
    cache = model.make_paged_cache(slots, slots * n, page, max_len)
    assert cache.latent.shape == (4, slots * n, page, 256)   # 144 -> 256
    assert cache.k is None and cache.v is None and cache.ring_k is None
    assert (cache.page_size, cache.num_pages, cache.capacity) == (
        page, slots * n, max_len)
    assert len(jax.tree_util.tree_leaves(cache)) == 3
    one = model.make_paged_cache(1, n, page, max_len)
    dep = LLMDeployment("xing_tiny", model=model, page_size=page,
                        prompt_buckets=[8])
    assert dep.pool_bytes_per_slot(model, max_len) == one.latent.nbytes
    # what the model NEEDS: one row of rank + rope a position a layer
    assert model.kv_bytes_per_slot(max_len) == 4 * max_len * (
        RANK + ROPE) * 4
    # the published widths: 576 held as 640 lanes, 12.5 KiB a position
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "xing4-29b-ep8-1chip.json").read_text())
    big = DecoderConfig(**cfg["program"]["decoder_config"])
    pool = jax.eval_shape(lambda: PagedKVCache.zeros(
        big, 40, 5760, 128, 18432))
    assert pool.latent.shape == (10, 5760, 128, 640)
    assert np.prod(pool.latent.shape) * 2 == 12800 * 5760 * 128
    m = CausalLM(big, name="big", dtype=jnp.bfloat16)
    assert m.kv_bytes_per_slot(18432) == 10 * 18432 * 1152


@pytest.mark.parametrize("option", [
    "host_spill_pages", "draft", "int8", "mesh"])
def test_what_cannot_work_with_a_latent_pool_is_refused_when_built(
        option, model, params):
    kw = dict(num_slots=2, max_len=256, prompt_buckets=[8], page_size=128)
    served = model
    if option == "draft":
        kw.update(draft_model=model, draft_params=params)
    elif option == "int8":
        served = CausalLM(TINY, name="xing_i8", dtype=jnp.float32,
                          kv_dtype=jnp.int8)
    elif option == "mesh":
        from jax.sharding import Mesh
        kw["mesh"] = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    else:
        kw.update(host_spill_pages=4, prefix_cache_size=4)
    name = {"draft": "draft_model", "int8": "kv_dtype int8"}.get(
        option, option)
    with pytest.raises(ValueError, match=f"{name} cannot be used with a "
                                         "latent pool"):
        DecodeEngine(served, params, RequestQueue(served.name, max_len=8),
                     **kw)


def test_other_refusals_name_their_reason(model):
    base = dict(vocab_size=8, d_model=64, num_layers=2, num_heads=4,
                num_kv_heads=4, mlp_dim=8, head_dim=48, rope_dim=16,
                v_head_dim=32)
    with pytest.raises(ValueError, match="needs q_lora_rank"):
        DecoderConfig(**base, kv_lora_rank=128)
    with pytest.raises(ValueError, match="attends its whole prefix"):
        DecoderConfig(**base, kv_lora_rank=128, q_lora_rank=48,
                      sliding_window=8)
    with pytest.raises(ValueError, match="rope_yarn_factor is a latent"):
        DecoderConfig(vocab_size=8, d_model=64, num_layers=2, num_heads=4,
                      num_kv_heads=4, mlp_dim=8, rope_yarn_factor=4.0)
    with pytest.raises(NotImplementedError, match="no scale plane"):
        CausalLM(TINY, name="i8", dtype=jnp.float32,
                 kv_dtype=jnp.int8).make_paged_cache(2, 4, 128, 256)
    with pytest.raises(NotImplementedError, match="no head axis"):
        model.paged_cache_pspec()
    with pytest.raises(NotImplementedError, match="slab cache has none"):
        p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: model.make_cache(2, 16))
        jax.eval_shape(model.decode_step, p, jnp.zeros((2, 1), jnp.int32),
                       cache, jnp.ones((2,), bool))


# --- the engine -------------------------------------------------------------------
def test_engine_serves_it_past_a_page_edge_and_a_slots_reuse(
        model, params, view, ref, tokens, other):
    """Through ``DecodeEngine``: ONE slot, so the second request reuses the
    first's; prompts of 250 and 270 in chunks of 8 on pages of 128 (the
    first decodes across position 256), greedy tokens against the
    reference's top-1."""
    queue = RequestQueue(model.name, max_len=64)
    engine = DecodeEngine(
        model, params, queue, num_slots=1, max_len=512,
        prompt_buckets=[8], paged=True, page_size=128, kv_pool_pages=4,
        decode_horizon=2, max_admissions_per_step=1,
        default_max_new_tokens=8, prefill_token_budget=64)
    prompts = [[int(t) for t in tokens[:250]], [int(t) for t in other[:270]]]
    for prompt in prompts:
        req = Request(model=model.name, slo_ms=60_000.0, payload={
            "tokens": prompt, "max_new_tokens": 8})
        queue.add_request(req)
        engine.run_until_idle(timeout_s=600)
        out = list(req.future.result(timeout=5).tokens)
        assert len(out) == 8
        want = np.asarray(ref.logits(view.view(params, SIZES), prompt + out,
                                     SIZES))
        for j, tok in enumerate(out):
            row = want[len(prompt) - 1 + j]
            assert row.max() - row[tok] < TOL
        assert engine._allocator.free_pages == 4  # all handed back
    scans = [t for t in engine.turns if t.kind == "turn"]
    # one busy slot at 250-278 positions: 2 or 3 live pages of 128 rows in
    # each of the 4 layers
    assert {t.kv_latent_rows for t in scans} <= {4 * 2 * 128, 4 * 3 * 128}
    assert engine.turn_summary()["kv_latent_rows"] > 0
    snap = engine.snapshot()["kv_pool"]
    rows = engine._cache.latent
    assert snap["kind"] == "latent" and snap["row_width"] == 256
    assert snap["shape"] == [4, 4, 128, 256] and snap["row_bytes"] == 1024
    assert snap["bytes_by_kind"] == {"latent": rows.nbytes}
    assert snap["resident_bytes"] == rows.nbytes
    assert snap["latent_rows_read"] == engine.turn_summary()["kv_latent_rows"]
    with pytest.raises(ValueError, match="page fabric"):
        engine.request_migration("r", lambda parcel: True)


def test_a_one_stream_kv_model_has_none_of_the_new_keys():
    plain = [Turn("turn", 0.0, 1.0, 2.0, 3.0, 8, 0, 4, 0, 0, 0, 0, False,
                  kv_pages_live=4)] * 3
    out = summarize_turns(plain, num_slots=4, table_entries=4)
    assert "kv_latent_rows" not in out
    assert plain[0].kv_latent_rows == 0
    m = CausalLM(DecoderConfig(
        vocab_size=8, d_model=64, num_layers=2, num_heads=4, num_kv_heads=2,
        mlp_dim=8), name="g", dtype=jnp.float32)
    p = m.init(jax.random.PRNGKey(0))
    assert not {"attn_hc", "mlp_hc", "q_down", "kv_up"} & set(
        p["params"]["layer0"])
    cache = jax.eval_shape(lambda: m.make_paged_cache(2, 4, 128, 256))
    assert cache.latent is None
    assert len(jax.tree_util.tree_leaves(cache)) == 4
    engine = DecodeEngine(m, p, RequestQueue("g", max_len=8), num_slots=2,
                          max_len=256, prompt_buckets=[8], page_size=128)
    pool = engine.snapshot()["kv_pool"]
    assert not {"kind", "row_width", "row_bytes", "shape",
                "latent_rows_read", "bytes_by_kind"} & set(pool)
    assert "kv_latent_rows" not in engine.snapshot()["turns"]


# --- this model's own programs, and the others', pinned ------------------------
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


def _programs(name, counters=True):
    """(decode, widest two-row chunk) program sizes of a configuration file
    at its own widths and deployment, counted as ``tests/test_mimo.py``
    counts MiMo's: top-level equations, and all of them."""
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / f"{name}.json").read_text())
    llm = cfg["deployment"]["llm"]
    m = CausalLM(DecoderConfig(**cfg["program"]["decoder_config"]),
                 name="m", dtype=jnp.bfloat16)
    B, ps = llm["num_slots"], llm["page_size"]
    W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
    cache = jax.eval_shape(lambda: m.make_paged_cache(
        B, llm["kv_pool_pages"], ps, llm["max_len"], widest_chunk=W))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    ring = ((sds((2, NP), jnp.int32),) if cache.ring_k is not None else ())
    decode = jax.make_jaxpr(m.decode_step_paged)(
        p, sds((B, 1), jnp.int32), cache, sds((B,), jnp.bool_))
    chunk = jax.make_jaxpr(
        lambda *a: m.prefill_chunk_paged(
            *a[:7], **({"ring_tables": a[7]} if ring else {})))(
        p, sds((2, W), jnp.int32), sds((2, W), jnp.int32), cache,
        sds((2, NP), jnp.int32), sds((2,), jnp.int32), sds((2,), jnp.int32),
        *ring)
    return ((len(decode.jaxpr.eqns), _equations(decode.jaxpr)),
            (len(chunk.jaxpr.eqns), _equations(chunk.jaxpr)))


# counted on the CPU (the blocked walks stand where the chip takes kernels)
# at the PARENT commit for the seven that were there: this PR's branches are
# taken by keys only Xing sets, so no other configuration's program moved.
# gpt2-medium's two were counted again in PR 48, which moves them ON PURPOSE
# (two 64-wide heads a pool row: each layer's paged write is a reshape where
# it was two pads of two equations, 24 x 4 = 96 fewer; the parent's were
# (4188, 5016) and (4216, 5051)); the other six are still the parent's.
PINNED = {
    "gpt2-medium": ((4188, 4920), (4216, 4955)),
    "gpt2-medium-x4": ((4188, 4920), (4216, 4955)),
    "mistral-7b-v0.3-1chip": ((2950, 3468), (2970, 3495)),
    "olmoe-1b-7b-1chip": ((3166, 3784), (3182, 3807)),
    "k-exaone-236b-ep8-1chip": ((1403, 1765), (1412, 1781)),
    "keye-vl2-30b-ep8-1chip": ((3150, 3836), (2874, 4527)),
    "mimo-v2-flash-ep16-1chip": ((1726, 2976), (1733, 2990)),
    # (the CPU's form: the Sinkhorn rounds unrolled, 800 equations a
    # sublayer; where Pallas is on they are one kernel's call). The chunk
    # program was counted again in PR 57, which moves it ON PURPOSE (one
    # walk for Xing and GLM-5: a block's keys made whole heads and scored
    # in ONE product, 3 equations a layer more; it was (39339, 40966)); the
    # decode program is still PR 46's.
    "xing4-29b-ep8-1chip": ((39405, 40985), (39369, 40996)),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_every_configurations_programs_trace_to_their_pinned_sizes(name):
    assert _programs(name) == PINNED[name]


@pytest.mark.parametrize("module", [
    "ray_dynamic_batching_tpu.models.decoder",
    "ray_dynamic_batching_tpu.models.causal_lm",
    "ray_dynamic_batching_tpu.engine.decode",
    "ray_dynamic_batching_tpu.serve.llm",
])
def test_importing_the_program_imports_no_latent_or_stream_module(module):
    import os
    import subprocess
    import sys

    code = (f"import sys, {module}; "
            "sys.exit(any(m in sys.modules for m in ("
            "'ray_dynamic_batching_tpu.ops.latent_attention', "
            "'ray_dynamic_batching_tpu.models.latent', "
            "'ray_dynamic_batching_tpu.models.hyper_connections')))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300).returncode == 0
