"""One rank's share of a GLM-5-shaped model on the normal path, against the
plain reference the benchmark keeps (``benchmark/reference/glm5.py``, read
through ``benchmark/views/glm5.py``; both loaded by path: they import nothing
of the program): LATENT attention (low-rank q and kv paths, ONE rotary key a
position) under a LEARNED SELECTION (an indexer whose queries come from the
q latent, a LayerNorm on its ONE key a position, rotary over the first part
of its head) served through a pool of rows with no head axis AND a plane of
index keys on the same page table; absorbed in decode; one dense layer, then
sigmoid top-k routing with a selection bias over ALL experts of which some
are held here, and a shared expert. CPU, float32, seeded weights, tiny
widths that keep the published ratios (d 64, 4 heads of 32 + 16 with values
of 32 on a latent of 128 + 16, 4 index heads of 32 of which 16 rotary, the
indexer keeping 16 of up to 96 positions, 16 experts top-4 of which 4 are
held, one shared), compared on LOGITS.
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

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.models import kv_state
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.models.kv_state import PagedKVCache
from ray_dynamic_batching_tpu.models.moe import MoEBlock, RoutingRule
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import sparse_latent_attention as sla
from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

ROOT = Path(__file__).resolve().parents[1]

E, HELD, TOP_K = 16, 4, 4
NOPE, ROPE, HV, RANK = 32, 16, 32, 128
N_INDEX, H_INDEX, TOPK = 4, 32, 16
T_ALL = 96
TINY = DecoderConfig(
    vocab_size=256, d_model=64, num_layers=3, num_heads=4, num_kv_heads=4,
    head_dim=NOPE + ROPE, v_head_dim=HV, rope_dim=ROPE, mlp_dim=32,
    max_seq_len=256, rms_eps=1e-5, rope_theta=1e6,
    kv_lora_rank=RANK, q_lora_rank=48,
    index_topk=TOPK, index_heads=N_INDEX, index_head_dim=H_INDEX,
    num_dense_layers=1, dense_mlp_dim=96, num_experts=E, moe_top_k=TOP_K,
    moe_renormalize=True, moe_scoring="sigmoid", moe_selection_bias=True,
    moe_gate_scale=2.5, moe_first_expert=HELD, moe_held_experts=HELD,
    moe_shared_experts=1,
)
SIZES = {
    "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1e6},
    "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE, "index_topk": TOPK,
    "num_experts_per_tok": TOP_K, "routed_scaling_factor": 2.5,
    "expert_parallel": {"first_expert": HELD},
    "program": {"decoder_config": {"num_layers": 3}},
}

# Program and reference both compute in float32 here, so they differ by
# summation order alone (worst gap read: 8e-6 on logits whose spread is 1);
# every wrong piece of arithmetic below moves them by hundredths or more.
# A position whose index scores at the edge of the kept set lie nearer than
# float32's rounding would flip a kept row and move logits by hundredths:
# none does at these seeds (the selected SET is compared on its own below).
TOL = 2e-4


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "glm5_" + Path(rel).stem + "_" + Path(rel).parent.name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/glm5.py")


@pytest.fixture(scope="module")
def view():
    return _load("benchmark/views/glm5.py")


def _seeded(model, view, seed=0):
    """``model.init``'s tree with every leaf the view has a rule for drawn by
    it, and biases by the common table's (std 0.02): at their initial zeros
    and ones, dropping the index key's norm would be (nearly) the same
    function."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        rule = view.seeding(names, tuple(x.shape))
        if rule is None and names[-1] == "bias":
            rule = (0.0, 0.02)
        if rule is None:
            return x
        k = jax.random.fold_in(
            key, zlib.crc32("/".join(names).encode()) % (2 ** 31))
        return rule[0] + rule[1] * jax.random.normal(k, x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def model():
    return CausalLM(TINY, name="glm5_tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model, view):
    return _seeded(model, view)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(7).integers(1, TINY.vocab_size, T_ALL)


@pytest.fixture(scope="module")
def other():
    return np.random.default_rng(8).integers(1, TINY.vocab_size, T_ALL)


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


def test_bfloat16_fails_the_tolerance(params, tokens, want):
    low = CausalLM(TINY, name="glm5_tiny_bf16", dtype=jnp.bfloat16)
    assert _gap(_full(low, params, tokens), want) > 50 * TOL


def test_a_layer_is_latent_and_selects_and_asks_in_one_place(params):
    kinds = [TINY.layer_kind(i) for i in range(3)]
    assert all(k.latent and k.select == TOPK and not k.window
               and k.pool_layer == -1 for k in kinds)
    assert [k.sparse for k in kinds] == [False, True, True]
    assert kv_state.state_kind(TINY) == "latent"
    layer = params["params"]["layer1"]
    # the index queries come from the q latent (48), not the input (64)
    assert layer["index_q"]["kernel"].shape == (48, N_INDEX, H_INDEX)
    assert layer["index_k"]["kernel"].shape == (64, H_INDEX)
    assert layer["index_w"]["kernel"].shape == (64, N_INDEX)
    assert set(layer["index_k_norm"]) == {"scale", "bias"}
    # the form is the layer's (a latent layer's indexer is DeepSeek-V3.2's),
    # not a key's: the config has no switch for it
    assert not {f.name for f in dataclasses.fields(DecoderConfig)} & {
        "index_q_latent", "index_k_norm", "index_rope_dim"}


def test_the_config_takes_latent_with_an_indexer_and_refuses_the_rest():
    """The refusal of ``index_topk`` beside ``kv_lora_rank`` is lifted;
    every other refusal of a latent layer stands, and its index head holds
    the rotary part."""
    assert TINY.latent and TINY.index_topk == TOPK
    for bad in (dict(sliding_window=8), dict(qk_norm=True),
                dict(use_bias=True)):
        with pytest.raises(ValueError, match="without a window, a q/k norm"):
            dataclasses.replace(TINY, **bad)
    with pytest.raises(ValueError, match="rotates the first rope_dim 16"):
        dataclasses.replace(TINY, index_head_dim=8)
    with pytest.raises(ValueError, match="index_topk needs index_heads"):
        dataclasses.replace(TINY, index_heads=0)


# --- chunked prefill through the pool and its index plane, then decode ---------
SLOTS = 4


def _serve(model, params, tokens, other, page, W, prompt, before=0):
    """In slot 1: first ``before`` tokens of ``other`` (a tenant whose rows
    and index keys stay behind in the slot's pages: nothing is cleared),
    then ``tokens``: ``prompt`` of them prefilled in W-wide chunks through
    the slot's page-table row, the rest decoded one token at a time in a
    batch of SLOTS slots of which slot 3 decodes ``other`` and two are
    idle. Returns the logits of every decoded position and of each chunk's
    last."""
    n_entries = TINY.max_seq_len // page
    n_pages = 2 * n_entries
    chunk = jax.jit(model.prefill_chunk_paged)
    step = jax.jit(model.decode_step_paged)
    cache = model.make_paged_cache(SLOTS, n_pages, page, TINY.max_seq_len)
    assert cache.k is None and cache.index_k is not None
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
            cache = cache.replace(latent=new.latent, index_k=new.index_k)

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


@pytest.mark.parametrize("backend, page, W, prompt, before", [
    ("xla", 16, 8, 62, 90), ("xla", 16, 16, 12, 0),
    ("pallas", 128, 16, 60, 0), ("pallas", 16, 8, 40, 50)])
def test_chunks_then_batched_decode_under_the_selection_match_the_reference(
        backend, page, W, prompt, before, model, params, tokens, other,
        want):
    """A slot whose pages held another tenant's positions (nothing
    cleared), then ``prompt`` positions through >= 3 chunks (keys and
    values EXPANDED from the pool's rows a block of pages at a time, each
    row's selection a block's mask; a prompt of 12 stays under
    ``index_topk`` 16 through its chunk and crosses it in decode) beside
    another sequence, and >= 34 single-token ABSORBED steps beside idle
    slots, every one keeping 16 of up to 96 positions: the XLA floor, or
    the mask-form kernel interpreted (a fold of 4 pages of 16 or of the
    table's 2 pages of 128); against the reference's ONE full forward,
    which caches nothing."""
    strict = backend == "pallas"
    attn_ops.set_attention_backend("pallas" if strict else "auto")
    attn_ops.clear_attention_paths()
    try:
        served = _serve(model, params, tokens, other, page, W, prompt,
                        before=before)
    finally:
        attn_ops.set_attention_backend("auto")
    assert set(range(prompt, T_ALL)) <= set(served)
    assert len([p for p in served if p < prompt]) >= 1
    assert max(_gap(row, want[pos]) for pos, row in served.items()) < TOL
    paths = attn_ops.attention_paths()
    assert {p.v_dim for p in paths} == {RANK}
    assert {p.path for p in paths} == {
        attn_ops.PATH_PAGED_KERNEL if strict else attn_ops.PATH_BLOCKED}


def test_the_strict_backend_declines_a_page_of_part_tiles_by_name():
    q = jnp.zeros((1, 1, 4, 256), jnp.float32)
    pool = jnp.zeros((1, 4, 8, 256), jnp.float32)
    select = sla.Selection(jnp.zeros((1, 1, 2, 32)), jnp.zeros((1, 1, 2)),
                           jnp.zeros((1, 4, 8, 128)), 4)
    attn_ops.set_attention_backend("pallas")
    try:
        with pytest.raises(attn_ops.AttentionDeclined,
                           match="not whole .16, 128. tiles"):
            sla.decode(q, pool, jnp.zeros((1, 4), jnp.int32),
                       jnp.asarray([20]), 0, select, rank=128, scale=0.1)
        with pytest.raises(attn_ops.AttentionDeclined, match="rows a slot"):
            sla.decode(jnp.concatenate([q, q], 1), pool,
                       jnp.zeros((1, 4), jnp.int32), jnp.asarray([20]), 0,
                       select._replace(q=jnp.zeros((1, 2, 2, 32)),
                                       w=jnp.zeros((1, 2, 2))),
                       rank=128, scale=0.1)
    finally:
        attn_ops.set_attention_backend("auto")


# --- knock-outs: each wrong arithmetic must FAIL the tolerance -----------------
def _wrong_reference(ref, wrong):
    """The reference with ONE piece of its arithmetic replaced: patched
    attributes of the loaded module (undone by the caller's monkeypatch)."""
    parts, select, rope = ref.index_parts, ref.select, ref._rope

    def queries_from_the_input(x, c_q, w, theta, r, rk=True):
        # qI from x (Keye's source) through the same kernel's first rows
        return parts(x, x[:, :c_q.shape[-1]], w, theta, r)

    def rotary_on_the_last_half(x, c_q, w, theta, r, rk=True):
        flip = lambda a: jnp.concatenate(  # noqa: E731
            [a[..., -r:], a[..., :-r]], -1)
        q_i, k_i, w_i = parts(
            x, c_q, dict(w, wq_index=flip(w["wq_index"]),
                         wk_index=flip(w["wk_index"]),
                         k_index_norm_g=flip(w["k_index_norm_g"]),
                         k_index_norm_b=flip(w["k_index_norm_b"])), theta, r)
        return q_i, k_i, w_i

    def keys_not_rotated(x, c_q, w, theta, r, rk=True):
        # (the harness's witness, ``logits(rotate_index_keys=False)``)
        return parts(x, c_q, w, theta, r, False)

    def norm_dropped(x, g, b, eps):
        return x

    def rope_halves(x, theta):
        # rotate-half (i, i + d/2) where the config says pairs (2i, 2i+1)
        d = x.shape[-1]
        order = jnp.concatenate([jnp.arange(0, d, 2), jnp.arange(1, d, 2)])
        return rope(x[..., jnp.argsort(order)], theta)

    return {
        "selection_off": ("select", lambda q, k, w, topk: select(
            q, k, w, T_ALL)),
        "topk_one_short": ("select", lambda q, k, w, topk: select(
            q, k, w, topk - 1)),
        "index_key_norm_dropped": ("_layer_norm", norm_dropped),
        "index_queries_from_the_input": ("index_parts",
                                         queries_from_the_input),
        "index_rotary_on_the_last_half": ("index_parts",
                                          rotary_on_the_last_half),
        "index_keys_not_rotated": ("index_parts", keys_not_rotated),
        "rotary_by_halves": ("_rope", rope_halves),
    }[wrong]


@pytest.mark.parametrize("wrong", [
    "selection_off", "topk_one_short", "index_key_norm_dropped",
    "index_queries_from_the_input", "index_rotary_on_the_last_half",
    "index_keys_not_rotated", "rotary_by_halves"])
def test_wrong_arithmetic_fails_the_tolerance(
        wrong, params, view, tokens, want, monkeypatch):
    """The program has no switch for any of these; the reference is read
    with the piece replaced (a fresh copy of the module: its jitted
    functions close over the patched names), and is another function."""
    fresh = _load("benchmark/reference/glm5.py")
    name, patched = _wrong_reference(fresh, wrong)
    monkeypatch.setattr(fresh, name, patched)
    got = fresh.logits(view.view(params, SIZES), tokens, SIZES)
    assert _gap(got, want) > 50 * TOL, wrong


# --- the selected SET, row by row ---------------------------------------------------
@pytest.mark.parametrize("T, start", [(1, 95), (1, 9), (8, 40), (32, 64)])
def test_the_selected_set_is_the_references_at_every_row(ref, T, start):
    """Index queries, keys and weights of small INTEGERS (so that scores tie
    exactly, in the program and in the reference alike): the positions the
    program keeps for rows ``start .. start + T`` of a slot (a decode row; a
    chunk's rows, scored a block of table columns at a time) are the
    reference's stable sort's, ties to the lower position, at every row."""
    rng = np.random.default_rng(5)
    n, Hi, ps, NP, P, topk = 2, 32, 16, 8, 12, 16
    q_i = rng.integers(-2, 3, size=(96, n, Hi)).astype(np.float32)
    k_i = rng.integers(-1, 2, size=(96, Hi)).astype(np.float32)
    w_i = rng.integers(-1, 3, size=(96, n)).astype(np.float32)
    theirs, scores = ref.select(
        jnp.asarray(q_i), jnp.asarray(k_i), jnp.asarray(w_i), topk)
    theirs = np.asarray(theirs)
    assert len(np.unique(np.asarray(scores)[95][:96])) < 60     # ties
    table = np.asarray([rng.permutation(P)[:NP]], np.int32)
    pool = np.zeros((2, P, ps, 128), np.float32)
    for pos in range(96):
        pool[1, table[0, pos // ps], pos % ps, :Hi] = k_i[pos]
    select = sla.Selection(
        jnp.asarray(q_i[None, start:start + T]),
        jnp.asarray(w_i[None, start:start + T]), jnp.asarray(pool), topk)
    for block in (sla.BLOCK_PAGES, 2):
        old, sla.BLOCK_PAGES = sla.BLOCK_PAGES, block
        try:
            mine = np.asarray(sla._chosen(
                select, 1, jnp.asarray(table), jnp.asarray([start]), T))[0]
        finally:
            sla.BLOCK_PAGES = old
        for t in range(T):
            assert np.flatnonzero(mine[t]).tolist() == np.flatnonzero(
                theirs[start + t]).tolist(), (block, t)
            assert mine[t].sum() == min(start + t + 1, topk)


# --- the kernel and its floor, side by side --------------------------------------
@pytest.mark.parametrize("lengths", [
    [0, 200, 0], [0, 639, 127], [3, 128, 255], [0, 511, 256], [1, 2, 640]])
def test_kernel_and_floor_agree_at_every_length(lengths):
    """The mask-form kernel, interpreted, against the blocked walk in XLA
    under one selection: a length at a page's last position, at its first,
    an idle slot, a slot at the table's end; slot 1 keeps NOTHING of its
    first fold (pages 0-3: a fold that adds nothing and leaves the running
    maximum where it was) wherever it has a later one."""
    from ray_dynamic_batching_tpu.ops import latent_attention as la

    rng = np.random.default_rng(0)
    L, P, ps, rank, rope, N, NP = 2, 12, 128, 128, 64, 8, 5
    Wp = la.row_width(rank, rope)
    pool = jnp.asarray(rng.normal(size=(L, P, ps, Wp)), jnp.float32)
    pool = pool.at[..., rank + rope:].set(0)
    table = jnp.asarray([[12] * 5, [5, 2, 7, 0, 3], [1, 4, 6, 8, 9]],
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, N, Wp)), jnp.float32)
    q = q.at[..., rank + rope:].set(0)
    lens = jnp.asarray(lengths, jnp.int32)
    pos = np.arange(NP * ps)
    chosen = rng.random((3, 1, NP * ps)) < 0.3
    chosen[:, 0, 0] = True                       # something is always kept
    if lengths[1] >= 4 * ps:
        chosen[1, 0, :4 * ps] = False
        chosen[1, 0, 4 * ps] = True
    chosen &= pos[None, None, :] <= np.asarray(lengths)[:, None, None]
    chosen = jnp.asarray(chosen)
    want = la.absorbed(q, pool, table, lens, 1, rank=rank, scale=0.1,
                       chosen=chosen)
    # the floor under "keep everything" is the latent walk itself
    everything = jnp.asarray(
        pos[None, None, :] <= np.asarray(lengths)[:, None, None])
    assert _gap(la.absorbed(q, pool, table, lens, 1, rank=rank, scale=0.1,
                            chosen=everything),
                la.absorbed(q, pool, table, lens, 1, rank=rank,
                            scale=0.1)) < 1e-6
    bp = min(la.FOLD_PAGES, NP)
    sel = jnp.pad(chosen[:, 0], ((0, 0), (0, -NP % bp * ps)))
    got = la._latent_paged_decode_attention(
        q[:, 0], pool, table, lens, jnp.full((1,), 1, jnp.int32),
        sel.reshape(3, -1, bp * ps).astype(jnp.int32), rank=rank, scale=0.1,
        interpret=True)
    live = jnp.asarray([1, 2])                             # slot 0 idles
    assert _gap(got[live], want[live, 0]) < 1e-5
    assert _gap(want[live], la.absorbed(
        q, pool, table, lens, 1, rank=rank, scale=0.1)[live]) > 1e-3 or (
            max(lengths[1:]) < 8)


# --- the ranks' parts of an expert layer ---------------------------------------
D_BLOCK, F_BLOCK, RANKS, E_ALL = 32, 16, 4, 32


def _block(first, held, shared):
    return MoEBlock(
        d_model=D_BLOCK, mlp_dim=F_BLOCK, num_experts=E_ALL, top_k=8,
        rule=RoutingRule("sigmoid", True, True, 2.5), first_expert=first,
        held_experts=held, shared_dim=F_BLOCK if shared else 0,
        dtype=jnp.float32)


def test_the_ranks_4_shares_add_up_to_the_uncut_layer(ref):
    """32 experts over 4 ranks of 8, top-8 of all 32, x 2.5: every rank's
    partial result against the reference given that share, and their sum
    against the reference's whole layer, the shared expert counted ONCE
    (rank 0's)."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 24, D_BLOCK)), jnp.float32)
    p = _block(0, E_ALL, True).init(jax.random.PRNGKey(5), x)["params"]
    p = dict(p, selection_bias=jnp.asarray(
        0.1 * rng.normal(size=(E_ALL,)), jnp.float32))
    w = {"w_router": p["router"]["kernel"],
         "router_bias": p["selection_bias"], "we_up": p["wi"],
         "we_gate": p["wg"], "we_down": p["wo"],
         "ws_gate": p["shared_gate"]["kernel"],
         "ws_up": p["shared_up"]["kernel"],
         "ws_down": p["shared_down"]["kernel"]}
    flat = x.reshape(-1, D_BLOCK)
    held = E_ALL // RANKS
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.experts(flat, w, top_k=8, scale=2.5, first=0)
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
                top_k=8, scale=2.5, first=r * held, shared=r == 0)
            assert _gap(part, theirs) < TOL
            parts.append(part)
    assert _gap(sum(parts), whole) < TOL
    assert _gap(parts[0], whole) > 20 * TOL


# --- bytes: the arrays, the counts ---------------------------------------------------
def test_pool_bytes_are_the_arrays_rows_and_index_keys(model):
    page, max_len, slots = 128, 256, 4
    n = max_len // page
    cache = model.make_paged_cache(slots, slots * n, page, max_len)
    assert cache.latent.shape == (3, slots * n, page, 256)   # 144 -> 256
    assert cache.index_k.shape == (3, slots * n, page, 128)  # 32 -> 128
    assert cache.k is None and cache.v is None and cache.ring_k is None
    assert [p.name for p in cache.planes()] == ["index_k", "latent"]
    assert {p.kind for p in cache.planes()} == {"latent"}
    assert {p.table for p in cache.planes()} == {"pages"}
    assert len(jax.tree_util.tree_leaves(cache)) == 4
    assert cache.bytes_by_kind() == {
        "latent": cache.latent.nbytes + cache.index_k.nbytes}
    one = model.make_paged_cache(1, n, page, max_len)
    dep = LLMDeployment("glm5_tiny", model=model, page_size=page,
                        prompt_buckets=[8])
    assert dep.pool_bytes_per_slot(model, max_len) == (
        one.latent.nbytes + one.index_k.nbytes)
    # what the model NEEDS: a row of rank + rope and an index key a position
    assert model.kv_bytes_per_slot(max_len) == 3 * max_len * (
        RANK + ROPE + H_INDEX) * 4
    # the published widths: 1,280 B of rows + 256 B of index keys held
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "glm-5-ep16-1chip.json").read_text())
    big = DecoderConfig(**cfg["program"]["decoder_config"])
    llm = cfg["deployment"]["llm"]
    pool = jax.eval_shape(lambda: PagedKVCache.zeros(
        big, llm["num_slots"], llm["kv_pool_pages"], 128, 18432))
    assert pool.latent.shape == (5, llm["kv_pool_pages"], 128, 640)
    assert pool.index_k.shape == (5, llm["kv_pool_pages"], 128, 128)
    m = CausalLM(big, name="big", dtype=jnp.bfloat16)
    assert m.kv_bytes_per_slot(18432) == 5 * 18432 * (1152 + 256)
    # the planner counts the indexer: at 4,096 positions a query attends as
    # many rows as without it (2 x 2,048) and pays the index heads on top;
    # at 16,384 it attends a quarter of them
    whole = CausalLM(dataclasses.replace(
        big, index_topk=0, index_heads=0, index_head_dim=0),
        name="b", dtype=jnp.bfloat16)
    assert m.flops_per_sample(4096) > whole.flops_per_sample(4096)
    assert m.flops_per_sample(16384) < whole.flops_per_sample(16384)


@pytest.mark.parametrize("option", [
    "host_spill_pages", "draft", "int8", "mesh"])
def test_what_the_combination_cannot_serve_is_refused_when_built(
        option, model, params):
    kw = dict(num_slots=2, max_len=256, prompt_buckets=[8], page_size=128)
    served = model
    if option == "draft":
        kw.update(draft_model=model, draft_params=params)
    elif option == "int8":
        served = CausalLM(TINY, name="glm5_i8", dtype=jnp.float32,
                          kv_dtype=jnp.int8)
    elif option == "mesh":
        from jax.sharding import Mesh
        kw["mesh"] = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    else:
        kw.update(host_spill_pages=4, prefix_cache_size=4)
    name = {"draft": "draft_model", "int8": "kv_dtype int8"}.get(
        option, option)
    with pytest.raises(ValueError, match=f"{name} cannot be used with a "
                                         "latent pool: "):
        DecodeEngine(served, params, RequestQueue(served.name, max_len=8),
                     **kw)


def test_every_entry_of_the_combinations_table_raises_by_name(model):
    table = kv_state.CANNOT["latent"]
    asks = {"host_spill_pages": dict(host_spill_pages=4),
            "draft_model": dict(draft_model=True), "mesh": dict(mesh=True),
            "kv_dtype int8": dict(kv_dtype=jnp.int8),
            "parcel": dict(parcel=True), "slab": dict(slab=True)}
    assert set(asks) == set(table)
    for what, ask in asks.items():
        words = table[what].format(name="m")[:40]
        with pytest.raises(ValueError) as err:
            kv_state.refuse_unsupported(TINY, "m", **ask)
        assert words in str(err.value), what
        if what not in ("parcel", "slab"):
            assert f"m: {what} cannot be used with a latent pool: " in str(
                err.value)
    assert "index key" in table["kv_dtype int8"]
    assert "index key" in table["parcel"]
    with pytest.raises(NotImplementedError, match="no scale plane, nor"):
        CausalLM(TINY, name="i8", dtype=jnp.float32,
                 kv_dtype=jnp.int8).make_paged_cache(2, 4, 128, 256)
    with pytest.raises(NotImplementedError, match="no head axis"):
        model.paged_cache_pspec()
    with pytest.raises(NotImplementedError, match="slab cache has none"):
        p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: model.make_cache(2, 16))
        jax.eval_shape(model.decode_step, p, jnp.zeros((2, 1), jnp.int32),
                       cache, jnp.ones((2,), bool))


# --- the engine, through the deployment document ------------------------------------
def test_the_deployment_document_serves_two_staggered_requests(
        params, view, ref, tokens, other):
    """``register_model`` + a deployment document (``apply_config`` ->
    controller -> router -> replica -> ``DecodeEngine``): two slots, the
    second request sent once the first has its first token, prompts of 70
    and 50 in chunks of 16 on pages of 128, greedy tokens against the
    reference's top-1; the ring counts rows live, rows selected and pool
    rows walked for ONE model."""
    from ray_dynamic_batching_tpu.models.base import (
        ModelSLO,
        get_model,
        register_model,
    )
    from ray_dynamic_batching_tpu.serve.controller import ServeController
    from ray_dynamic_batching_tpu.serve.schema import (
        ServeConfigSchema,
        apply_config,
    )

    name = "glm5_tiny_served"
    register_model(name, slo=ModelSLO(latency_slo_ms=60_000.0))(
        lambda **kw: CausalLM(TINY, name=name, **kw))
    assert get_model(name, dtype=jnp.float32).cfg is TINY
    doc = {"applications": [{"name": "t", "deployments": [{
        "name": "glm5", "num_replicas": 1, "max_ongoing_requests": 16,
        "llm": dict(model=name, params=params, dtype=jnp.float32,
                    num_slots=2, max_len=256, prompt_buckets=[16],
                    page_size=128, kv_pool_pages=4, decode_horizon=2,
                    max_admissions_per_step=1, default_max_new_tokens=8),
    }]}]}
    controller = ServeController()
    controller.start()
    try:
        handle = apply_config(ServeConfigSchema.from_dict(doc),
                              controller=controller)["glm5"]
        prompts = [[int(t) for t in tokens[:70]],
                   [int(t) for t in other[:50]]]
        stream, first = handle.remote_stream(
            {"tokens": prompts[0], "max_new_tokens": 8}, slo_ms=600_000.0)
        next(iter(stream))                       # the first has a token
        _, second = handle.remote_stream(
            {"tokens": prompts[1], "max_new_tokens": 8}, slo_ms=600_000.0)
        outs = [list(f.result(timeout=600).tokens) for f in (first, second)]
        engine = handle.router.replicas()[0].engine
        for prompt, out in zip(prompts, outs):
            assert len(out) == 8
            logits = np.asarray(ref.logits(
                view.view(params, SIZES), prompt + out, SIZES))
            for j, tok in enumerate(out):
                row = logits[len(prompt) - 1 + j]
                assert row.max() - row[tok] < TOL
        scans = [t for t in engine.turns.copy() if t.kind == "turn"]
        assert scans and all(
            0 < t.kv_rows_selected < t.kv_rows_live for t in scans)
        assert all(t.kv_latent_rows > 0 for t in scans)
        # 3 selecting layers, each slot keeps at most 16 of its rows
        assert max(t.kv_rows_selected for t in scans) <= 3 * 2 * TOPK
        snap = engine.snapshot()["kv_pool"]
        assert snap["kind"] == "latent" and snap["row_width"] == 256
        assert snap["index_pool"]["shape"] == [3, 4, 128, 128]
        assert snap["bytes_by_kind"] == {
            "latent": engine._cache.latent.nbytes
            + engine._cache.index_k.nbytes}
        assert snap["index_topk"] == TOPK and snap["select_layers"] == 3
        rows = snap["rows_a_substep"]
        assert 0 < rows["selected"] < rows["live"] <= rows["walked"]
        assert rows["index_scored"] == 2 * 256 * 3
        assert any("latent rows" in f for f in snap["sparse_forms"])
        assert any("expanded chunk walk" in f for f in snap["sparse_forms"])
        with pytest.raises(ValueError, match="page fabric"):
            engine.request_migration("r", lambda parcel: True)
    finally:
        controller.delete_deployment("glm5")
        controller.shutdown()


# --- what the other models are handed: nothing ----------------------------------
def test_a_latent_model_without_an_indexer_has_no_index_plane():
    xing = dataclasses.replace(
        TINY, index_topk=0, index_heads=0, index_head_dim=0)
    assert kv_state.state_kind(xing) == "latent"
    m = CausalLM(xing, name="x", dtype=jnp.float32)
    cache = jax.eval_shape(lambda: m.make_paged_cache(2, 4, 128, 256))
    assert cache.index_k is None
    assert len(jax.tree_util.tree_leaves(cache)) == 3
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    assert not {"index_q", "index_k", "index_k_norm", "index_w"} & set(
        p["params"]["layer0"])


@pytest.mark.parametrize("module", [
    "ray_dynamic_batching_tpu.models.decoder",
    "ray_dynamic_batching_tpu.models.causal_lm",
    "ray_dynamic_batching_tpu.engine.decode",
    "ray_dynamic_batching_tpu.serve.llm",
    "ray_dynamic_batching_tpu.models.latent",
    "ray_dynamic_batching_tpu.ops.latent_attention",
])
def test_importing_the_program_imports_no_sparse_latent_module(module):
    import os
    import subprocess
    import sys

    code = (f"import sys, {module}; "
            "sys.exit('ray_dynamic_batching_tpu.ops.sparse_latent_attention'"
            " in sys.modules)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300).returncode == 0


def test_the_configuration_file_holds_the_published_keys_and_its_cuts():
    """Every key of the catalog row at its published value but the four
    ``reduced``; the program's widths are the published ones."""
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "glm-5-ep16-1chip.json").read_text())
    published = {
        "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
        "head_dim": 64, "hidden_size": 6144, "index_head_dim": 128,
        "index_n_heads": 32, "index_topk": 2048,
        "indexer_rope_interleave": True, "intermediate_size": 12288,
        "kv_lora_rank": 512, "max_position_embeddings": 202752,
        "moe_intermediate_size": 2048, "moe_layer_freq": 1,
        "model_type": "glm_moe_dsa", "n_group": 1, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 64,
        "num_nextn_predict_layers": 1, "q_lora_rank": 2048,
        "qk_head_dim": 256, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_interleave": True,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 256}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size"]
    assert cfg["reduced_from"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 19360)
    d = cfg["program"]["decoder_config"]
    assert (d["d_model"], d["num_heads"], d["head_dim"], d["v_head_dim"],
            d["rope_dim"], d["kv_lora_rank"], d["q_lora_rank"]) == (
                6144, 64, 256, 256, 64, 512, 2048)
    assert (d["index_topk"], d["index_heads"], d["index_head_dim"]) == (
        2048, 32, 128)
    assert (d["num_experts"], d["moe_held_experts"], d["moe_top_k"],
            d["mlp_dim"], d["dense_mlp_dim"], d["num_dense_layers"],
            d["moe_gate_scale"], d["moe_shared_experts"]) == (
                256, 16, 8, 2048, 12288, 1, 2.5, 1)
    assert cfg["expert_parallel"] == {
        "size": 16, "rank": 0, "first_expert": 0, "held_experts": 16,
        "router_width": 256, "vocab_rows": [0, 19360]}
    assert any("num_nextn_predict_layers" in s for s in cfg["not_loaded"])
