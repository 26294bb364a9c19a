"""The two forms of a decode grid step's body
(``ops/decode_attention.py::_accumulate_tile``): all of a head block's
heads in one contraction over the tile as it arrives ("flat heads": a
block of 8 KV heads with 1 to 4 query rows a head), or one head at a
time ("per head": narrower blocks, wider windows). Both run here
interpreted on the CPU, at the head geometries of the benchmark's three
configurations with small pools, against the gather path, against each
other's arithmetic through the slab kernel, and against themselves with
a stranger head perturbed. What the CPU cannot show (that Mosaic reads
the tile flat without a relayout, and what a step costs) is
``tests/test_tpu_lowering.py``'s compile for a described chip and
``tools/run_kernel_ab.py --paged`` on a real one.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.models.decoder import paged_window_mask
from ray_dynamic_batching_tpu.models.kv_state import (
    dequantize_kv,
    pool_head_dim,
)
from ray_dynamic_batching_tpu.ops import decode_attention as da
from ray_dynamic_batching_tpu.ops import tile_math
from ray_dynamic_batching_tpu.ops.attention import _xla_attention

PS, NP, L = 128, 3, 2

# Head geometries of benchmark/configs/ (N query heads, K KV heads, head
# size; gpt2-medium's 64-wide rows are lane-padded to 128 in the pool),
# and a block narrower than 8 heads beside them.
GEOMETRIES = {
    "gpt2-medium": dict(N=16, K=16, H=64),
    "mistral-7b": dict(N=32, K=8, H=128),
    "olmoe-1b-7b": dict(N=16, K=16, H=128),
    "four-kv-heads": dict(N=8, K=4, H=128),
    # Eight query heads a KV head: at window 8 a head's 64 rows would
    # make 4 MiB of flat score tiles, past
    # ``tile_math.FLAT_SCORE_MAX_BYTES`` (the form's record only).
    "wide-gqa": dict(N=64, K=8, H=128),
}
CONFIGS = ["four-kv-heads", "gpt2-medium", "mistral-7b", "olmoe-1b-7b"]
KB8 = ["gpt2-medium", "mistral-7b", "olmoe-1b-7b"]


def lengths_for(case: str, window: int) -> int:
    """The length (positions already cached; window row t attends
    pos <= length + t) that puts the LAST row's bound where ``case``
    says."""
    last = {
        "zero": window - 1,            # length 0
        "mid_page": PS + 40,
        "page_last": 2 * PS - 1,       # the last position of page 1
        "page_first": 2 * PS,          # the first position of page 2
        "whole_table": NP * PS - 1,
    }[case]
    return last - (window - 1)


LENGTH_CASES = ["zero", "mid_page", "page_last", "page_first",
                "whole_table"]


def make_case(config, dtype, window, length, seed=0, B=2):
    g = GEOMETRIES[config]
    N, K, H = g["N"], g["K"], g["H"]
    Hp = pool_head_dim(H)
    rng = np.random.default_rng(seed)
    P = B * NP + 1
    q = jnp.asarray(rng.standard_normal((B, window, N, H)), jnp.bfloat16)
    shape = (L, P, PS, K, Hp)
    ks = vs = None
    if dtype == jnp.int8:
        k = rng.integers(-127, 127, shape).astype(np.int8)
        v = rng.integers(-127, 127, shape).astype(np.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (L, P, PS, K)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (L, P, PS, K)), jnp.float32)
    else:
        k = rng.standard_normal(shape).astype(np.float32)
        v = rng.standard_normal(shape).astype(np.float32)
    k[..., H:] = 0  # the pool's lane padding holds zeros
    v[..., H:] = 0
    # Every slot's pages are its own, in a shuffled physical order; the
    # second slot is one position shorter where it can be.
    table = rng.permutation(B * NP).reshape(B, NP).astype(np.int32)
    lens = np.maximum(length - np.arange(B), 0).astype(np.int32)
    return (q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), ks, vs,
            jnp.asarray(table), jnp.asarray(lens))


def gathered(pool, table, layer):
    """[L, P, PS, ...] -> the slots' logical rows [B, NP * PS, ...]."""
    B = table.shape[0]
    return pool[layer][table].reshape((B, NP * PS) + pool.shape[3:])


def run_paged(q, k, v, ks, vs, table, lens, layer=L - 1):
    out = da.paged_decode_attention(
        q, k, v, table, lens, layer=layer, interpret=True,
        k_scale=None if ks is None else ks[layer],
        v_scale=None if vs is None else vs[layer])
    assert out is not None, "paged kernel declined"
    return out


def expected_form(config, window, dtype=jnp.bfloat16):
    g = GEOMETRIES[config]
    kb = da._pick_heads_block(g["K"])
    rows = window * g["N"] // g["K"]
    # a block of 8 by the score tiles' size; a narrower one that is all of
    # K folds flat too, but for an int8 pool (the fold reads no scales)
    return (da.FORM_FLAT
            if tile_math.flat_heads(kb, rows, PS) or da._narrow_fold(
                g["K"], kb, rows, PS, dtype == jnp.int8) > 1
            else da.FORM_PER_HEAD)


class TestKernelAgainstTheGatherPath:
    """Every live position scored, none past the staircase bound: the
    kernel against XLA's softmax over the gathered rows, within bf16's
    tolerance, at every edge of a page."""

    @pytest.mark.parametrize("case", LENGTH_CASES)
    @pytest.mark.parametrize("window", [1, 5])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("config", CONFIGS)
    def test_kernel_matches_gather(self, config, dtype, window, case):
        args = make_case(config, dtype, window, lengths_for(case, window))
        q, k, v, ks, vs, table, lens = args
        H = q.shape[-1]
        da.clear_decode_paths()
        out = np.asarray(run_paged(*args).astype(jnp.float32))
        assert da.decode_paths()[-1].form == expected_form(
            config, window, dtype)
        kg = gathered(k, table, L - 1)[..., :H]
        vg = gathered(v, table, L - 1)[..., :H]
        if ks is not None:
            kg = dequantize_kv(kg, gathered(ks, table, L - 1), jnp.float32)
            vg = dequantize_kv(vg, gathered(vs, table, L - 1), jnp.float32)
        ref = np.asarray(_xla_attention(
            q.astype(jnp.float32), kg.astype(jnp.float32),
            vg.astype(jnp.float32), causal=False,
            mask=paged_window_mask(lens, NP * PS, window), scale=None))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(
            out, ref, atol=3e-2 * max(1.0, np.abs(ref).max()), rtol=3e-2)


class TestNoHeadReadsAStranger:
    """The flat form scores every row against every head's keys and
    masks the strangers: perturbing ONE head's K and V everywhere in the
    pool must leave every other head's output bit-equal, and move its
    own."""

    @pytest.mark.parametrize("window", [1, 5])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("config", CONFIGS)
    def test_perturbed_head_moves_only_its_own_rows(
            self, config, dtype, window):
        q, k, v, ks, vs, table, lens = make_case(
            config, dtype, window, lengths_for("mid_page", window))
        g = GEOMETRIES[config]
        G, hp = g["N"] // g["K"], g["K"] - 3   # a head inside a block
        base = np.asarray(
            run_paged(q, k, v, ks, vs, table, lens).astype(jnp.float32))
        flip = lambda pool: pool.at[:, :, :, hp, :g["H"]].set(
            (-pool[:, :, :, hp, :g["H"]].astype(jnp.float32) * 0.5 + 3
             ).astype(pool.dtype))
        moved = np.asarray(run_paged(
            q, flip(k), flip(v), ks, vs, table, lens).astype(jnp.float32))
        own = np.zeros(g["N"], bool)
        own[hp * G:(hp + 1) * G] = True
        np.testing.assert_array_equal(moved[:, :, ~own], base[:, :, ~own])
        assert np.abs(moved[:, :, own] - base[:, :, own]).max() > 1e-3


class TestSlabAndPagedAreOneBody:
    """``_accumulate_tile`` is one function: the slab kernel over the
    gathered rows, a page a tile, equals the paged kernel bit for bit in
    either form (mask-derived validity there, length-derived here)."""

    @pytest.mark.parametrize("case", ["page_last", "whole_table"])
    @pytest.mark.parametrize("window", [1, 5])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("config", CONFIGS)
    def test_slab_equals_paged(self, config, dtype, window, case):
        args = make_case(config, dtype, window, lengths_for(case, window))
        q, k, v, ks, vs, table, lens = args
        paged = run_paged(*args)
        Hp = k.shape[-1]
        q_wide = jnp.pad(q, ((0, 0),) * 3 + ((0, Hp - q.shape[-1]),))
        slab = da.decode_attention(
            q_wide, gathered(k, table, L - 1), gathered(v, table, L - 1),
            mask=paged_window_mask(lens, NP * PS, window), block_k=PS,
            scale=q.shape[-1] ** -0.5, interpret=True,
            k_scale=None if ks is None else gathered(ks, table, L - 1),
            v_scale=None if vs is None else gathered(vs, table, L - 1))
        assert slab is not None
        paged = np.asarray(paged.astype(jnp.float32))
        slab = np.asarray(slab[..., :q.shape[-1]].astype(jnp.float32))
        if config == "four-kv-heads" and dtype == jnp.bfloat16:
            # not one body: the paged kernel folds a narrow block that is
            # all of K in one contraction (``_fold_flat``), the slab kernel
            # a head at a time; f32 summation order, then bf16's rounding
            np.testing.assert_allclose(paged, slab, rtol=1e-2, atol=1e-3)
        else:
            np.testing.assert_array_equal(paged, slab)


class TestFormsAgree:
    """Where the shape allows both forms, they give the same numbers:
    the flat form against the per-head form on the same pool (the rule
    turned off for the comparison), within f32 summation order."""

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("config", KB8)
    def test_flat_equals_per_head(self, config, dtype, monkeypatch):
        args = make_case(config, dtype, 1, lengths_for("mid_page", 1))
        flat = np.asarray(run_paged(*args).astype(jnp.float32))
        assert da.decode_paths()[-1].form == da.FORM_FLAT
        monkeypatch.setattr(tile_math, "FLAT_SCORE_MAX_BYTES", 0)
        da._paged_decode_attention.clear_cache()
        try:
            per_head = np.asarray(run_paged(*args).astype(jnp.float32))
            assert da.decode_paths()[-1].form == da.FORM_PER_HEAD
        finally:
            da._paged_decode_attention.clear_cache()
        np.testing.assert_allclose(
            flat, per_head, rtol=1e-2,
            atol=1e-2 * max(1.0, np.abs(per_head).max()))


    @pytest.mark.parametrize("window", [1, 5])
    def test_a_narrow_blocks_fold_equals_per_head(self, window, monkeypatch):
        """Four KV heads that are all of K: the one contraction over the
        page's tile view against the per-head form on the same pool (the
        rule turned off), and an int8 pool keeps the per-head form."""
        args = make_case("four-kv-heads", jnp.bfloat16, window,
                         lengths_for("mid_page", window))
        flat = np.asarray(run_paged(*args).astype(jnp.float32))
        assert da.decode_paths()[-1].form == da.FORM_FLAT
        monkeypatch.setattr(da, "_narrow_fold", lambda *a: 1)
        da._paged_decode_attention.clear_cache()
        try:
            per_head = np.asarray(run_paged(*args).astype(jnp.float32))
            assert da.decode_paths()[-1].form == da.FORM_PER_HEAD
        finally:
            da._paged_decode_attention.clear_cache()
        np.testing.assert_allclose(
            flat, per_head, rtol=1e-2,
            atol=1e-2 * max(1.0, np.abs(per_head).max()))
        monkeypatch.undo()
        run_paged(*make_case("four-kv-heads", jnp.int8, window,
                             lengths_for("mid_page", window)))
        assert da.decode_paths()[-1].form == da.FORM_PER_HEAD


class TestDecodePaths:
    """Which body a program took is on record (``decode_paths()``), as
    ``ops/moe.py::moe_paths`` records the expert path."""

    @pytest.mark.parametrize("config,window,form", [
        ("gpt2-medium", 1, da.FORM_FLAT),
        ("mistral-7b", 1, da.FORM_FLAT),
        ("olmoe-1b-7b", 1, da.FORM_FLAT),
        ("gpt2-medium", 5, da.FORM_FLAT),
        ("mistral-7b", 8, da.FORM_FLAT),      # 8 x G 4: 2 MiB of tiles
        ("four-kv-heads", 1, da.FORM_FLAT),   # all of K in one fold
        ("four-kv-heads", 5, da.FORM_FLAT),
        ("wide-gqa", 1, da.FORM_FLAT),
        ("wide-gqa", 8, da.FORM_PER_HEAD),    # 8 x G 8: 4 MiB
    ])
    def test_each_configuration_reports_its_form(
            self, config, window, form):
        args = make_case(config, jnp.bfloat16, window,
                         lengths_for("mid_page", window))
        da.clear_decode_paths()
        run_paged(*args)
        (path,) = da.decode_paths()
        g = GEOMETRIES[config]
        assert path.form == form
        assert path.kb == da._pick_heads_block(g["K"])
        assert path.rows == window * g["N"] // g["K"]
        assert (path.page_size, path.head_dim) == (
            PS, pool_head_dim(g["H"]))
        assert path.form in path.describe() and path.why

    def test_a_declined_call_leaves_no_record(self):
        q, k, v, ks, vs, table, lens = make_case(
            "mistral-7b", jnp.bfloat16, 1, 10)
        da.clear_decode_paths()
        assert da.paged_decode_attention(
            q, k[:, :, :100], v[:, :, :100], table, lens,
            interpret=True) is None
        assert da.decode_paths() == []

    def test_tp_shards_record_their_own_block(self):
        # 16 KV heads over tp=2: each shard's block is its 8 heads.
        assert tile_math.flat_heads(
            da._pick_heads_block(tile_math.shard_heads(16, 2)), 1, PS)
        # 8 KV heads over tp=2: a 4-head block, per head.
        assert not tile_math.flat_heads(
            da._pick_heads_block(tile_math.shard_heads(8, 2)), 1, PS)


class TestVmemModelFollowsTheBody:
    """``paged_tile_bytes`` counts the flat form's f32 score and
    probability tiles, so a geometry that no longer fits declines by the
    budget, with its reason."""

    def test_flat_form_adds_its_two_score_tiles(self):
        # 8 rows x 1,024 columns of f32, twice.
        assert tile_math.flat_score_bytes(PS, 8, 1) == 2 * 8 * 1024 * 4
        assert tile_math.flat_score_bytes(PS, 8, 4) == 2 * 32 * 1024 * 4
        streamed = tile_math.DOUBLE_BUFFER * 2 * tile_math.padded_block_bytes(
            (1, PS, 8, 128), 2)
        assert tile_math.paged_tile_bytes(PS, 8, 128, 2) \
            == streamed + tile_math.flat_score_bytes(PS, 8, 1)

    @pytest.mark.parametrize("kb,rows,sb", [
        (4, 1, PS), (12, 1, PS), (8, 33, PS), (8, 64, PS), (8, 4, 16 * PS)])
    def test_per_head_form_adds_nothing(self, kb, rows, sb):
        assert not tile_math.flat_heads(kb, rows, sb)
        assert tile_math.flat_score_bytes(sb, kb, rows) == 0

    @pytest.mark.parametrize("rows", [1, 2, 4, 5, 8, 16, 32])
    def test_rule_is_the_shape_alone(self, rows):
        """Every row count measured on the chip (PR 31: 1 to 32 rows a
        head over a 128-position page of 8 heads) takes the flat form."""
        assert tile_math.flat_heads(8, rows, PS)
        assert tile_math.flat_score_bytes(PS, 8, rows) \
            <= tile_math.FLAT_SCORE_MAX_BYTES

    def test_the_widest_measured_window_is_the_cap(self):
        # ISSUE 31: window 8 x G 4 scored flat is 256 x 1,024 f32, 1 MB
        # a tile, two tiles: the last shape measured, the first refused
        # is one row more.
        assert tile_math.padded_block_bytes((8 * 32, PS * 8), 4) == 1 << 20
        assert tile_math.flat_score_bytes(PS, 8, 32) \
            == tile_math.FLAT_SCORE_MAX_BYTES

    @pytest.mark.parametrize("ps,window,G", [
        (2048, 1, 4), (2048, 8, 4), (896, 1, 4)])
    def test_decline_at_a_fat_page_names_the_budget(self, ps, window, G):
        """A page too fat for VMEM still declines by the budget with its
        reason in ``why``: at the narrowest and the widest window by its
        streamed blocks, and (896 positions) by its flat score tiles
        on top of streamed blocks that fit."""
        K, H = 8, 128
        assert tile_math.paged_tile_bytes(
            ps, 8, H, 2, window=window, G=G
        ) > tile_math.VMEM_BLOCK_BUDGET_BYTES
        q = jnp.zeros((1, window, K * G, H), jnp.bfloat16)
        pool = jnp.zeros((1, 2, ps, K, H), jnp.bfloat16)
        why = []
        assert da.paged_decode_attention(
            q, pool, pool, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1,), jnp.int32), interpret=True, why=why) is None
        assert len(why) == 1 and "VMEM block budget" in why[0]
        assert f"ps={ps}" in why[0] and "kb=8" in why[0]

    def test_flat_tiles_tip_a_page_over_the_budget(self):
        """A page whose streamed blocks alone fit, and whose flat score
        tiles do not: the model, not the streamed bytes, declines it."""
        ps, kb, H = 896, 8, 128
        streamed = tile_math.DOUBLE_BUFFER * 2 * tile_math.padded_block_bytes(
            (1, ps, kb, H), 2)
        assert streamed <= tile_math.VMEM_BLOCK_BUDGET_BYTES
        assert tile_math.paged_tile_bytes(ps, kb, H, 2, G=4) \
            > tile_math.VMEM_BLOCK_BUDGET_BYTES
        # ... while a 12-head block of the same page, scored per head,
        # is held to its streamed bytes alone.
        assert tile_math.flat_score_bytes(ps, 12, 4) == 0


# --- the page walk: a loop over a slot's live pages (ISSUE 33) ----------------

WALK_NP = 5     # table entries a slot in the walk's cases


def walk_case(config, dtype, window, lens, seed=0):
    """``make_case`` with a length a slot and a ``WALK_NP``-entry table:
    every slot's pages its own, in a shuffled physical order, and one
    spare page that no table names."""
    g = GEOMETRIES[config]
    N, K, H = g["N"], g["K"], g["H"]
    B = len(lens)
    rng = np.random.default_rng(seed)
    P = B * WALK_NP + 1
    q = jnp.asarray(rng.standard_normal((B, window, N, H)), jnp.bfloat16)
    shape = (L, P, PS, K, pool_head_dim(H))
    ks = vs = None
    if dtype == jnp.int8:
        k = rng.integers(-127, 127, shape).astype(np.int8)
        v = rng.integers(-127, 127, shape).astype(np.int8)
        ks = rng.uniform(0.01, 0.1, (L, P, PS, K)).astype(np.float32)
        vs = rng.uniform(0.01, 0.1, (L, P, PS, K)).astype(np.float32)
    else:
        k = rng.standard_normal(shape).astype(np.float32)
        v = rng.standard_normal(shape).astype(np.float32)
    k[..., H:] = 0
    v[..., H:] = 0
    table = rng.permutation(B * WALK_NP).reshape(B, WALK_NP).astype(np.int32)
    return q, k, v, ks, vs, table, np.asarray(lens, np.int32)


def run_walk(q, k, v, ks, vs, table, lens, dtype, sliding=0, layer=L - 1):
    out = da.paged_decode_attention(
        q, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
        jnp.asarray(table), jnp.asarray(lens), layer=layer, interpret=True,
        sliding=sliding,
        k_scale=None if ks is None else jnp.asarray(ks[layer]),
        v_scale=None if vs is None else jnp.asarray(vs[layer]))
    assert out is not None, "paged kernel declined"
    return np.asarray(out.astype(jnp.float32))


def walk_reference(q, k, v, ks, vs, table, lens, window, sliding):
    """XLA's softmax over the gathered rows under the model's own mask."""
    H = q.shape[-1]
    B = table.shape[0]
    safe = np.minimum(table, k.shape[1] - 1)    # the sentinel clamps
    rows = lambda pool: jnp.asarray(pool[L - 1][safe].reshape(
        (B, WALK_NP * PS) + pool.shape[3:]), jnp.float32)
    kg, vg = rows(k)[..., :H], rows(v)[..., :H]
    if ks is not None:
        kg = dequantize_kv(kg, rows(ks), jnp.float32)
        vg = dequantize_kv(vg, rows(vs), jnp.float32)
    return np.asarray(_xla_attention(
        q.astype(jnp.float32), kg, vg, causal=False, scale=None,
        mask=paged_window_mask(jnp.asarray(lens), WALK_NP * PS, window,
                               sliding)))


# Lengths at every edge of a page and of the table; a window of 128 lies
# astride two pages, of 200 astride two or three, of 300 three or four.
WALK_LENGTHS = [0, 1, PS - 1, PS, PS + 40, 2 * PS - 1, 2 * PS, 3 * PS + 7,
                WALK_NP * PS - 5, WALK_NP * PS - 1]


class TestWalkBounds:
    """``tile_math.live_pages`` is the ONE rule of the kernel's loop, the
    engine's ``kv_pages_live`` and the window's two older functions: its
    columns are exactly the pages that hold a position some window row
    attends under the model's own mask."""

    @pytest.mark.parametrize("sliding", [0, 128, 200, 300])
    @pytest.mark.parametrize("rows", [1, 5])
    @pytest.mark.parametrize("length", WALK_LENGTHS)
    def test_bounds_are_the_pages_the_mask_attends(
            self, length, rows, sliding):
        length = min(length, WALK_NP * PS - rows)   # the rows fit
        first, count = tile_math.live_pages(
            length, rows, sliding, PS, WALK_NP)
        mask = np.asarray(paged_window_mask(
            jnp.asarray([length]), WALK_NP * PS, rows, sliding))
        pages = np.flatnonzero(
            mask.reshape(rows, WALK_NP, PS).any(axis=(0, 2)))
        assert (first, count) == (pages[0], len(pages))
        assert (pages == np.arange(first, first + count)).all()
        assert 1 <= count <= tile_math.window_table_width(
            sliding, rows, PS, WALK_NP)
        if sliding:
            assert first == tile_math.window_first_page(length, sliding, PS)

    @pytest.mark.parametrize("windows", [(0,), (128, 128, 0), (200, 0)],
                             ids=["full", "LLG", "LG200"])
    @pytest.mark.parametrize("rows", [1, 5])
    def test_the_engines_counter_is_the_same_rule(self, rows, windows):
        from types import SimpleNamespace

        from ray_dynamic_batching_tpu.engine.decode import DecodeEngine

        lens = np.minimum(np.asarray(WALK_LENGTHS), WALK_NP * PS - rows)
        engine = SimpleNamespace(
            _len_host=lens, page_size=PS, _n_table_entries=WALK_NP,
            _layer_windows=windows)
        want = np.mean([
            sum(tile_math.live_pages(int(n), rows, w, PS, WALK_NP)[1]
                for n in lens) for w in windows])
        assert DecodeEngine._kv_pages_live(engine, rows) == want

    def test_a_length_past_the_table_still_walks_inside_it(self):
        """A stale length (a freed slot's) past the capacity: the walk
        ends at the table's last column and starts no later."""
        for sliding in (0, 128):
            first, count = tile_math.live_pages(
                10 * PS, 1, sliding, PS, WALK_NP)
            assert first + count == WALK_NP and count >= 1


WALK_CONFIGS = ["four-kv-heads", "gpt2-medium", "mistral-7b"]


class TestWalkReadsOnlyItsLivePages:
    """The loop visits the table columns ``live_pages`` names and no
    other: the sentinel in every other entry, NaN in every page those
    columns do not name, leave every output bit as it was; and the
    result is the gather path's, at odd and even live counts (the ring
    slot's parity), a one-page slot, and from one step into the next."""

    @pytest.mark.parametrize("sliding", [0, 128, 200])
    @pytest.mark.parametrize("window", [1, 5])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("config", WALK_CONFIGS)
    def test_poison_outside_the_bounds_changes_no_bit(
            self, config, dtype, window, sliding):
        # live counts 1, 2, 3, 4, 5 in a full layer: both parities of a
        # ring of two, and every step hands the ring to the next
        lens = [40, 2 * PS - window, 2 * PS + 9, 4 * PS - window,
                WALK_NP * PS - window]
        q, k, v, ks, vs, table, lens = walk_case(config, dtype, window, lens)
        base = run_walk(q, k, v, ks, vs, table, lens, dtype, sliding)
        ref = walk_reference(q, k, v, ks, vs, table, lens, window, sliding)
        assert np.isfinite(base).all()
        np.testing.assert_allclose(
            base, ref, atol=3e-2 * max(1.0, np.abs(ref).max()), rtol=3e-2)
        P = k.shape[1]
        live = np.zeros(P, bool)
        dead_table = np.full_like(table, P)            # the sentinel
        for b, n in enumerate(lens):
            first, count = tile_math.live_pages(
                int(n), window, sliding, PS, WALK_NP)
            cols = slice(first, first + count)
            dead_table[b, cols] = table[b, cols]
            live[table[b, cols]] = True
        nan = lambda x: np.where(
            live.reshape((1, P) + (1,) * (x.ndim - 2)), x, np.nan)
        if dtype == jnp.int8:   # codes cannot hold one: the scales do
            poisoned = (k, v, nan(ks), nan(vs))
        else:
            poisoned = (nan(k), nan(v), ks, vs)
        for tab in (dead_table, table):
            got = run_walk(q, *poisoned, tab, lens, dtype, sliding)
            np.testing.assert_array_equal(got, base)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("config", WALK_CONFIGS)
    def test_empty_and_idle_slots_write_finite_rows(self, config, dtype):
        """A slot of length 0 attends position 0 alone; an idle slot's
        table holds the sentinel everywhere and its column 0 clamps to a
        real page: both rows finite, the live slot between them right."""
        q, k, v, ks, vs, table, lens = walk_case(
            config, dtype, 1, [0, 3 * PS + 1, 0, 0])
        table[2] = k.shape[1]                           # idle: no pages
        table[3] = k.shape[1]
        lens[3] = 2 * PS + 5                            # ...a stale length
        out = run_walk(q, k, v, ks, vs, table, lens, dtype)
        assert np.isfinite(out).all()
        ref = walk_reference(q, k, v, ks, vs, table, lens, 1, 0)
        np.testing.assert_allclose(
            out[:2], ref[:2], atol=3e-2 * max(1.0, np.abs(ref).max()),
            rtol=3e-2)

    def test_the_record_names_the_walk_and_its_depth(self):
        q, k, v, ks, vs, table, lens = walk_case(
            "gpt2-medium", jnp.bfloat16, 1, [40, 300])
        da.clear_decode_paths()
        run_walk(q, k, v, ks, vs, table, lens, jnp.bfloat16, sliding=128)
        (path,) = da.decode_paths()
        assert path.walk == da.WALK_LOOP
        assert path.depth == tile_math.paged_walk_depth(PS, 8, 128, 2)
        assert path.depth >= tile_math.DOUBLE_BUFFER
        assert (path.sliding, path.table_width) == (128, 2)
        assert path.walk in path.describe()
        assert f"a ring of {path.depth}" in path.describe()
