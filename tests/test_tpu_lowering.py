"""TPU lowering legality for the Pallas kernels — runnable on CPU.

Interpret-mode parity tests (test_decode_attention, test_flash_attention)
prove the MATH but skip Mosaic's block-mapping checks entirely: the first
real-TPU bench attempt of round 5 died on a block spec whose trailing
dims weren't (8, 128)-tile-aligned — a failure class invisible to every
CPU test in the suite until now. ``jax.export`` cross-platform lowering
(platforms=['tpu']) runs Pallas's lowering to Mosaic MLIR without a
chip, so that error is reproducible — and pinned — on the CPU lane. What
it does NOT run is the Mosaic compiler itself (vector layouts, VMEM
allocation), which lives in libtpu on the machine with the chip:
``chip_smoke.py``'s kernels phase is the check for that.

Geometries pinned below are the ones the serving path actually emits:
the bench LLM row (gpt2_medium MHA, 64 slots), llama-family GQA, the
speculative-verify window staircase, and the flash prefill buckets.
"""

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow  # full Mosaic lowering per case

from jax import export

from ray_dynamic_batching_tpu.ops import decode_attention as da
from ray_dynamic_batching_tpu.ops import flash_attention as fa


def _lower_decode(B, Tq, N, H, S, K, dtype=jnp.bfloat16, with_mask=True,
                  require_engaged=True):
    q = jnp.zeros((B, Tq, N, H), dtype)
    k = jnp.zeros((B, S, K, H), dtype)
    v = jnp.zeros((B, S, K, H), dtype)
    mask = jnp.ones((B, 1, Tq, S), bool) if with_mask else None

    def f(q, k, v, mask):
        out = da.decode_attention(q, k, v, mask=mask, interpret=False)
        if require_engaged:
            assert out is not None, \
                "kernel declined an expected-eligible shape"
        return q if out is None else out  # decline-to-XLA is legal

    export.export(jax.jit(f), platforms=["tpu"])(q, k, v, mask)


def _lower_flash(B, Tq, N, H, Tk, K, dtype=jnp.bfloat16, causal=True,
                 with_mask=False, require_engaged=True):
    q = jnp.zeros((B, Tq, N, H), dtype)
    k = jnp.zeros((B, Tk, K, H), dtype)
    v = jnp.zeros((B, Tk, K, H), dtype)
    mask = jnp.ones((B, 1, Tq, Tk), bool) if with_mask else None

    def f(q, k, v, mask):
        out = fa.flash_attention(
            q, k, v, causal=causal, mask=mask, interpret=False
        )
        if require_engaged:
            assert out is not None, \
                "kernel declined an expected-eligible shape"
        return q if out is None else out  # decline-to-XLA is legal

    export.export(jax.jit(f), platforms=["tpu"])(q, k, v, mask)


class TestDecodeKernelLowersForTPU:
    def test_bench_llm_row_geometry(self):
        # gpt2_medium: 16 MHA heads x 64 dim, 64 slots — the exact row
        # whose first on-chip attempt failed to lower (round 5).
        _lower_decode(64, 1, 16, 64, 256, 16)

    def test_tiny_capacity_tail(self):
        # S=8: the smallest capacity bucket the engine warms up with —
        # the literal failing shape of that first on-chip attempt.
        _lower_decode(1, 1, 16, 64, 8, 16, dtype=jnp.float32,
                      with_mask=False)

    def test_llama_tiny_gqa(self):
        _lower_decode(8, 1, 8, 64, 128, 4)

    def test_spec_verify_window(self):
        # speculative verify: Tq = k+1 staircase windows ride the same
        # kernel with a per-row mask.
        _lower_decode(8, 5, 16, 64, 512, 8)

    def test_mha_single_kv_head_group(self):
        # K not a multiple of 8: the head block must span K exactly.
        _lower_decode(4, 1, 12, 64, 64, 12, dtype=jnp.float32)

    def test_8b_large_capacity_tiles_and_lowers(self):
        # llama-3-8B geometry at a 8k KV capacity: the S grid axis tiles
        # the scan so the kernel's motivating workload (GQA without the
        # jnp.repeat materialization) lowers instead of declining.
        _lower_decode(8, 1, 32, 128, 8192, 8)

    def test_sb_picker_divides_and_fits(self):
        for S in (8, 70, 256, 1024, 2048, 8192):
            for kb, H in ((8, 64), (8, 128), (16, 64), (4, 64)):
                sb = da._pick_sb(S, kb, H, 2, True)
                assert sb > 0 and S % sb == 0
                assert sb == S or sb % 128 == 0  # mask-tile-legal
                # big geometries must tile below whole-S (VMEM-bound)
                if 2 * 2 * S * kb * H * 2 > da.VMEM_BLOCK_BUDGET_BYTES:
                    assert sb < S

    def test_sb_picker_pads_lane_dim_h64(self):
        # VMEM budget must count the PADDED footprint on the lane dim too:
        # Mosaic tiles VMEM in 128-lane units, so an H=64 K/V block
        # occupies 128 lanes — budgeting raw H undercounts ~2x. The ADVICE
        # geometry: bf16, S=1024, kb=16 (K=16), H=64 — the raw-H budget
        # picked the whole-S tile (~8.4 MB budgeted, ~16.8 MB real,
        # double-buffered); lane padding must reject it.
        S, kb, H, itemsize = 1024, 16, 64, 2
        sb = da._pick_sb(S, kb, H, itemsize, with_mask=True)
        assert 0 < sb < S and S % sb == 0 and sb % 128 == 0
        # Pin the padded math itself: the true double-buffered K/V block
        # footprint at the chosen sb, with H padded to 128 lanes, must fit
        # the budget — and the whole-S tile must not.
        def padded_kv_bytes(tile):
            lane_h = -(-H // 128) * 128   # 64 -> 128
            return 2 * (2 * tile * kb * lane_h * itemsize)
        assert padded_kv_bytes(sb) <= da.VMEM_BLOCK_BUDGET_BYTES
        assert padded_kv_bytes(S) > da.VMEM_BLOCK_BUDGET_BYTES
        # H=128 geometries were budgeted correctly before (lane-aligned):
        # padding must not change their pick.
        assert da._pick_sb(S, kb, 128, itemsize, True) == sb

    def test_sb_picker_honors_test_cap(self):
        # target caps the tile when a legal tile under it exists...
        assert da._pick_sb(256, 4, 64, 2, True, target=128) == 128
        # ...and is ignored when it doesn't (70 has no 128-multiple
        # divisor, so the whole-S tile is the only legal choice).
        assert da._pick_sb(70, 4, 64, 2, True, target=32) == 70

    def test_heads_block_legality(self):
        for K in (1, 2, 4, 8, 12, 16, 24, 32):
            kb = da._pick_heads_block(K)
            assert K % kb == 0
            assert kb == K or kb % 8 == 0

    def test_int8_cache_codes_and_scales(self):
        # int8 KV cache: codes + scales transposed to [B, K, S] with
        # (1, kb, sb) blocks must lower — trailing dims (kb, sb) are
        # tile-legal (kb pads to 8 sublanes, sb is a 128-lane multiple),
        # where the naive [B, S, K] layout's (sb, kb) trailing dims are
        # ILLEGAL for kb < K. gpt2_medium (kb=8 < K=16) and llama GQA
        # (kb == K) both covered.
        for (B, N, H, S, K) in ((8, 16, 64, 256, 16), (4, 32, 128, 512, 8)):
            q = jnp.zeros((B, 1, N, H), jnp.bfloat16)
            k = jnp.zeros((B, S, K, H), jnp.int8)
            ksc = jnp.zeros((B, S, K), jnp.float32)
            mask = jnp.ones((B, 1, 1, S), bool)

            def f(q, k, ksc, mask):
                out = da.decode_attention(
                    q, k, k, mask=mask, k_scale=ksc, v_scale=ksc,
                    interpret=False,
                )
                assert out is not None, "int8 path declined"
                return out

            export.export(jax.jit(f), platforms=["tpu"])(q, k, ksc, mask)

    def test_whisper_decoder_geometry(self):
        # whisper_large_v3: 20 MHA heads (not a multiple of 8 — the head
        # block must span), 448-token decode capacity.
        _lower_decode(8, 1, 20, 64, 448, 20)

    def test_odd_capacity_whole_tile(self):
        # A capacity with no 128-multiple divisor rides one whole-S tile.
        _lower_decode(4, 1, 8, 64, 257, 4)


class TestPagedKernelLowersForTPU:
    """The paged kernel over the STACKED pool left in HBM — the layer a
    prefetched scalar, each live page's ``[1, ps, kb, Hp]`` K and V tiles
    (a strided copy of an 8-head block where the pool holds 16) copied by
    the kernel into its VMEM ring inside a loop bounded by the slot's
    length — at the benchmark's three configurations
    (``benchmark/configs/``): gpt2-medium (24 layers, 128 pages x 128,
    MHA 16x64 in lane-padded 128-wide pool rows, 16 slots x 8 table
    entries), Mistral 7B cut to 16 layers (160 pages x 128, GQA 32/8 x
    128, 8 slots x 32 entries) and OLMoE cut to 12 layers (256 pages x
    128, MHA 16x128, 32 slots x 8 entries)."""

    GEOMETRIES = {
        "gpt2-medium": dict(L=24, P=128, B=16, NP=8, N=16, K=16, H=64),
        "mistral-7b": dict(L=16, P=160, B=8, NP=32, N=32, K=8, H=128),
        "olmoe-1b-7b": dict(L=12, P=256, B=32, NP=8, N=16, K=16, H=128),
    }

    @staticmethod
    def _call(g, window, dtype, struct=jnp.zeros, sliding=0):
        """The jitted call and its arguments (``struct(shape, dtype)``
        makes each: arrays for an export, shapes for a compile)."""
        from ray_dynamic_batching_tpu.models.kv_state import pool_head_dim

        ps = 128
        per_row = g.get("f", 1)     # KV heads side by side in a pool row
        q = struct((g["B"], window, g["N"], g["H"]), jnp.bfloat16)
        pool = struct(
            (g["L"], g["P"], ps, g["K"] // per_row,
             pool_head_dim(g["H"] * per_row)), dtype)
        table = struct((g["B"], g["NP"]), jnp.int32)
        lengths = struct((g["B"],), jnp.int32)
        scale = (struct((g["P"], ps, g["K"]), jnp.float32)
                 if dtype == jnp.int8 else None)

        def f(q, pool, table, lengths, scale):
            out = da.paged_decode_attention(
                q, pool, pool, table, lengths, layer=g["L"] - 1,
                k_scale=scale, v_scale=scale, interpret=False,
                sliding=sliding, heads_per_row=per_row)
            assert out is not None, "paged kernel declined"
            return out

        return jax.jit(f), (q, pool, table, lengths, scale)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
    @pytest.mark.parametrize("window", [1, 5])
    @pytest.mark.parametrize("config", sorted(GEOMETRIES))
    def test_stacked_pool_block(self, config, window, dtype):
        f, args = self._call(self.GEOMETRIES[config], window, dtype)
        export.export(f, platforms=["tpu"])(*args)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
    @pytest.mark.parametrize("window", [2, 8])
    def test_gqa_window_rows(self, window, dtype):
        """Mistral's GQA at the widest window: R = window * G = 8 x 4
        query rows a KV head, 256 rows a block against a page's 1,024
        columns in the flat-heads form (2 MiB of score tiles: the last
        shape that takes it, ``tile_math.FLAT_SCORE_MAX_BYTES``)."""
        f, args = self._call(self.GEOMETRIES["mistral-7b"], window, dtype)
        da.clear_decode_paths()
        export.export(f, platforms=["tpu"])(*args)
        assert da.decode_paths()[-1].form == da.FORM_FLAT


@pytest.fixture(scope="module")
def one_chip():
    """A described, unattached v5e chip: ``.compile()`` for it runs the
    Mosaic compiler itself (vector layouts, VMEM), which an export does
    not. Only inside a fixture: one process may load the TPU's library,
    and every xdist worker imports this file."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


class TestPagedKernelCompilesForV5e:
    """The Mosaic compiler accepts both forms of the body at the
    benchmark's geometries: the flat form's [ps, kb, H] -> [ps * kb, H]
    view of a page, its [kb * R, ps * kb] score tiles and lane-row
    scales; the per-head form's strided head slices under the flat
    q/out layout (a 4-head block); and the walk itself (the kernel's own
    copies out of the pool in HBM, an 8-head block of 16 among them,
    the ring's dynamic slot, the loop to the slot's length, the cursor
    in SMEM), for a full layer and for a sliding one. Nothing runs: what
    a step and a page cost is ``tools/run_kernel_ab.py --paged`` on the
    chip."""

    CASES = {
        **{(c, 1): g
           for c, g in TestPagedKernelLowersForTPU.GEOMETRIES.items()},
        ("gpt2-medium", 5): TestPagedKernelLowersForTPU.GEOMETRIES[
            "gpt2-medium"],
        ("mistral-7b", 8): TestPagedKernelLowersForTPU.GEOMETRIES[
            "mistral-7b"],
        ("four-kv-heads", 1): dict(L=2, P=16, B=4, NP=4, N=8, K=4, H=128),
        ("four-kv-heads", 5): dict(L=2, P=16, B=4, NP=4, N=8, K=4, H=128),
        # narrower still (a shard of a TP mesh; one KV head): four and
        # eight positions' heads an (8, 128) tile of the page's view
        ("two-kv-heads", 1): dict(L=2, P=16, B=4, NP=4, N=8, K=2, H=128),
        ("one-kv-head", 1): dict(L=2, P=16, B=4, NP=4, N=8, K=1, H=128),
        # K-EXAONE's pool (benchmark/configs/k-exaone-236b-ep8-1chip.json)
        # read by a window-128 layer, and gpt2-medium's two head blocks
        # under a window astride three pages
        ("k-exaone", 1, 128): dict(
            L=5, P=2048, B=64, NP=32, N=64, K=8, H=128),
        ("gpt2-medium", 5, 200): TestPagedKernelLowersForTPU.GEOMETRIES[
            "gpt2-medium"],
        # gpt2-medium's pool as the engine lays it out since PR 48: two
        # 64-wide heads a row, [.., 8, 128], ONE head block, two query
        # rows a pool head (ten under the spec window)
        **{("gpt2-medium-packed", *rest): dict(
            TestPagedKernelLowersForTPU.GEOMETRIES["gpt2-medium"], f=2)
           for rest in ((1,), (5,), (5, 200))},
    }

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
    @pytest.mark.parametrize(
        "case", sorted(CASES),
        ids=lambda c: f"{c[0]}-w{c[1]}" + (f"-sliding{c[2]}" if c[2:] else ""))
    def test_both_forms_compile(self, case, dtype, one_chip):
        from jax.experimental.compilation_cache import compilation_cache

        if dtype == jnp.int8 and self.CASES[case]["K"] < 4:
            pytest.skip("an int8 pool of fewer than 4 heads keeps the "
                        "per-head form, whose (4, 128) int8 tile Mosaic "
                        "refuses to slice: no configuration has one")
        if dtype == jnp.int8 and self.CASES[case].get("f", 1) > 1:
            pytest.skip("an int8 pool keeps a head a row "
                        "(models/kv_state.py::pool_heads_per_row)")
        struct = lambda shape, dt: jax.ShapeDtypeStruct(
            shape, dt, sharding=one_chip)
        f, args = TestPagedKernelLowersForTPU._call(
            self.CASES[case], case[1], dtype, struct, *case[2:])
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out.
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            f.lower(*args).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        # four heads that are all of K fold in one contraction over the
        # page's tile view; an int8 pool's (no scales there) one at a time
        want = da.FORM_PER_HEAD if (
            case[0].endswith(("-kv-heads", "-kv-head")) and dtype == jnp.int8
        ) else da.FORM_FLAT
        assert da.decode_paths()[-1].form == want


class TestPackedPoolProgramsCompileForV5e:
    """gpt2-medium's decode step and widest chunk program, at its benchmark
    deployment cut to 3 layers, compiled for the described chip with the
    pool two heads a row (``[3, 128, 128, 8, 128]``): the pool is read and
    written in place (no pool-sized ``copy``, every operation that yields a
    pool a scatter's fusion), the paged write takes the k projection's
    output as it lies (no ``pad`` in front of the scatter), the kernel is
    handed 8 x 128 rows, and the ``bf16[16,64]`` copies of ROADMAP S1 (f)
    are NAMED: each is the asynchronous prefetch of a q, k or v BIAS (a
    ``[16, 64]`` parameter), there at the parent too: not the pool's."""

    def test_the_pool_is_read_and_written_where_it_lies(
            self, one_chip, monkeypatch):
        import dataclasses
        import json
        import pathlib
        import re

        from jax.experimental.compilation_cache import compilation_cache

        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
        from ray_dynamic_batching_tpu.models.decoder import DecoderConfig

        root = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
        cfg = json.loads((root / "configs" / "gpt2-medium.json").read_text())
        llm = cfg["deployment"]["llm"]
        dc = dataclasses.replace(
            DecoderConfig(**cfg["program"]["decoder_config"]), num_layers=3)
        m = CausalLM(dc, name="m", dtype=jnp.bfloat16)
        B, ps = llm["num_slots"], llm["page_size"]
        W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        placed = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda x: struct(x.shape, x.dtype), tree)
        cache = placed(jax.eval_shape(lambda: m.make_paged_cache(
            B, llm["kv_pool_pages"], ps, llm["max_len"])))
        assert cache.k.shape == (3, llm["kv_pool_pages"], ps, 8, 128)
        p = jax.tree_util.tree_map(
            lambda x: struct(x.shape, jnp.bfloat16),
            jax.eval_shape(m.init, jax.random.PRNGKey(0)))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        da.clear_decode_paths()
        try:
            decode = jax.jit(
                lambda p, t, c, a: m.decode_step_paged(p, t, c, a),
                donate_argnums=(2,)).lower(
                p, struct((B, 1), jnp.int32), cache,
                struct((B,), jnp.bool_)).compile().as_text()
            chunk = jax.jit(
                lambda p, t, k, c, tb, s, i: m.prefill_chunk_paged(
                    p, t, k, c, tb, s, i), donate_argnums=(3,)).lower(
                p, struct((2, W), jnp.int32), struct((2, W), jnp.int32),
                cache, struct((2, NP), jnp.int32), struct((2,), jnp.int32),
                struct((2,), jnp.int32)).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        paths = da.decode_paths()
        assert paths and all(
            (q.heads_per_row, q.kb, q.rows, q.head_dim) == (2, 8, 2, 128)
            for q in paths)
        pool = rf"bf16\[3,{llm['kv_pool_pages']},{ps},8,128\]"
        for text in (decode, chunk):
            lines = text.splitlines()
            assert not [ln for ln in lines
                        if re.search(rf"= {pool}\S* copy", ln)]
            # what yields a pool: a scatter (its fusion), in place
            made = [ln for ln in lines if re.search(
                rf"^\s*(ROOT )?%\S+ = {pool}\S* (?!parameter|get-tuple)", ln)]
            assert made and all(
                re.search(r" (scatter|fusion)\(", ln) and "scatter" in ln
                for ln in made), made[:3]
            # the rows reach the scatter as the projection left them
            # (the parent's: ``pad_bitcast_fusion``, ``jit(_pad)/pad``)
            assert not [ln for ln in lines if "_kv_attention" in ln
                        and re.search(r"= bf16\S* pad\(|jit\(_pad\)", ln)]
        # S1 (f), named: the [16, 64] copies are the biases' prefetch
        starts = [ln for ln in decode.splitlines()
                  if "copy-start(" in ln and "bf16[16,64]" in ln]
        assert starts and all(
            re.search(r"copy-start\(%p__params____layer\d+____[qkv]____"
                      r"bias__", ln) for ln in starts)
        assert "_paged_decode_attention" in decode


class TestSparseKernelCompilesForV5e:
    """``ops/sparse_attention.py``'s mask-form kernel at the configuration
    that has an indexer (24 slots, a table of 144 pages, 32/4 heads of 128:
    a block of FOUR key heads folded in one contraction over the pool's
    tile view, ``[8, 3456, 128, 4, 128] -> [8, 3456, 64, 8, 128]``: two
    positions' heads an (8, 128) tile, which the compiler must take as a
    BITCAST behind the page write, never as a copy of the pool) and at 8
    key heads (the shared flat fold, the pool as it lies); ONE bf16 key
    head is under the page copy's tiling and declines by name
    (``_mask_form_declines``). Nothing runs:
    ``tools/run_kernel_ab.py --sparse`` on the chip says what it costs."""

    @pytest.mark.parametrize("kv, pages", [
        (4, 4),     # what the cell's shapes pick: four pages a fold
        (4, 2), (4, 1),     # the A/B tool's other widths (picker patched)
        (8, 1)])
    def test_the_mask_form_compiles(self, kv, pages, one_chip, monkeypatch):
        import re

        from jax.experimental.compilation_cache import compilation_cache

        from ray_dynamic_batching_tpu.ops import sparse_attention as sparse

        B, NP, ps, H, N, L, P = 24, 144, 128, 128, 32, 8, 3456
        if (kv, pages) in ((4, 4), (8, 1)):     # from the shapes alone
            assert sparse._walk(kv, N // kv, ps, H, 2, NP) == (pages, 3)
        else:
            monkeypatch.setattr(sparse, "_fold_pages", lambda *a: pages)
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)

        def step(q, k, v, table, lengths, chosen, page, row):
            # the decode program's order: this step's row written into
            # the (donated) pool, then the read
            k, v = k.at[3, page, 0].set(row), v.at[3, page, 0].set(row)
            return sparse.sparse_paged_decode_attention(
                q, k, v, table, lengths, chosen, layer=3,
                interpret=False), k, v

        pool = struct((L, P, ps, kv, H), jnp.bfloat16)
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
                struct((B, 1, N, H), jnp.bfloat16), pool, pool,
                struct((B, NP), jnp.int32), struct((B,), jnp.int32),
                struct((B, NP * ps), jnp.bool_), struct((B,), jnp.int32),
                struct((B, kv, H), jnp.bfloat16)).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        text = compiled.as_text()
        view = f"bf16[{L},{P},{ps // 2},8,{H}]"
        casts = [ln for ln in text.splitlines()
                 if re.search(rf"= {re.escape(view)}\S* bitcast\(", ln)]
        # k and v, each a bitcast of the written pool (4 heads); 8 heads
        # are read as they lie
        assert len(casts) == (2 if kv == 4 else 0), casts
        # nothing but the two in-place page writes makes an array of the
        # pool's size: no copy, no transpose, no temporary
        made = re.findall(
            rf"= bf16\[{L},{P},[\d,]*\]\{{[^}}]*\}} ([\w\-]+)\(", text)
        made = [op for op in made if op != "parameter"]
        assert sorted(set(made)) == (
            ["bitcast", "fusion", "scatter"] if kv == 4
            else ["fusion", "scatter"]), made
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


class TestKindsKernelCompilesForV5e:
    """The paged kernel handed a pool of ONE LAYER KIND of a model whose
    kinds differ (``benchmark/configs/mimo-v2-flash-ep16-1chip.json``): the
    full layers' pages (40 slots, a table of 144, 64/4 heads, k rows 256
    lanes and v rows 128: a block of FOUR key heads folded in one
    contraction; the v pool, a lane tile a row, read through its tile
    view, two positions' heads an (8, 128) tile, which the compiler must
    take as a BITCAST behind the page write; the k pool, two lane tiles a
    row, as it lies: its view would be a 3 GB copy)
    and the window layers' ring (64/8 heads, 240 pages, a window of 128, a
    sink a query head). Nothing runs: the cell's traced run says what a
    page costs."""

    @pytest.mark.parametrize("kv, pages, sliding", [
        (4, 5760, 0), (8, 240, 128)])
    def test_a_narrower_v_pool_and_a_sink_compile(
            self, kv, pages, sliding, one_chip):
        import re

        from jax.experimental.compilation_cache import compilation_cache

        from ray_dynamic_batching_tpu.ops import decode_attention

        B, NP, ps, N, L = 40, 144, 128, 64, 2
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)

        def step(q, k, v, table, lengths, sink, page, krow, vrow):
            k, v = k.at[1, page, 0].set(krow), v.at[1, page, 0].set(vrow)
            out = decode_attention.paged_decode_attention(
                q, k, v, table, lengths, layer=1, sliding=sliding,
                sink=sink if sliding else None, v_dim=128, interpret=False)
            assert out is not None
            return out, k, v

        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        decode_attention.clear_decode_paths()
        try:
            compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
                struct((B, 1, N, 192), jnp.bfloat16),
                struct((L, pages, ps, kv, 256), jnp.bfloat16),
                struct((L, pages, ps, kv, 128), jnp.bfloat16),
                struct((B, NP), jnp.int32), struct((B,), jnp.int32),
                struct((N,), jnp.float32), struct((B,), jnp.int32),
                struct((B, kv, 256), jnp.bfloat16),
                struct((B, kv, 128), jnp.bfloat16)).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        (path,) = decode_attention.decode_paths()
        assert (path.form, path.kv_heads, path.v_dim, path.sink) == (
            decode_attention.FORM_FLAT, kv, 128, bool(sliding))
        text = compiled.as_text()
        casts = [ln for ln in text.splitlines() if re.search(
            rf"= bf16\[{L},{pages},{ps // 2},8,(256|128)\]\S* bitcast\(", ln)]
        assert len(casts) == (1 if kv == 4 else 0), casts      # the v pool
        made = re.findall(
            rf"= bf16\[{L},{pages},[\d,]*\]\{{[^}}]*\}} ([\w\-]+)\(", text)
        made = sorted({op for op in made if op != "parameter"})
        assert made == (["bitcast", "fusion", "scatter"] if kv == 4
                        else ["fusion", "scatter"]), made
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


class TestNarrowFoldCompilesForV5e:
    """ISSUE 52: the paged kernel's narrow arm at 4, 2 (what the shapes
    pick) and 1 live pages an online-softmax update, at the two cells' own
    shapes: LFM2's pool of two
    heads a row (64 slots, a table of 32, ``[10, 2048, 128, 4, 128]``, both
    pools through their tile view) and MiMo's full layers (40 slots, a table
    of 144, k rows of two lane tiles as they lie, v rows through the view).
    The Mosaic compiler takes the group's ring (``[depth, pages * 64, 8,
    128]``: a page's copy lands ``r * 64`` rows into a slot), the
    conditional copies of a short last group and the ring's zeroing; the
    pool's view stays a BITCAST behind the page write and nothing else
    makes an array of the pool's size. Nothing runs:
    ``tools/run_kernel_ab.py --paged --pages-a-fold 1,2,4`` on the chip
    says what a page costs at each width."""

    CELLS = {
        # q heads, q width, pool rows a position, k width, v width, heads a
        # row, slots, table, layers, pages
        "lfm2": (32, 64, 4, 128, 128, 2, 64, 32, 10, 2048),
        "mimo_full": (64, 192, 4, 256, 128, 1, 40, 144, 2, 5760),
    }

    @pytest.mark.parametrize("pages", [4, 2, 1])
    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_the_group_walk_compiles(self, cell, pages, one_chip,
                                     monkeypatch):
        import re

        from jax.experimental.compilation_cache import compilation_cache

        from ray_dynamic_batching_tpu.ops import tile_math

        N, H, rows, Hk, Hv, f, B, NP, L, P = self.CELLS[cell]
        ps = 128
        if pages != 2:      # the A/B tool's other widths
            monkeypatch.setattr(
                tile_math, "paged_fold_pages",
                lambda *a, narrow=False, **kw: pages if narrow else 1)
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)

        def step(q, k, v, table, lengths, page, krow, vrow):
            k, v = k.at[1, page, 0].set(krow), v.at[1, page, 0].set(vrow)
            out = da.paged_decode_attention(
                q, k, v, table, lengths, layer=1, interpret=False,
                heads_per_row=f, v_dim=Hv if f == 1 else 0)
            assert out is not None
            return out, k, v

        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        da.clear_decode_paths()
        try:
            compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
                struct((B, 1, N, H), jnp.bfloat16),
                struct((L, P, ps, rows, Hk), jnp.bfloat16),
                struct((L, P, ps, rows, Hv), jnp.bfloat16),
                struct((B, NP), jnp.int32), struct((B,), jnp.int32),
                struct((B,), jnp.int32),
                struct((B, rows, Hk), jnp.bfloat16),
                struct((B, rows, Hv), jnp.bfloat16)).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        (path,) = da.decode_paths()
        da.clear_decode_paths()
        assert (path.form, path.pages, path.kb) == (da.FORM_FLAT, pages, 4)
        # MiMo's rows of two lane tiles: four pages leave the ring a
        # double buffer (an A/B reading only: the picker takes 2 for both)
        assert path.depth == (2 if (cell, pages) == ("mimo_full", 4) else 3)
        text = compiled.as_text()
        casts = [ln for ln in text.splitlines() if re.search(
            rf"= bf16\[{L},{P},{ps // 2},8,128\]\S* bitcast\(", ln)]
        # a pool a lane tile wide is read through its view: LFM2's two,
        # MiMo's v
        assert len(casts) == (2 if cell == "lfm2" else 1), casts
        made = re.findall(
            rf"= bf16\[{L},{P},[\d,]*\]\{{[^}}]*\}} ([\w\-]+)\(", text)
        assert sorted({op for op in made if op != "parameter"}) == [
            "bitcast", "fusion", "scatter"], made
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


class TestFullLayersChunkAttentionInTheCompiledProgram:
    """``chunk_attention_full_dev_share_pct.batch`` finds the full layers'
    chunk attention in a device trace by XLA's fusion names, because the
    TPU's trace carries no ``jax.named_scope``. The compiled program does
    (each instruction's ``op_name``): here the two are held together. In
    ``mimo-v2-flash-ep16-1chip``'s widest chunk program, compiled for a
    described v5e, every operation the pattern takes that says where it
    came from came from ``chunk_attention_full`` (none from
    ``chunk_attention_window``), and the walk's parts are all there under
    the names the cell's traced run recorded
    (``benchmark/tests/data/mimo_chunk_ops.txt``)."""

    def test_the_metrics_pattern_is_the_scopes_operations(
            self, one_chip, monkeypatch):
        import json
        import re
        from pathlib import Path
        from types import SimpleNamespace

        from jax.experimental.compilation_cache import compilation_cache

        from benchmark import trace_reduce
        from benchmark.tests.test_kind_readers import FULL_WALK
        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
        from ray_dynamic_batching_tpu.models.decoder import DecoderConfig

        root = Path(__file__).resolve().parents[1] / "benchmark"
        cfg = json.loads((root / "configs"
                          / "mimo-v2-flash-ep16-1chip.json").read_text())
        args = json.loads((root / "layer_metrics" / (
            "chunk_attention_full_dev_share_pct.batch.json")).read_text())[
                "args"]
        llm = cfg["deployment"]["llm"]
        m = CausalLM(DecoderConfig(**cfg["program"]["decoder_config"]),
                     name="m", dtype=jnp.bfloat16)
        B, ps = llm["num_slots"], llm["page_size"]
        W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        placed = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda x: struct(x.shape, x.dtype), tree)
        cache = placed(jax.eval_shape(lambda: m.make_paged_cache(
            B, llm["kv_pool_pages"], ps, llm["max_len"], widest_chunk=W)))
        p = jax.tree_util.tree_map(
            lambda x: struct(x.shape, jnp.bfloat16),
            jax.eval_shape(m.init, jax.random.PRNGKey(0)))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            text = jax.jit(
                lambda *a: m.prefill_chunk_paged(*a[:-1], ring_tables=a[-1]),
                donate_argnums=(3,)).lower(
                p, struct((1, W), jnp.int32), struct((1, W), jnp.int32),
                cache, struct((1, NP), jnp.int32), struct((1,), jnp.int32),
                struct((1,), jnp.int32), struct((1, NP), jnp.int32),
            ).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        rx = re.compile(args["op"])
        scopes = {}         # a taken operation's name -> where it came from
        for line in text.splitlines():
            if not line.startswith("  ") or "fused_computation" in (
                    line.split("=")[0]):
                continue
            line = line.strip().removeprefix("ROOT ")
            name = trace_reduce.stable_name(SimpleNamespace(name=line))
            if not rx.search(name):
                continue
            said = re.search(r'op_name="([^"]*)"', line)
            scopes.setdefault(name, set()).add(
                "full" if said and "chunk_attention_full" in said.group(1)
                else "window" if said and "chunk_attention_window" in (
                    said.group(1)) else "")
        assert FULL_WALK <= set(scopes), sorted(scopes)
        assert all("window" not in where for where in scopes.values())
        # the walk's arithmetic says its scope (a bare copy says nothing)
        assert all("full" in scopes[n] for n in FULL_WALK
                   if not n.startswith("copy_"))


class TestLatentProgramsCompileForV5e:
    """``xing4-29b-ep8-1chip``'s own kernels and programs at the published
    widths, for a described v5e: the absorbed decode kernel
    (``ops/latent_attention.py``: 40 slots, 32 heads against a pool of
    ``[10, 5760, 128, 640]`` rows, a page copied once into a ring of VMEM
    slots and contracted twice), the Sinkhorn kernel at a decode step's 40
    tokens and a chunk group's 1,024 (``models/hyper_connections.py``), and
    the decode and the widest chunk program of the first three layers (two
    dense, one of experts): nothing but the in-place row write makes an
    array of the pool's size, and the operations under the PR's scopes are
    the ones the two pattern metrics take
    (``chunk_attention_latent_dev_share_pct.batch``,
    ``hc_mix_dev_share_pct.batch``: the TPU's trace carries no scope).
    Nothing runs: ``tools/latent_ab.py`` on the chip says what they cost."""

    @staticmethod
    def _compile(f, *args, donate=()):
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return jax.jit(f, donate_argnums=donate).lower(*args).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()

    def test_the_decode_kernel_and_the_sinkhorn_kernel_compile(
            self, one_chip):
        from ray_dynamic_batching_tpu.models import hyper_connections as hc
        from ray_dynamic_batching_tpu.ops import latent_attention as la

        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        B, N, NP, L, P, ps = 40, 32, 144, 10, 5760, 128
        W = la.row_width(512, 64)
        assert W == 640
        self._compile(
            lambda q, pool, t, n, ly: la._latent_paged_decode_attention(
                q, pool, t, n, ly, rank=512, scale=0.1, interpret=False),
            struct((B, N, W), jnp.bfloat16),
            struct((L, P, ps, W), jnp.bfloat16), struct((B, NP), jnp.int32),
            struct((B,), jnp.int32), struct((1,), jnp.int32))
        for tokens in (40, 1024):
            self._compile(
                lambda m: hc._hc_sinkhorn(m, iters=20, eps=1e-6,
                                          interpret=False),
                struct((16, tokens), jnp.float32))

    def test_the_programs_compile_and_the_patterns_are_the_scopes_operations(
            self, one_chip, monkeypatch):
        import json
        import re
        from pathlib import Path
        from types import SimpleNamespace

        from benchmark import trace_reduce
        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
        from ray_dynamic_batching_tpu.models.decoder import DecoderConfig

        root = Path(__file__).resolve().parents[1] / "benchmark"
        cfg = json.loads((root / "configs"
                          / "xing4-29b-ep8-1chip.json").read_text())
        # a metric's pattern, and what an operation it takes may say of
        # where it came from: the scopes; the latent sublayer's own method
        # (the walk's mask, the last division); for the streams the products
        # XLA fuses a mix INTO (the mix is their epilogue), the experts' sum
        # on its way into the mix and the embedding's copy into the streams
        pattern = {
            metric: (re.compile(json.loads((root / "layer_metrics" / (
                f"{metric}.json")).read_text())["args"]["op"]),
                re.compile(said))
            for metric, said in (
                ("chunk_attention_latent_dev_share_pct.batch",
                 r"latent_chunk_|\._latent_attention/"),
                ("hc_mix_dev_share_pct.batch",
                 r"hc_maps|hc_mix|/(o|mlp_down|shared_down)/dot_general$"
                 r"|moe_combine/convert_element_type$"
                 r"|DecoderModule/broadcast_in_dim$"))}
        llm = cfg["deployment"]["llm"]
        dc = dict(cfg["program"]["decoder_config"], num_layers=3)
        m = CausalLM(DecoderConfig(**dc), name="m", dtype=jnp.bfloat16)
        B, ps = llm["num_slots"], llm["page_size"]
        W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        cache = jax.tree_util.tree_map(
            lambda x: struct(x.shape, x.dtype),
            jax.eval_shape(lambda: m.make_paged_cache(
                B, llm["kv_pool_pages"], ps, llm["max_len"])))
        p = jax.tree_util.tree_map(
            lambda x: struct(x.shape, jnp.bfloat16),
            jax.eval_shape(m.init, jax.random.PRNGKey(0)))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        chunk = self._compile(
            lambda *a: m.prefill_chunk_paged(*a, moe_counters=True),
            p, struct((2, W), jnp.int32), struct((2, W), jnp.int32), cache,
            struct((2, NP), jnp.int32), struct((2,), jnp.int32),
            struct((2,), jnp.int32), donate=(3,))
        decode = self._compile(
            lambda *a: m.decode_step_paged(*a, moe_counters=True),
            p, struct((B, 1), jnp.int32), cache, struct((B,), jnp.bool_),
            donate=(2,))
        pool = rf"bf16\[3,{llm['kv_pool_pages']},{ps},640\]"
        for compiled in (chunk, decode):
            text = compiled.as_text()
            made = re.findall(rf"= {pool}\{{[^}}]*\}} ([\w\-]+)\(", text)
            assert set(made) <= {"parameter", "scatter", "fusion",
                                 "bitcast", "get-tuple-element"}, made
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
            # every top-level operation a pattern takes says it came from
            # that pattern's scopes, or says nothing (a bare copy)
            comp = None
            for line in text.splitlines():
                opened = re.match(r"^(ENTRY )?%?([\w\.\-]+) \(.*\{\s*$",
                                  line)
                if opened:
                    comp = opened.group(2)
                    continue
                if (comp is None or not line.startswith("  ")
                        or comp.startswith(("fused_computation", "region"))):
                    continue
                line = line.strip().removeprefix("ROOT ")
                if " parameter(" in line:    # an argument: no operation
                    continue
                name = trace_reduce.stable_name(SimpleNamespace(name=line))
                said = re.search(r'op_name="([^"]*)"', line)
                for metric, (rx, may_say) in pattern.items():
                    if (rx.search(name) and said
                            and ("chunk" not in metric or compiled is chunk)):
                        assert may_say.search(said.group(1)), (
                            metric, name, said.group(1))
        assert "_latent_paged_decode_attention" in decode.as_text()
        assert "_hc_sinkhorn" in decode.as_text()
        assert "_hc_sinkhorn" in chunk.as_text()


class TestSparseLatentProgramsCompileForV5e:
    """``glm-5-ep16-1chip``'s own kernel and programs at the published
    widths, for a described v5e: the latent decode kernel under a selection
    (``ops/latent_attention.py``, the mask form: 40 slots, 64 heads against
    a pool of ``[5, 5760, 128, 640]`` rows, a slot's selection ``[36, 512]``
    a fold's row beside it), and the decode and the widest chunk program of
    the first two layers (the dense one and one of experts): nothing but
    the in-place writes of a row and an index key makes an array the size
    of either plane, the kernel is in the decode program, and the
    operations the two pattern metrics take
    (``sparse_latent_select_dev_share_pct.batch``,
    ``chunk_attention_sparse_latent_dev_share_pct.batch``: the TPU's trace
    carries no scope) say they came from the selection and from the chunk
    walk. Nothing runs: a traced run of the cell says what they cost."""

    _compile = staticmethod(TestLatentProgramsCompileForV5e._compile)

    def test_the_mask_form_kernel_compiles_at_full_size(self, one_chip):
        from ray_dynamic_batching_tpu.ops import latent_attention as la

        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        B, N, NP, L, P, ps = 40, 64, 144, 5, 5760, 128
        W, bp = la.row_width(512, 64), la.FOLD_PAGES
        self._compile(
            lambda q, pool, t, n, ly, sel: (
                la._latent_paged_decode_attention(
                    q, pool, t, n, ly, sel, rank=512, scale=0.0625,
                    interpret=False)),
            struct((B, N, W), jnp.bfloat16),
            struct((L, P, ps, W), jnp.bfloat16), struct((B, NP), jnp.int32),
            struct((B,), jnp.int32), struct((1,), jnp.int32),
            struct((B, NP // bp, bp * ps), jnp.int32))

    @staticmethod
    def _lowered(one_chip, layers):
        """(the decode program's and the widest chunk program's
        ``jax.stages.Lowered`` thunks, the deployment, the select metric's
        and the chunk walk's arguments) of the first ``layers`` layers."""
        import json
        import re
        from pathlib import Path

        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
        from ray_dynamic_batching_tpu.models.decoder import DecoderConfig

        root = Path(__file__).resolve().parents[1] / "benchmark"
        cfg = json.loads((root / "configs"
                          / "glm-5-ep16-1chip.json").read_text())
        metric = lambda name: json.loads(  # noqa: E731
            (root / "layer_metrics" / f"{name}.json").read_text())["args"]
        select = metric("sparse_latent_select_dev_share_pct.batch")
        walk = re.compile(metric(
            "chunk_attention_sparse_latent_dev_share_pct.batch")["op"])
        llm = cfg["deployment"]["llm"]
        dc = dict(cfg["program"]["decoder_config"], num_layers=layers)
        m = CausalLM(DecoderConfig(**dc), name="m", dtype=jnp.bfloat16)
        B, ps = llm["num_slots"], llm["page_size"]
        W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        cache = jax.tree_util.tree_map(
            lambda x: struct(x.shape, x.dtype),
            jax.eval_shape(lambda: m.make_paged_cache(
                B, llm["kv_pool_pages"], ps, llm["max_len"])))
        p = jax.tree_util.tree_map(
            lambda x: struct(x.shape, jnp.bfloat16),
            jax.eval_shape(m.init, jax.random.PRNGKey(0)))
        compile_ = TestLatentProgramsCompileForV5e._compile
        chunk = lambda: compile_(  # noqa: E731
            lambda *a: m.prefill_chunk_paged(*a, moe_counters=True),
            p, struct((2, W), jnp.int32), struct((2, W), jnp.int32), cache,
            struct((2, NP), jnp.int32), struct((2,), jnp.int32),
            struct((2,), jnp.int32), donate=(3,))
        decode = lambda: compile_(  # noqa: E731
            lambda *a: m.decode_step_paged(*a, moe_counters=True),
            p, struct((B, 1), jnp.int32), cache, struct((B,), jnp.bool_),
            donate=(2,))
        return decode, chunk, llm, select, walk

    def test_the_views_the_decode_program_computes_again_are_the_selections(
            self, one_chip, monkeypatch):
        """ALL five layers' decode step: XLA's rematerialisation pass
        computes some layers' gathered index keys a second time and renames
        them ``fusion.<n>.remat`` (none at two layers), which the select
        metric's ``remat`` takes by that name inside the decode program:
        every such operation there says it is the selection's gather."""
        import re
        from types import SimpleNamespace

        from benchmark import trace_reduce

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        decode, _, _, select, _ = self._lowered(one_chip, 5)
        remat = re.compile(select["remat"]["op"])
        again = [
            line for line in decode().as_text().splitlines()
            if line.startswith("  %") and remat.search(
                trace_reduce.stable_name(SimpleNamespace(name=line.strip())))]
        assert again
        for line in again:
            assert re.search(
                r"bf16\[5760,128,128\].*sparse_latent_select/.*gather", line
            ), line

    def test_the_programs_compile_and_the_patterns_are_the_selections(
            self, one_chip, monkeypatch):
        import re
        from types import SimpleNamespace

        from benchmark import trace_reduce

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        decode, chunk, llm, select, walk = self._lowered(one_chip, 2)
        decode, chunk, ps = decode(), chunk(), llm["page_size"]
        planes = rf"bf16\[2,{llm['kv_pool_pages']},{ps},(?:640|128)\]"
        taken = TestSelectionsOperationsInTheCompiledPrograms._taken
        for compiled in (chunk, decode):
            text = compiled.as_text()
            made = re.findall(rf"= {planes}\{{[^}}]*\}} ([\w\-]+)\(", text)
            assert set(made) <= {"parameter", "scatter", "fusion",
                                 "bitcast", "get-tuple-element"}, made
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
            # one k-th-key search a layer, taken whole; the scores among
            # the operations beside it
            loops, names = taken(text, select)
            assert len(loops) == 2, loops
            assert names
        # what the chunk walk's pattern takes at the top level of the chunk
        # program says it came from the walk (or says nothing: a bare copy)
        comp = None
        found = set()
        for line in chunk.as_text().splitlines():
            opened = re.match(r"^(ENTRY )?%?([\w\.\-]+) \(.*\{\s*$", line)
            if opened:
                comp = opened.group(2)
                continue
            if (comp is None or not line.startswith("  ")
                    or comp.startswith(("fused_computation", "region"))):
                continue
            line = line.strip().removeprefix("ROOT ")
            if " parameter(" in line:
                continue
            name = trace_reduce.stable_name(SimpleNamespace(name=line))
            said = re.search(r'op_name="([^"]*)"', line)
            if walk.search(name) and said:
                found.add(name)
                assert re.search(r"sparse_latent_chunk|\._latent_attention/",
                                 said.group(1)), (name, said.group(1))
        assert found
        assert "_latent_paged_decode_attention" in decode.as_text()
        assert "_latent_paged_decode_attention" not in chunk.as_text()


class TestHybridProgramsCompileForV5e:
    """``lfm2-24b-a2b-ep8-1chip``'s programs at the published widths, 8 of
    its 40 layers (CCGC CCGC: six conv layers, two pool layers, the first
    two dense and the rest of experts), for a described v5e: the decode
    step takes the dense paged kernel over two heads a pool row at GQA 32/8;
    the widest chunk program gathers its pages where they lie (a gather of
    4-row positions out of a pool of more than one layer made XLA lay the
    WHOLE pool out anew, twice an attention layer:
    ``ops/attention.py::_pages``), so nothing but the in-place page write
    makes an array of the pool's size; the conv state is written where it
    lies; and the operations ``short_conv_in_proj_dev_share_pct.batch``'s
    pattern takes (one width: 3 x hidden) are the conv mixers' first product
    and its streamed weights (the TPU's trace carries no scope). Nothing
    runs: ``tools/short_conv_ab.py`` on the chip says what the mixer costs."""

    def test_the_programs_compile_and_the_pattern_is_the_mixers_operations(
            self, one_chip, monkeypatch):
        import json
        import re
        from pathlib import Path
        from types import SimpleNamespace

        from benchmark import trace_reduce
        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
        from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
        from ray_dynamic_batching_tpu.ops import attention as attn_ops

        root = Path(__file__).resolve().parents[1] / "benchmark"
        cfg = json.loads((root / "configs"
                          / "lfm2-24b-a2b-ep8-1chip.json").read_text())
        rx = re.compile(json.loads((
            root / "layer_metrics"
            / "short_conv_in_proj_dev_share_pct.batch.json"
        ).read_text())["args"]["op"])
        llm = cfg["deployment"]["llm"]
        dc = dict(cfg["program"]["decoder_config"], num_layers=8)
        m = CausalLM(DecoderConfig(**dc), name="m", dtype=jnp.bfloat16)
        B, ps, P = llm["num_slots"], llm["page_size"], llm["kv_pool_pages"]
        W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        cache = jax.tree_util.tree_map(
            lambda x: struct(x.shape, x.dtype),
            jax.eval_shape(lambda: m.make_paged_cache(
                B, P, ps, llm["max_len"])))
        assert cache.k.shape == (2, P, ps, 4, 128)
        assert cache.conv_state.shape == (6, B, 2, 2048)
        p = jax.tree_util.tree_map(
            lambda x: struct(x.shape, jnp.bfloat16),
            jax.eval_shape(m.init, jax.random.PRNGKey(0)))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        attn_ops.clear_attention_paths()
        compile_ = TestLatentProgramsCompileForV5e._compile
        g = 2
        chunk = compile_(
            lambda *a: m.prefill_chunk_paged(
                *a[:-1], moe_counters=True, state_slots=a[-1]),
            p, struct((g, W), jnp.int32), struct((g, W), jnp.int32), cache,
            struct((g, NP), jnp.int32), struct((g,), jnp.int32),
            struct((g,), jnp.int32), struct((g,), jnp.int32), donate=(3,))
        decode = compile_(
            lambda *a: m.decode_step_paged(*a, moe_counters=True),
            p, struct((B, 1), jnp.int32), cache, struct((B,), jnp.bool_),
            donate=(2,))
        said = {(r.q_shape[1], r.describe()) for r in attn_ops.attention_paths()
                if r.path != "short_conv"}
        assert said == {
            (W, "gather-then-flash kernel"),
            (1, "paged kernel (stacked pool, 2 heads a row)")}
        assert {r.describe() for r in attn_ops.attention_paths()
                if r.path == "short_conv"} == {
            "short convolution, 3 taps in XLA, a state a slot (no pages)"}
        pool = rf"bf16\[2,{P},{ps},4,128\]"
        for compiled in (chunk, decode):
            text = compiled.as_text()
            made = re.findall(rf"= {pool}\{{[^}}]*\}} ([\w\-]+)\(", text)
            assert set(made) <= {"parameter", "scatter", "fusion",
                                 "bitcast", "get-tuple-element"}, made
            # the chunk's rows and scores, never a copy of the pool (0.5 GB
            # here, 2.7 GB at the cell's 10 pool layers)
            assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
            comp = None
            taken = set()
            for line in text.splitlines():
                opened = re.match(r"^(ENTRY )?%?([\w\.\-]+) \(.*\{\s*$",
                                  line)
                if opened:
                    comp = opened.group(2)
                    continue
                if (comp is None or not line.startswith("  ")
                        or comp.startswith(("fused_computation", "region"))):
                    continue
                line = line.strip().removeprefix("ROOT ")
                if " parameter(" in line:
                    continue
                name = trace_reduce.stable_name(SimpleNamespace(name=line))
                op_name = re.search(r'op_name="([^"]*)"', line)
                if rx.search(name) and op_name:
                    # the mixer's own first product, under its scope
                    assert re.search(r"short_conv|conv_in",
                                     op_name.group(1)), (
                        name, op_name.group(1))
                    taken.add(name)
            assert taken, "the pattern takes nothing of this program"
        assert "_paged_decode_attention" in decode.as_text()


class TestStateSpaceProgramsCompileForV5e:
    """``falcon-h1-34b-1chip``'s programs at the published widths, 2 of its 6
    layers, for a described v5e: the decode step takes the dense paged kernel
    at GQA 20/4 (FIVE query heads a KV head, 4 pool rows a position: the
    narrow arm) and updates the float32 state plane IN PLACE, one Pallas
    kernel a layer with the plane aliased onto its first result
    (``ops/ssm_update.py``; no copy of it: a second 1.6 GB would not fit the
    cell, and the stable name of the kernel's HLO line is one that
    ``ssm_state_update_roofline_pct``'s reader takes); the widest chunk program
    gathers its pages where they lie (``ops/attention.py::_pages``: 4 KV
    heads of 128 are 4 rows a position), cuts its rows' states out of the
    plane a row at a time (a gather made XLA lay half the plane out anew,
    twice) and makes one row of logits a sequence, not 512 (1.07 GB at
    261,120 columns). The patterns the cell's metrics read are held to the
    scopes' operations (the TPU's trace carries no scope). Nothing runs."""

    def test_the_programs_compile_in_place_and_the_patterns_find_the_mixer(
            self, one_chip, monkeypatch):
        import json
        import re
        from pathlib import Path
        from types import SimpleNamespace

        from benchmark import trace_reduce
        from benchmark.readers import ssm_state_update
        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
        from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
        from ray_dynamic_batching_tpu.ops import attention as attn_ops

        root = Path(__file__).resolve().parents[1] / "benchmark"
        cfg = json.loads((root / "configs"
                          / "falcon-h1-34b-1chip.json").read_text())
        layers = 2
        cfg["num_hidden_layers"] = layers
        metric = lambda name: json.loads((  # noqa: E731
            root / "layer_metrics" / f"{name}.json").read_text())["args"]
        in_proj = re.compile(metric("ssm_in_proj_dev_share_pct.batch")["op"])
        scan = re.compile(metric("ssm_chunk_scan_dev_share_pct.batch")["op"])
        update = re.compile(ssm_state_update.plane_pattern(cfg))
        llm = cfg["deployment"]["llm"]
        dc = dict(cfg["program"]["decoder_config"], num_layers=layers)
        m = CausalLM(DecoderConfig(**dc), name="m", dtype=jnp.bfloat16)
        B, ps, P = llm["num_slots"], llm["page_size"], llm["kv_pool_pages"]
        W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        cache = jax.tree_util.tree_map(
            lambda x: struct(x.shape, x.dtype),
            jax.eval_shape(lambda: m.make_paged_cache(
                B, P, ps, llm["max_len"])))
        assert cache.k.shape == (layers, P, ps, 4, 128)
        assert cache.ssm_state.shape == (layers, B, 32, 128, 256)
        assert cache.ssm_state.dtype == jnp.float32
        p = jax.tree_util.tree_map(
            lambda x: struct(x.shape, jnp.bfloat16),
            jax.eval_shape(m.init, jax.random.PRNGKey(0)))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        attn_ops.clear_attention_paths()
        compile_ = TestLatentProgramsCompileForV5e._compile
        g = 2
        chunk = compile_(
            lambda *a: m.prefill_chunk_paged(*a[:-1], state_slots=a[-1]),
            p, struct((g, W), jnp.int32), struct((g, W), jnp.int32), cache,
            struct((g, NP), jnp.int32), struct((g,), jnp.int32),
            struct((g,), jnp.int32), struct((g,), jnp.int32), donate=(3,))
        decode = compile_(
            m.decode_step_paged, p, struct((B, 1), jnp.int32), cache,
            struct((B,), jnp.bool_), donate=(2,))
        said = {(r.q_shape[1], r.describe())
                for r in attn_ops.attention_paths() if r.path != "ssm"}
        assert said == {(W, "gather-then-flash kernel"),
                        (1, "paged kernel (stacked pool)")}
        rows = [r for r in attn_ops.attention_paths()
                if r.path == "ssm" and r.q_shape[1] == 1]
        assert len(rows) == layers and all(
            r.kernel and not r.interpret and not r.declines for r in rows)
        pool = rf"bf16\[{layers},{P},{ps},4,128\]"
        plane = rf"f32\[{layers},{B},32,128,256\]"
        found = {"decode": set(), "chunk": set()}
        for which, compiled in (("chunk", chunk), ("decode", decode)):
            text = compiled.as_text()
            for shape in (pool, plane):
                made = re.findall(rf"= {shape}\{{[^}}]*\}} ([\w\-]+)\(", text)
                # (an update slice is the write in place, inside a fusion;
                # the state's kernel is a custom call whose FIRST result,
                # the plane, comes out of its tuple by get-tuple-element)
                assert set(made) <= {"parameter", "scatter", "fusion",
                                     "bitcast", "get-tuple-element",
                                     "dynamic-update-slice"}, made
                assert not re.search(rf"= {shape}\{{[^}}]*\}} copy\(", text)
            # the chunk's rows, scores and MLP, never a copy of the pool
            # (0.27 GB here), of the plane (0.54 GB) or 512 rows of logits
        # (``causal_lm.CHUNK_LOGITS_ROW_BYTES``)
            assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9
            comp = None
            for line in text.splitlines():
                opened = re.match(r"^(ENTRY )?%?([\w\.\-]+) \(.*\{\s*$",
                                  line)
                if opened:
                    comp = opened.group(2)
                    continue
                if (comp is None or not line.startswith("  ")
                        or comp.startswith(("fused_computation", "region"))):
                    continue
                line = line.strip().removeprefix("ROOT ")
                if " parameter(" in line or " get-tuple-element(" in line:
                    continue
                name = trace_reduce.stable_name(SimpleNamespace(name=line))
                op_name = re.search(r'op_name="([^"]*)"', line)
                if not op_name:
                    continue
                for label, rx, scope in (
                        ("in_proj", in_proj, "ssm_in_proj|ssm_in"),
                        ("scan", scan, "ssm_chunk_scan|ssm_conv"),
                        ("update", update,
                         "ssm_state_update|dynamic_update_slice|scatter")):
                    if label == "scan" and which == "decode":
                        continue
                    if label == "update" and which == "chunk":
                        continue
                    if rx.search(name):
                        assert re.search(scope, op_name.group(1)), (
                            label, name, op_name.group(1))
                        found[which].add(label)
        assert found == {"decode": {"in_proj", "update"},
                         "chunk": {"in_proj", "scan"}}
        assert "_paged_decode_attention" in decode.as_text()
        # The state's kernel: one custom call a layer whose first result is
        # the plane, under the mixer's scope, by a stable name the metric's
        # reader takes (``args`` pass no ``kernel``: by SHAPE alone); the
        # decode program's temporaries stay far below a plane's 0.54 GB here
        # (17 MB at six layers)
        calls = [ln.strip().removeprefix("ROOT ")
                 for ln in decode.as_text().splitlines()
                 if re.search(rf"= \({plane}\{{[^}}]*\}}, f32\[{B},[\d,]*128\]"
                              r"\{[^}]*\}\) custom-call\(", ln)]
        assert len(calls) == layers, calls
        for line in calls:
            name = trace_reduce.stable_name(SimpleNamespace(name=line))
            assert name == f"_ssm_state_update_f32_{layers}_{B}_32_128_256_"
            assert update.search(name)
            assert "ssm_state_update" in re.search(
                r'op_name="([^"]*)"', line).group(1)
        assert "_ssm_state_update" not in chunk.as_text()
        assert decode.memory_analysis().temp_size_in_bytes < 0.1e9


class TestSelectionsOperationsInTheCompiledPrograms:
    """``sparse_select_dev_share_pct.batch`` finds the index scan and the
    top-k in a device trace by the HLO lines of the cell's programs (the
    TPU's trace carries no scope). Compiled here for the described chip at
    the cell's own shapes, the decode step and the widest chunk: with the
    selection ON each layer's k-th-key search is a loop the metric's
    ``loops`` takes whole and the scores are among its ``ops``; with the
    selection OFF (``index_topk`` as large as the cache: the scores are
    dead code) no loop is left and ``ops`` take no more than a staircase
    mask. Nothing runs: the trace of a chip run says what each costs."""

    @staticmethod
    def _taken(text, args):
        """(loops the metric takes whole, stable names its ``ops`` take
        outside them and outside fusions) of a compiled program's text."""
        import re

        from benchmark.trace_reduce import Event, stable_name

        comps, cur = {}, None
        for line in text.splitlines():
            head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
            if head:
                cur = comps.setdefault(head.group(1), [])
            elif cur is not None and line.startswith("  "):
                cur.append(line.strip().removeprefix("ROOT "))
        loop_rx = re.compile(args["loops"])
        loops = [ln for lines in comps.values() for ln in lines
                 if " while(" in ln and loop_rx.search(ln)]
        inside = set()
        for ln in loops:
            inside |= set(re.findall(r"(?:body|condition)=%?([\w.\-]+)", ln))
        ops_rx = [re.compile(p) for p in args["ops"]]
        names = set()
        for comp, lines in comps.items():
            if comp in inside or "fused_computation" in comp:
                continue
            for ln in lines:
                name = stable_name(Event(ln, 0.0, 1.0, {}))
                if any(rx.search(name) for rx in ops_rx):
                    names.add(name)
        return loops, names

    @pytest.mark.parametrize("selection", ["on", "off"])
    def test_the_metrics_patterns_take_the_selection_and_nothing_else(
            self, selection, one_chip, monkeypatch):
        import dataclasses
        import json
        import pathlib

        from jax.experimental.compilation_cache import compilation_cache

        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
        from ray_dynamic_batching_tpu.models.decoder import DecoderConfig

        root = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
        cfg = json.loads(
            (root / "configs" / "keye-vl2-30b-ep8-1chip.json").read_text())
        args = json.loads(
            (root / "layer_metrics" / "sparse_select_dev_share_pct.batch.json"
             ).read_text())["args"]
        llm = cfg["deployment"]["llm"]
        dc = DecoderConfig(**cfg["program"]["decoder_config"])
        if selection == "off":
            dc = dataclasses.replace(dc, index_topk=dc.max_seq_len)
        m = CausalLM(dc, name="m", dtype=jnp.bfloat16)
        B, ps = llm["num_slots"], llm["page_size"]
        W, NP = max(llm["prompt_buckets"]), llm["max_len"] // ps
        struct = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        placed = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda x: struct(x.shape, x.dtype), tree)
        cache = placed(jax.eval_shape(lambda: m.make_paged_cache(
            B, llm["kv_pool_pages"], ps, llm["max_len"])))
        # weights as served: bfloat16
        p = jax.tree_util.tree_map(
            lambda x: struct(x.shape, jnp.bfloat16),
            jax.eval_shape(m.init, jax.random.PRNGKey(0)))
        # the dispatchers ask the backend: take the chip's branches
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            decode = jax.jit(lambda *a: m.decode_step_paged(*a)).lower(
                p, struct((B, 1), jnp.int32), cache,
                struct((B,), jnp.bool_)).compile().as_text()
            chunk = jax.jit(lambda *a: m.prefill_chunk_paged(*a)).lower(
                p, struct((2, W), jnp.int32), struct((2, W), jnp.int32),
                cache, struct((2, NP), jnp.int32), struct((2,), jnp.int32),
                struct((2,), jnp.int32)).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        for text, scores in ((decode, f"fusion_f32_{B}_{NP * ps}_"),
                             (chunk, f"fusion_u32_2_{W}_{NP * ps}_")):
            loops, names = self._taken(text, args)
            if selection == "on":
                assert len(loops) == dc.num_layers
                assert scores in names
            else:
                assert not loops
                assert all("_pred_" in n for n in names), names


class TestRegisteredDecodersLowerForTPU:
    """Geometries discovered from the MODEL REGISTRY — not hand-picked
    shapes — so a new decoder family is covered the moment it registers.
    Decode steps, speculative windows, and prefill buckets must never
    RAISE on chip: engaging the kernel and declining to XLA are both
    legal outcomes here (the hand-pinned classes above assert which)."""

    def _geometries(self):
        from ray_dynamic_batching_tpu.models import registry  # noqa: F401
        from ray_dynamic_batching_tpu.models.base import (
            get_model, registered_models,
        )
        from ray_dynamic_batching_tpu.models.decoder import DecoderConfig

        geoms = {}
        for name in registered_models():
            cfg = getattr(get_model(name), "cfg", None)
            if isinstance(cfg, DecoderConfig):
                geoms[(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                       cfg.max_seq_len)] = name
        assert len(geoms) >= 3, f"registry discovery broke: {geoms}"
        return geoms

    def test_decode_and_spec_windows(self):
        for (N, K, H, max_len) in self._geometries():
            S = min(max_len, 4096)
            for Tq in (1, 5):
                _lower_decode(8, Tq, N, H, S, K, require_engaged=False)

    def test_prefill_buckets(self):
        for (N, K, H, max_len) in self._geometries():
            S = min(max_len, 2048)
            for Tq in (16, 64, 256):
                # fresh prefill (Tk == bucket) and chunked prefill into
                # the live cache (Tk == capacity, window mask)
                _lower_flash(1, Tq, N, H, Tq, K, causal=True,
                             require_engaged=False)
                _lower_flash(1, Tq, N, H, S, K, causal=True,
                             with_mask=True, require_engaged=False)


class TestDriverEntryLowersForTPU:
    def test_entry_program_lowers(self):
        """__graft_entry__.entry() is the program the round-end driver
        compile-checks ON THE REAL CHIP — it must lower for TPU from the
        CPU lane too, so a breakage is caught before the driver finds
        it."""
        import __graft_entry__ as graft

        fn, args = graft.entry()
        export.export(jax.jit(fn), platforms=["tpu"])(*args)


class TestFlashKernelLowersForTPU:
    def test_prefill_bucket(self):
        _lower_flash(1, 512, 16, 64, 512, 16)

    def test_chunked_prefill_window_mask(self):
        # chunked admission: query chunk attends into a longer cache
        # through an explicit window mask.
        _lower_flash(1, 128, 8, 64, 1024, 4, causal=True, with_mask=True)

    def test_gqa_wide_head(self):
        _lower_flash(2, 256, 8, 128, 256, 2)

    def test_vit_odd_sequence_declines(self):
        # ViT-shaped self-attention (197 = CLS + 14x14 patches, prime):
        # bf16's sublane-unaligned query tile trips a Mosaic verifier
        # bug (mixed-type vector.broadcast in the f32-preferred dot),
        # and any dtype's KV tiling degenerates to width-1 tiles — both
        # must decline to XLA, never emit the kernel.
        for dtype in (jnp.bfloat16, jnp.float32):
            q = jnp.zeros((4, 197, 12, 64), dtype)
            k = jnp.zeros((4, 197, 12, 64), dtype)
            assert fa.flash_attention(
                q, k, k, causal=False, interpret=False) is None

    def test_unaligned_long_sequence_finds_aligned_subtile(self):
        # Tq = Tk = 520 > the 512 target: the largest divisor (260) is
        # not sublane-aligned, but _pick_block must prefer the 8-aligned
        # 104 so bf16 stays on the kernel instead of declining.
        _lower_flash(2, 520, 8, 64, 520, 8, causal=True)

    def test_whisper_cross_attention(self):
        # decoder cross-attention into the 1500-frame encoder output.
        _lower_flash(2, 448, 20, 64, 1500, 20, causal=False)
