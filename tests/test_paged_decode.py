"""Paged KV decode — token-exactness + pool behavior.

The paged pool's contract is the tokens the model gives: logical KV
positions land in pages, the decode-mask window bounds attention, the
dequant rule reads int8 codes — so a seeded workload must be served
EXACTLY the tokens of a reference that shares no engine code
(``tests/decode_reference.py``: the model's full forward for f32, the
model's own slab ``KVCache`` for int8 KV), through the XLA gather
fallback AND through the CPU-interpreted Pallas page-table kernel
(ISSUE 7 acceptance; tier-1).

The tiny-model engine tests here stay un-marked (tier-1): llama_tiny
compiles in seconds and the paged plane is exactly the code the rest of
the PR stands on. The long-prompt CoW paths ride the `slow`
mark with the rest of the compile-heavy decode suites.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.decoder import (
    decode_mask,
    paged_window_mask,
)
from ray_dynamic_batching_tpu.models.kv_state import dequantize_kv
from ray_dynamic_batching_tpu.ops import decode_attention as da
from ray_dynamic_batching_tpu.ops.attention import (
    _xla_attention,
    set_attention_backend,
)

from tests.decode_reference import assert_served


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def lm_int8(lm):
    model = get_model("llama_tiny_int8kv", dtype=jnp.float32)
    # Same weights as the f32 fixture: only the cache dtype differs.
    return model, lm[1]


def _workload(queue, model_name, seed=7, n=6, sampled_row=True):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 30))
        payload = {
            "tokens": rng.integers(1, 500, plen).tolist(),
            "max_new_tokens": int(rng.integers(4, 12)),
        }
        if sampled_row and i == n - 1:
            # One sampled row keeps the per-request sampler (seeded) on
            # the exactness contract too — not just greedy argmax.
            payload.update(temperature=0.8, top_k=16, seed=123)
        req = Request(model=model_name, payload=payload, slo_ms=60_000.0)
        queue.add_request(req)
        reqs.append(req)
    return reqs


def _run(model, params, **kw):
    queue = RequestQueue(model.name, max_len=256)
    defaults = dict(
        num_slots=4, max_len=64, prompt_buckets=[8, 16], eos_token_id=None,
        default_max_new_tokens=8, decode_horizon=4, page_size=128,
    )
    defaults.update(kw)
    engine = DecodeEngine(model, params, queue, **defaults)
    reqs = _workload(queue, model.name)
    engine.run_until_idle(timeout_s=180)
    tokens = [tuple(r.future.result(timeout=5).tokens) for r in reqs]
    return tokens, engine, reqs


class TestTokenExactness:
    @pytest.mark.parametrize("case", ["f32", "int8_kv", "pallas_kernel"])
    def test_served_tokens_match_the_reference(self, case, lm, lm_int8):
        """The former slab-against-paged arms, each against a reference
        that shares no engine code: the model's full forward (f32, and
        the page-table Pallas kernel in CPU interpret mode — the fused
        gather is a pure layout change), the model's own int8 slab
        ``KVCache`` for the quantized pool."""
        model, params = lm_int8 if case == "int8_kv" else lm
        set_attention_backend("pallas" if case == "pallas_kernel"
                              else "auto")
        try:
            served, engine, reqs = _run(model, params)
        finally:
            set_attention_backend("auto")
        assert_served(model, params, reqs, served,
                      cached=case == "int8_kv")
        # Drained engine: every page either free or pinned by a cache
        # (none configured here -> all free), invariants intact.
        engine._allocator.check()
        assert engine._allocator.free_pages == engine.num_pages


class TestPagedKernel:
    def _pool(self, dtype, seed=0):
        rng = np.random.default_rng(seed)
        B, N, K, H, P, ps, NP = 3, 8, 4, 32, 10, 128, 2
        q = jnp.asarray(rng.standard_normal((B, 1, N, H)), jnp.float32)
        if dtype == jnp.int8:
            k = jnp.asarray(rng.integers(-127, 127, (P, ps, K, H)), jnp.int8)
            v = jnp.asarray(rng.integers(-127, 127, (P, ps, K, H)), jnp.int8)
            ks = jnp.asarray(rng.uniform(0.01, 0.1, (P, ps, K)), jnp.float32)
            vs = jnp.asarray(rng.uniform(0.01, 0.1, (P, ps, K)), jnp.float32)
        else:
            k = jnp.asarray(rng.standard_normal((P, ps, K, H)), jnp.float32)
            v = jnp.asarray(rng.standard_normal((P, ps, K, H)), jnp.float32)
            ks = vs = None
        # Slot 1 has one allocated page (sentinel tail), slot 2 a short
        # window — exercises clamping + in-kernel length masking.
        pt = jnp.asarray([[3, 7], [1, P], [5, 0]], jnp.int32)
        lens = jnp.asarray([200, 100, 37], jnp.int32)
        return q, k, v, ks, vs, pt, lens, (B, NP, ps, K, H, P)

    def _gather_ref(self, q, k, v, ks, vs, pt, lens, dims):
        B, NP, ps, K, H, P = dims
        safe = jnp.minimum(pt, P - 1)
        kg = k[safe].reshape(B, NP * ps, K, H)
        vg = v[safe].reshape(B, NP * ps, K, H)
        if ks is not None:
            kg = dequantize_kv(
                kg, ks[safe].reshape(B, NP * ps, K), jnp.float32)
            vg = dequantize_kv(
                vg, vs[safe].reshape(B, NP * ps, K), jnp.float32)
        return _xla_attention(
            q, kg, vg, causal=False, mask=decode_mask(lens, NP * ps),
            scale=None,
        )

    def test_kernel_matches_gather_f32(self):
        q, k, v, ks, vs, pt, lens, dims = self._pool(jnp.float32)
        out = da.paged_decode_attention(q, k, v, pt, lens, interpret=True)
        assert out is not None
        ref = self._gather_ref(q, k, v, ks, vs, pt, lens, dims)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-3, rtol=1e-3
        )

    def test_kernel_matches_gather_int8(self):
        q, k, v, ks, vs, pt, lens, dims = self._pool(jnp.int8)
        out = da.paged_decode_attention(
            q, k, v, pt, lens, k_scale=ks, v_scale=vs, interpret=True
        )
        assert out is not None
        ref = self._gather_ref(q, k, v, ks, vs, pt, lens, dims)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-2, rtol=1e-2
        )

    def test_kernel_declines_unaligned_page(self):
        q, k, v, _ks, _vs, pt, lens, _ = self._pool(jnp.float32)
        # 100-position pages are not lane-aligned: decline, don't lower.
        assert da.paged_decode_attention(
            q, k[:, :100], v[:, :100], pt, lens, interpret=True
        ) is None

    def test_small_window_runs_staircase(self):
        """Tq > 1 no longer declines (ISSUE 13): the spec-verify window
        runs through the kernel with STAIRCASE validity — row t attends
        <= lengths + t (tests/test_spec_paged.py pins the values; here
        only the accept/decline contract)."""
        q, k, v, _ks, _vs, pt, lens, _ = self._pool(jnp.float32)
        q2 = jnp.concatenate([q, q], axis=1)  # Tq == 2: spec window
        assert da.paged_decode_attention(
            q2, k, v, pt, lens, interpret=True
        ) is not None
        q9 = jnp.concatenate([q] * 9, axis=1)  # past the kernel band
        assert da.paged_decode_attention(
            q9, k, v, pt, lens, interpret=True
        ) is None



PS, NP_LIVE = 128, 3      # the liveness cases: 3 table entries of 128


def liveness_case(dtype, lengths, window, dead, seed=0):
    """A pool, a table and lengths for the paged kernel's length guard.
    Slot b holds real pages up to the last one a row of the ``window``
    attends (``(lengths[b] + window - 1) // PS``); its entries past that
    are ``dead``: ``"sentinel"`` (unallocated) or ``"nan"`` (allocated
    pages full of NaN — NaN scale planes for an int8 pool —, which a scan
    must neither read into its result nor multiply by zero). A length of
    -1 is an idle slot: length 0 on the device, every entry the sentinel.
    Returns the kernel's arguments and ``clean``, the same table with
    every dead entry pointed at a page of finite rows, for references
    that walk the whole table."""
    rng = np.random.default_rng(seed)
    B, N, K, H = len(lengths), 4, 2, 32
    P = B * NP_LIVE + 3           # + the NaN page, a zero page, page P - 1
    nan_page, zero_page = P - 3, P - 2
    q = jnp.asarray(rng.standard_normal((B, window, N, H)), jnp.bfloat16)
    shape = (P, PS, K, H)
    ks = vs = None
    if dtype == jnp.int8:
        k = rng.integers(-127, 127, shape).astype(np.int8)
        v = rng.integers(-127, 127, shape).astype(np.int8)
        ks = rng.uniform(0.01, 0.1, (P, PS, K)).astype(np.float32)
        vs = rng.uniform(0.01, 0.1, (P, PS, K)).astype(np.float32)
        ks[nan_page] = vs[nan_page] = np.nan
        ks, vs = jnp.asarray(ks), jnp.asarray(vs)
    else:
        k = rng.standard_normal(shape).astype(np.float32)
        v = rng.standard_normal(shape).astype(np.float32)
        k[nan_page] = v[nan_page] = np.nan
        k[zero_page] = v[zero_page] = 0.0
    table = np.full((B, NP_LIVE), P, np.int32)
    clean = np.full((B, NP_LIVE), zero_page, np.int32)
    for b, n in enumerate(lengths):
        if n < 0:
            continue
        live = min((n + window - 1) // PS + 1, NP_LIVE)
        table[b, :live] = clean[b, :live] = b * NP_LIVE + np.arange(live)
        if dead == "nan":
            table[b, live:] = nan_page
    lens = jnp.asarray(np.maximum(lengths, 0), jnp.int32)
    return (q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), ks, vs,
            jnp.asarray(table), lens, jnp.asarray(clean))


def walk_whole_table(q, k, v, ks, vs, clean, lens, *, kernel):
    """The gather path over ``clean``: each slot's logical rows rebuilt
    from its table, then attended under the staircase mask — through the
    slab kernel at one page a tile (``kernel=True``: the paged kernel's
    own arithmetic, run on EVERY tile), or through XLA's softmax."""
    B, NP = clean.shape
    g = lambda pool: pool[clean].reshape((B, NP * PS) + pool.shape[2:])
    win = paged_window_mask(lens, NP * PS, q.shape[1])
    if kernel:
        return da.decode_attention(
            q, g(k), g(v), mask=win, block_k=PS, interpret=True,
            k_scale=None if ks is None else g(ks),
            v_scale=None if vs is None else g(vs))
    kg, vg = g(k), g(v)
    if ks is not None:
        kg = dequantize_kv(kg, g(ks), q.dtype)
        vg = dequantize_kv(vg, g(vs), q.dtype)
    return _xla_attention(q, kg, vg, causal=False, mask=win, scale=None)


LIVENESS_LENGTHS = {
    "len0": [0, 0],
    "page_less_1": [PS - 1, PS - 1],
    "page": [PS, PS],
    "page_plus_1": [PS + 1, PS + 1],
    "capacity_less_1": [NP_LIVE * PS - 1, NP_LIVE * PS - 1],
    # with an idle slot (-1) and, at window 5, rows that cross a page
    # edge (PS - 3 + 4) beside rows that stop just short of one (PS - 5)
    "mixed": [PS + 1, -1, NP_LIVE * PS - 1, PS - 3, PS - 5, 2 * PS - 1],
}


class TestPagedKernelStopsAtTheLength:
    """A grid step whose page lies wholly past the slot's window does
    nothing (no copy, no arithmetic): the outputs of every consumed row
    equal, bit for bit, the same arithmetic run over the whole table
    (the slab kernel over the gathered rows, a page a tile), and XLA's
    softmax within bf16's tolerance; an idle slot's rows stay finite."""

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("window", [1, 5])
    @pytest.mark.parametrize("dead", ["sentinel", "nan"])
    @pytest.mark.parametrize("case", sorted(LIVENESS_LENGTHS))
    def test_consumed_rows_equal_the_whole_table_walk(
            self, case, dead, window, dtype):
        lengths = LIVENESS_LENGTHS[case]
        q, k, v, ks, vs, table, lens, clean = liveness_case(
            dtype, lengths, window, dead)
        out = da.paged_decode_attention(
            q, k, v, table, lens, k_scale=ks, v_scale=vs, interpret=True)
        assert out is not None
        out = np.asarray(out.astype(jnp.float32))
        assert np.isfinite(out).all()       # idle slots too
        used = [b for b, n in enumerate(lengths) if n >= 0]
        whole = walk_whole_table(q, k, v, ks, vs, clean, lens, kernel=True)
        np.testing.assert_array_equal(
            out[used], np.asarray(whole.astype(jnp.float32))[used])
        ref = np.asarray(walk_whole_table(
            q, k, v, ks, vs, clean, lens, kernel=False
        ).astype(jnp.float32))[used]
        # bf16's eight bits, on outputs as large as the int8 codes make them
        np.testing.assert_allclose(
            out[used], ref, atol=3e-2 * max(1.0, np.abs(ref).max()),
            rtol=3e-2)

    def test_entries_past_the_length_may_hold_anything(self):
        """The index maps stop at the last live page: the block a dead
        step names is the last live step's, whatever the table holds past
        the length (here: entries that index far past the pool)."""
        q, k, v, ks, vs, table, lens, clean = liveness_case(
            jnp.bfloat16, [PS + 1, 5], 1, "sentinel")
        wild = jnp.where(table == k.shape[0], 10_000, table)
        out = da.paged_decode_attention(q, k, v, wild, lens, interpret=True)
        whole = walk_whole_table(q, k, v, ks, vs, clean, lens, kernel=True)
        np.testing.assert_array_equal(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(whole.astype(jnp.float32)))


class TestPoolBehavior:
    def test_kv_occupancy_follows_allocated_pages(self, lm):
        """The decode slot-occupancy criterion, measured at the engine:
        mid-stream, the reserved KV is the allocated pages, so the useful
        fraction is higher than a reservation of num_slots x max_len
        would give on the SAME traffic."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        # max_len must exceed the page size for pages to be the FINER
        # reservation (the realistic serving geometry: 256+ positions a
        # slot, 128-position pages).
        engine = DecodeEngine(
            model, params, queue, num_slots=4, max_len=256,
            prompt_buckets=[8, 16], eos_token_id=None,
            default_max_new_tokens=32, decode_horizon=2, page_size=128,
        )
        rng = np.random.default_rng(11)
        reqs = []
        for _ in range(3):  # 3 of 4 slots live: an idle slot holds no page
            r = Request(model=model.name, payload={
                "tokens": rng.integers(1, 500, 6).tolist(),
                "max_new_tokens": 32,
            }, slo_ms=60_000.0)
            queue.add_request(r)
            reqs.append(r)
        engine._admit()
        engine._drain_prefill()
        for _ in range(4):
            engine._step(horizon=1)
        occ = engine.kv_occupancy()
        used = float(engine._len_host.sum())
        assert engine._allocator.allocated_pages == 3
        assert occ == used / (3 * 128)
        assert occ > used / (4 * 256)
        assert occ >= 0.05  # useful fraction of one 128-page/slot
        engine.run_until_idle(timeout_s=120)
        for r in reqs:
            r.future.result(timeout=5)

    def test_eos_frees_pages_mid_cycle(self, lm):
        """A finished stream's pages return to the free list inside the
        harvest (before the next admission), not at drain."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=64,
            prompt_buckets=[8], eos_token_id=None,
            default_max_new_tokens=3, decode_horizon=1,
            paged=True, page_size=128,
        )
        r = Request(model=model.name, payload={
            "tokens": [1, 2, 3], "max_new_tokens": 3,
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine._admit()
        engine._drain_prefill()  # chunked-universal: grants land here
        assert engine._allocator.allocated_pages == 1
        while not engine._slots[0].free:
            engine._step(horizon=1)
        # The finish happened inside _step's harvest; pages already free.
        assert engine._allocator.allocated_pages == 0
        assert r.future.result(timeout=5).finish_reason == "length"

    def test_page_starved_admission_requeues_and_drains(self, lm):
        """An over-subscribed pool (3 pages for 4 slots' worth of
        demand) admits what fits, requeues the rest, and drains as EOS
        frees pages — nobody is dropped, conservation holds."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=4, max_len=192,
            prompt_buckets=[8, 16], eos_token_id=None,
            default_max_new_tokens=5, decode_horizon=2,
            paged=True, page_size=128, kv_pool_pages=3,
        )
        rng = np.random.default_rng(5)
        reqs = []
        for _ in range(5):
            r = Request(model=model.name, payload={
                "tokens": rng.integers(1, 500, 10).tolist(),
                "max_new_tokens": 5,
            }, slo_ms=60_000.0)
            queue.add_request(r)
            reqs.append(r)
        engine.run_until_idle(timeout_s=120)
        results = [r.future.result(timeout=5) for r in reqs]
        assert all(len(x.tokens) == 5 for x in results)
        engine._allocator.check()
        assert engine._allocator.free_pages == 3

    def test_cache_pins_shed_under_pool_pressure(self, lm):
        """Review regression: a pool pinned by session-store entries
        must shed those pins to admit new work — not requeue-spin while
        capacity-finishing live streams. 2-page pool, 6 session-tagged
        requests: every finish pins a page; without LRU pin reclaim the
        3rd admission starves forever."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=128,
            prompt_buckets=[8], eos_token_id=None,
            default_max_new_tokens=4, decode_horizon=1,
            paged=True, page_size=128, kv_pool_pages=2,
            session_cache_size=8,
        )
        reqs = []
        for i in range(6):
            r = Request(model=model.name, payload={
                "tokens": [1 + i, 2, 3], "max_new_tokens": 4,
                "session_id": f"sess{i}",
            }, slo_ms=60_000.0)
            queue.add_request(r)
            reqs.append(r)
        engine.run_until_idle(timeout_s=120)
        results = [r.future.result(timeout=5) for r in reqs]
        assert all(x.finish_reason == "length" and len(x.tokens) == 4
                   for x in results)
        engine._allocator.check()

    def test_session_reservation_covers_only_the_tail(self, lm):
        """Review regression: a continuation whose history is cached
        must not demand the whole prompt's worth of free pages — with
        the history's page shared, a 1-page-free pool still admits."""
        from ray_dynamic_batching_tpu.engine.decode import SESSION_HITS

        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=256,
            prompt_buckets=[8, 16], eos_token_id=None,
            default_max_new_tokens=3, decode_horizon=1,
            paged=True, page_size=128, kv_pool_pages=2,
            session_cache_size=4,
        )
        # Turn 1: grows past one page (126 prompt + 3 generated = 129).
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, 500, 126).tolist()
        r1 = Request(model=model.name, payload={
            "tokens": prompt, "max_new_tokens": 3, "session_id": "t",
        }, slo_ms=60_000.0)
        queue.add_request(r1)
        engine.run_until_idle(timeout_s=180)
        t1 = r1.future.result(timeout=5).tokens
        # Stored turn (128-token history) pins one page; 1 page free.
        # Turn 2's prompt is 131 tokens (pages_for(132) = 2 total) but
        # shares the stored full page — the single free page suffices
        # iff the reservation covers only the non-shared tail.
        assert engine._allocator.free_pages == 1
        before = SESSION_HITS.get(tags={"model": model.name})
        r2 = Request(model=model.name, payload={
            "tokens": prompt + t1 + [9, 8], "max_new_tokens": 3,
            "session_id": "t",
        }, slo_ms=60_000.0)
        queue.add_request(r2)
        engine.run_until_idle(timeout_s=180)
        assert len(r2.future.result(timeout=5).tokens) == 3
        # The HIT path served it (a full-size reservation would have
        # starved, shed the pin, and re-admitted as a miss).
        assert SESSION_HITS.get(tags={"model": model.name}) == before + 1
        engine._allocator.check()

    def test_snapshot_surfaces_allocator_journal(self, lm):
        """ISSUE 8: the allocator event journal rides the engine's
        snapshot() — allocs/frees from a real decode run, page counts
        consistent with the allocator, and the journal renders into the
        same Chrome trace as the decode spans."""
        from ray_dynamic_batching_tpu.utils.trace_export import (
            to_chrome_trace,
        )

        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=64,
            prompt_buckets=[8], eos_token_id=None,
            default_max_new_tokens=3, decode_horizon=1,
            paged=True, page_size=128,
        )
        r = Request(model=model.name, payload={
            "tokens": [1, 2, 3], "max_new_tokens": 3,
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine.run_until_idle(timeout_s=120)
        r.future.result(timeout=5)
        snap = engine.snapshot()
        assert snap["paged"] is True and snap["model"] == model.name
        assert snap["free_pages"] == engine._allocator.free_pages
        journal = snap["page_journal"]
        kinds = [e["kind"] for e in journal["events"]]
        assert "alloc" in kinds and "free" in kinds
        assert journal["journal_total"] == len(journal["events"])
        assert journal["journal_rotated"] == 0
        # In-use gauge returns to zero after drain (free follows alloc).
        assert journal["events"][-1]["pages_in_use"] == 0
        doc = to_chrome_trace([], journal=journal["events"])
        assert any(e["ph"] == "C" for e in doc["traceEvents"])

    def test_paged_rejects_bad_config(self, lm):
        # (TP meshes no longer reject — ROADMAP item 2 shards the pool,
        # tests/test_tp_paged_decode.py — and neither do draft models:
        # ISSUE 13 lifts speculation onto the paged pool, pinned in
        # tests/test_spec_paged.py. Only paged+spec+MESH still raises.)
        model, params = lm
        queue = RequestQueue(model.name, max_len=16)
        with pytest.raises(ValueError, match="128-lane"):
            DecodeEngine(model, params, queue, paged=True, page_size=100)
        with pytest.raises(ValueError, match="cannot back"):
            DecodeEngine(model, params, queue, max_len=256, paged=True,
                         page_size=128, kv_pool_pages=1)


@pytest.mark.slow  # full serving stack build
class TestPagedServing:
    def test_llm_deployment_paged_roundtrip(self, lm):
        """serve/llm.py wiring: paged/page_size/kv_pool_pages reach the
        engine, and a request round-trips through replica + router."""
        from ray_dynamic_batching_tpu.serve.controller import (
            DeploymentConfig,
        )
        from ray_dynamic_batching_tpu.serve.handle import DeploymentHandle
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment
        from ray_dynamic_batching_tpu.serve.router import Router

        model, params = lm
        dep = LLMDeployment(
            "llama_tiny", model=model, params=params, num_slots=4,
            max_len=128, prompt_buckets=[16], warmup=False,
            paged=True, page_size=128,
        )
        replica = dep.make_replica(
            "llama_tiny#p", DeploymentConfig(name="llama_tiny"))
        replica.start()
        try:
            assert replica.engine.snapshot()["paged"] is True
            assert replica.engine.page_size == 128
            router = Router("llama_tiny", replicas=[replica])
            handle = DeploymentHandle(router, default_slo_ms=60_000.0)
            out = handle.remote(
                {"tokens": [3, 1, 4, 1, 5], "max_new_tokens": 4}
            ).result(timeout=60)
            assert len(out.tokens) == 4
        finally:
            replica.stop(timeout_s=2.0, drain=False)

    def test_paged_with_draft_accepted_at_deployment(self):
        """ISSUE 13: the deployment-level paged+draft rejection is
        lifted — speculation rides the paged pool (scratch pages +
        splice commits); only paged+spec+mesh still raises, at engine
        build (tests/test_spec_paged.py)."""
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

        dep = LLMDeployment("llama_tiny", paged=True,
                            draft_model_name="llama_tiny")
        assert dep.draft_model_name == "llama_tiny"


@pytest.mark.slow  # chunked-prefill paths compile several extra programs
class TestPagedCoW:
    """Copy-on-write sharing through the chunked admission paths: paged
    prefix (longest shared page-prefix, by reference) and session
    continuation (O(1) store pinning the finished turn's pages) must
    serve the tokens of the model-level reference AND leave the allocator
    conserved with only cache pins outstanding."""

    def _engines(self, lm, model=None, params=None):
        model_, params_ = lm
        model = model or model_
        params = params if params is not None else params_
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=4, max_len=192,
            prompt_buckets=[16, 32, 64, 128], eos_token_id=None,
            default_max_new_tokens=6, decode_horizon=4, page_size=128,
            prefix_cache_size=8, session_cache_size=4,
        )
        return engine, queue

    def _prompts(self):
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 500, n).tolist()
                   for n in (5, 40, 150, 160, 150, 20)]
        prompts[3][:128] = prompts[2][:128]  # shared 1-page prefix
        prompts[4] = list(prompts[2])        # identical long prompt
        return prompts

    def _run(self, engine, queue, model_name, prompts):
        reqs = []
        for i, p in enumerate(prompts):
            r = Request(model=model_name, payload={
                "tokens": p, "max_new_tokens": 6,
                "session_id": f"s{i % 2}" if i >= 4 else None,
            }, slo_ms=60_000.0)
            queue.add_request(r)
            reqs.append(r)
        engine.run_until_idle(timeout_s=300)
        return [tuple(r.future.result(timeout=5).tokens)
                for r in reqs], reqs

    def test_long_prefix_session_exact_and_conserved(self, lm):
        from ray_dynamic_batching_tpu.engine.decode import PREFIX_HITS

        model, params = lm
        prompts = self._prompts()
        before = PREFIX_HITS.get(
            tags={"model": model.name, "granularity": "page"})
        e_paged, q_paged = self._engines(lm)
        served, reqs = self._run(e_paged, q_paged, model.name, prompts)
        assert_served(model, params, reqs, served)
        # The shared 128-token head actually shared: page-granular hits
        # fired (prompts 3 and 4 reuse prompt 2's first page).
        after = PREFIX_HITS.get(
            tags={"model": model.name, "granularity": "page"})
        assert after - before >= 2
        # Conservation with live cache pins: every non-free page is
        # pinned by the prefix/session caches, none by slots.
        e_paged._allocator.check()
        assert all(s.free for s in e_paged._slots)
        pinned = e_paged._allocator.allocated_pages
        assert pinned > 0  # caches hold the published prefixes/turns
        e_paged.paged_prefix.clear()
        e_paged.paged_sessions.clear()
        assert e_paged._allocator.free_pages == e_paged.num_pages

    def test_int8_long_paths_exact(self, lm):
        model8 = get_model("llama_tiny_int8kv", dtype=jnp.float32)
        params = lm[1]
        prompts = self._prompts()
        engine, queue = self._engines(lm, model8, params)
        served, reqs = self._run(engine, queue, model8.name, prompts)
        assert_served(model8, params, reqs, served, cached=True)

    def test_session_store_is_by_reference(self, lm):
        """A finished session turn pins the slot's pages instead of
        copying a row: the stored entry's page ids are exactly the
        pages the slot held."""
        model, _ = lm
        engine, queue = self._engines(lm)
        rng = np.random.default_rng(9)
        prompt = rng.integers(1, 500, 140).tolist()
        r = Request(model=model.name, payload={
            "tokens": prompt, "max_new_tokens": 4, "session_id": "ref",
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine.run_until_idle(timeout_s=300)
        turn1 = r.future.result(timeout=5).tokens
        # Turn 2 resends the whole conversation (prompt + assistant
        # tokens) plus the new user message — the stored history must
        # strictly prefix it.
        turn2_prompt = prompt + turn1 + [7, 8, 9]
        entry = engine.paged_sessions.lookup(
            "ref", np.asarray(turn2_prompt, np.int32)
        )
        assert entry is not None
        pages, stored_len = entry
        assert stored_len == 140 + 4 - 1  # prompt + generated[:-1]
        for p in pages:
            assert engine._allocator.refcount[p] >= 1
        # Turn 2 continues from the stored pages (session-hit path) and
        # borrows the full page by reference.
        r2 = Request(model=model.name, payload={
            "tokens": turn2_prompt, "max_new_tokens": 4,
            "session_id": "ref",
        }, slo_ms=60_000.0)
        queue.add_request(r2)
        engine.run_until_idle(timeout_s=300)
        assert len(r2.future.result(timeout=5).tokens) == 4
        engine._allocator.check()
