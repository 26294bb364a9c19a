"""Token-budgeted chunked prefill interleaved with paged decode
(ISSUE 15 tentpole acceptance; tier-1).

Two contracts:

- **Token exactness**: chunked-interleaved admission (every admission is
  a chunk train) is served EXACTLY the tokens of a reference that shares
  no engine code (``tests/decode_reference.py``) — f32 + int8-KV, greedy
  + the seeded sampled row, XLA fallback + CPU-interpreted Pallas kernel,
  and the chunked+spec / chunked+mesh compositions. Pages-direct chunk
  k/v (scatter through the slot's page table, no row cache, no commit
  copy) is a pure layout/scheduling change.

- **Stall bound**: with budget B, the engine's own step loop spends at
  most B prefill tokens between decode turns — under a saturating
  long-prompt burst, no active stream ever waits more than one chunk
  program (the budget's worth) between its turns.
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
from ray_dynamic_batching_tpu.ops.attention import set_attention_backend

from tests.decode_reference import assert_served
from tests.test_paged_decode import _workload


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def lm_int8(lm):
    model = get_model("llama_tiny_int8kv", dtype=jnp.float32)
    return model, lm[1]


def _run(model, params, *, queue_reqs=None, **kw):
    queue = RequestQueue(model.name, max_len=256)
    defaults = dict(
        num_slots=4, max_len=96, prompt_buckets=[8, 16, 32],
        eos_token_id=None, default_max_new_tokens=8, decode_horizon=4,
        page_size=128,
    )
    defaults.update(kw)
    engine = DecodeEngine(model, params, queue, **defaults)
    if queue_reqs is not None:
        reqs = queue_reqs(queue, model.name)
    else:
        reqs = _workload(queue, model.name)
    engine.run_until_idle(timeout_s=300)
    tokens = [tuple(r.future.result(timeout=5).tokens) for r in reqs]
    engine._allocator.check()
    return tokens, engine, reqs


def _mixed_workload(queue, model_name, seed=3):
    """Short bucketed + long (over-bucket, multi-chunk) prompts, greedy
    plus one seeded sampled row — every admission shape in one pass."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, plen in enumerate((5, 17, 70, 88, 30, 12)):
        payload = {
            "tokens": rng.integers(1, 500, plen).tolist(),
            "max_new_tokens": int(rng.integers(4, 10)),
        }
        if i == 4:
            payload.update(temperature=0.7, top_k=12, seed=99)
        req = Request(model=model_name, payload=payload, slo_ms=60_000.0)
        queue.add_request(req)
        reqs.append(req)
    return reqs


class TestTokenExactness:
    @pytest.mark.parametrize("case", [
        "f32", "f32_standard_workload",
        pytest.param("int8_kv", marks=pytest.mark.slow),
        pytest.param("pallas_kernel", marks=pytest.mark.slow),
        pytest.param("spec", marks=pytest.mark.slow),
        pytest.param("mesh", marks=pytest.mark.slow),
    ])
    def test_served_tokens_match_the_reference(self, case, lm, lm_int8,
                                               request):
        """THE acceptance pin, the former arms as cases: short bucketed
        prompts (single-chunk trains), long multi-chunk trains, greedy
        and the seeded sampled row are served the tokens of the
        model-level reference. ``int8_kv``: chunk writes quantize per
        row at the pool write (reference: the model's own int8 slab
        ``KVCache``). ``pallas_kernel``: decode turns ride the
        page-table kernel (CPU interpret) while wide chunk windows
        decline to the gather. ``spec``: a self-draft (acceptance 1.0)
        replays the prompt through its own chunk program after the
        target's final chunk. ``mesh``: the chunk program's scatter and
        staircase gather partition under GSPMD over the TP=2 pool. The
        last two also equal the same engine without the draft / on one
        device."""
        model, params = lm_int8 if case == "int8_kv" else lm
        workload = None if case == "f32_standard_workload" \
            else _mixed_workload
        kw = {}
        if case == "spec":
            kw = dict(draft_model=model, draft_params=params,
                      spec_tokens=3)
        elif case == "mesh":
            from ray_dynamic_batching_tpu.parallel.mesh import (
                MeshConfig,
                build_mesh,
            )

            request.getfixturevalue("eight_devices")
            kw = dict(mesh=build_mesh(MeshConfig(tp=2),
                                      jax.devices()[:2]))
        set_attention_backend("pallas" if case == "pallas_kernel"
                              else "auto")
        try:
            served, engine, reqs = _run(model, params,
                                        queue_reqs=workload, **kw)
        finally:
            set_attention_backend("auto")
        assert_served(model, params, reqs, served,
                      cached=case == "int8_kv")
        # Drained chunked engine returns every page (per-chunk grants
        # all transferred to slots and freed at finish).
        assert engine._allocator.free_pages == engine.num_pages
        if kw:
            plain, _, _ = _run(model, params, queue_reqs=workload)
            assert served == plain

    @pytest.mark.slow
    def test_session_continuation_chunked(self, lm):
        """Paged chunked session continuation: the borrow floors to a
        page boundary and the train recomputes the partial boundary
        positions — turn-2 tokens match a fresh no-cache engine fed the
        same concatenated history."""
        model, params = lm

        def turns(session_cache_size):
            queue = RequestQueue(model.name, max_len=256)
            engine = DecodeEngine(
                model, params, queue, num_slots=2, max_len=160,
                prompt_buckets=[16], eos_token_id=None,
                default_max_new_tokens=6, decode_horizon=2,
                page_size=128, session_cache_size=session_cache_size,
            )
            rng = np.random.default_rng(5)
            t1 = rng.integers(1, 500, 40).tolist()
            r1 = Request(model=model.name, payload={
                "tokens": t1, "max_new_tokens": 6,
                "session_id": "s1",
            }, slo_ms=60_000.0)
            queue.add_request(r1)
            engine.run_until_idle(timeout_s=300)
            out1 = r1.future.result(timeout=5).tokens
            t2 = t1 + out1[:-1] + rng.integers(1, 500, 9).tolist()
            r2 = Request(model=model.name, payload={
                "tokens": t2, "max_new_tokens": 6,
                "session_id": "s1",
            }, slo_ms=60_000.0)
            queue.add_request(r2)
            engine.run_until_idle(timeout_s=300)
            out2 = r2.future.result(timeout=5).tokens
            assert_served(model, params, [r1, r2], [out1, out2])
            return tuple(out1), tuple(out2), engine

        o1_hit, o2_hit, engine = turns(4)
        o1_cold, o2_cold, _ = turns(0)
        assert (o1_hit, o2_hit) == (o1_cold, o2_cold)
        from ray_dynamic_batching_tpu.engine.decode import SESSION_HITS

        assert SESSION_HITS.get(tags={"model": model.name}) >= 1

    def test_prefix_cow_chunked(self, lm):
        """Two long prompts sharing a >1-page head: the second train
        borrows the published pages by reference (CoW) and still emits
        the tokens a cold engine would."""
        model, params = lm

        def run(prefix_cache_size):
            queue = RequestQueue(model.name, max_len=256)
            engine = DecodeEngine(
                model, params, queue, num_slots=2, max_len=224,
                prompt_buckets=[16], eos_token_id=None,
                default_max_new_tokens=5, decode_horizon=2,
                page_size=128, prefix_cache_size=prefix_cache_size,
            )
            rng = np.random.default_rng(9)
            head = rng.integers(1, 500, 130).tolist()  # > one page
            outs = []
            for tail_seed in (1, 2):
                tail = np.random.default_rng(tail_seed).integers(
                    1, 500, 7
                ).tolist()
                r = Request(model=model.name, payload={
                    "tokens": head + tail, "max_new_tokens": 5,
                }, slo_ms=60_000.0)
                queue.add_request(r)
                engine.run_until_idle(timeout_s=300)
                outs.append(tuple(r.future.result(timeout=5).tokens))
                assert_served(model, params, [r], outs[-1:])
            return outs, engine

        cold, _ = run(0)
        warm, engine = run(4)
        assert warm == cold
        from ray_dynamic_batching_tpu.engine.decode import PREFIX_HITS

        assert PREFIX_HITS.get(
            tags={"model": model.name, "granularity": "page"}
        ) >= 1


class TestStallBound:
    def test_budget_bounds_chunks_between_turns(self, lm):
        """Under a saturating long-prompt burst with one long-lived
        active stream, the engine's turn ring shows at most
        ``prefill_token_budget`` chunk tokens between consecutive decode
        turns — no serial prefill train, ever."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=6, max_len=96,
            prompt_buckets=[8, 16], eos_token_id=None,
            default_max_new_tokens=48, decode_horizon=4,
            page_size=128,
        )
        budget = engine.prefill_token_budget
        rng = np.random.default_rng(2)
        # One short request first: it registers and stays decoding
        # through the whole burst (48 new tokens).
        live = Request(model=model.name, payload={
            "tokens": rng.integers(1, 500, 4).tolist(),
            "max_new_tokens": 48,
        }, slo_ms=60_000.0)
        queue.add_request(live)
        engine._admit()
        engine._drain_prefill()
        assert engine.active_slots == 1
        engine.reset_ttft_window()
        burst = []
        for _ in range(4):
            r = Request(model=model.name, payload={
                "tokens": rng.integers(1, 500, 80).tolist(),  # 5 chunks
                "max_new_tokens": 4,
            }, slo_ms=60_000.0)
            queue.add_request(r)
            burst.append(r)
        engine.run_until_idle(timeout_s=300)
        for r in burst + [live]:
            r.future.result(timeout=5)
        log = list(engine.turns)
        assert any(t.kind == "chunk" for t in log)
        # Between consecutive turns, chunk tokens never exceed the
        # budget while a stream was active (the whole ring here: the
        # live stream outlasts the burst).
        since_turn = 0
        for t in log:
            if t.kind == "turn":
                since_turn = 0
            else:
                since_turn += t.tokens
                assert since_turn <= budget, log

    def test_budget_clamps_to_chunk_width(self, lm):
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=96,
            prompt_buckets=[8, 32],
            prefill_token_budget=4,  # below one chunk: clamped up
        )
        assert engine.prefill_token_budget == 32

    def test_trains_force_single_step_turns(self, lm):
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=96,
            prompt_buckets=[8], decode_horizon=8,
        )
        assert engine._pick_horizon() in (engine.ttft_horizon, 1)
        engine._trains.append(object())  # sentinel: a pending train
        try:
            assert engine._pick_horizon() == 1
        finally:
            engine._trains.clear()


class TestTrainLifecycle:
    def test_page_starved_trains_park_then_drain(self, lm):
        """An over-subscribed pool: trains park on grant failure (no
        live stream is ever evicted for an admission) and drain as EOS
        frees pages — conservation holds, nobody drops."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=4, max_len=192,
            prompt_buckets=[16], eos_token_id=None,
            default_max_new_tokens=4, decode_horizon=1,
            page_size=128, kv_pool_pages=3,
        )
        rng = np.random.default_rng(4)
        reqs = []
        for _ in range(5):
            r = Request(model=model.name, payload={
                "tokens": rng.integers(1, 500, 10).tolist(),
                "max_new_tokens": 4,
            }, slo_ms=60_000.0)
            queue.add_request(r)
            reqs.append(r)
        engine.run_until_idle(timeout_s=300)
        for r in reqs:
            assert r.future.result(timeout=5).tokens
        engine._allocator.check()
        assert engine._allocator.free_pages == engine.num_pages

    def test_abort_rejects_pending_trains(self, lm):
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=96,
            prompt_buckets=[8],
        )
        r = Request(model=model.name, payload={
            "tokens": [1, 2, 3], "max_new_tokens": 4,
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine._admit()   # train parked, nothing dispatched yet
        assert engine.busy
        engine.abort_active(RuntimeError("shutdown"))
        with pytest.raises(RuntimeError):
            r.future.result(timeout=5)
        assert not engine._trains
        assert engine._allocator.free_pages == engine.num_pages

    def test_snapshot_carries_prefill_block(self, lm):
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=96,
            prompt_buckets=[8],
        )
        snap = engine.snapshot()
        assert snap["prefill"]["mode"] == "chunked"
        assert snap["prefill"]["token_budget"] == \
            engine.prefill_token_budget
        assert snap["prefill"]["pending_trains"] == 0
