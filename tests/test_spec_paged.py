"""Speculative decoding over the paged KV engine (ISSUE 13, tier-1).

The contract is threefold:

- **Token-exactness**: the spec engine serves the greedy tokens of the
  model-level reference (``tests/decode_reference.py``) AND of the same
  engine without the draft on the same prompts (f32 and int8-KV, XLA
  fallback and CPU-interpreted Pallas kernel) — speculation is a pure
  latency transform, never a sampling one.
- **Splice semantics**: accepted prefixes commit by PAGE-TABLE SPLICE
  (scratch pages re-pointed into the slot's table, zero KV bytes copied
  — the journal shows ``spec_commit`` and no ``cow_copy`` on the accept
  path), rejected tails free back to the pool (``spec_reject``), and
  the allocator conserves through arbitrary accept/reject interleaving.
- **Observability conservation**: accepted + rejected == drafted per
  round, pinned from the live counters; the acceptance gauge tracks the
  rolling rate (1.0 under a self-draft, ~0 under a divergent one).

The tiny-model engine tests stay un-marked (tier-1), like the rest of
the paged plane.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_dynamic_batching_tpu.engine.decode import (
    DecodeEngine,
    SPEC_ACCEPTED,
    SPEC_DRAFTED,
    SPEC_REJECTED,
    SPEC_ACCEPTANCE,
)
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.decoder import paged_window_mask
from ray_dynamic_batching_tpu.models.kv_state import dequantize_kv
from ray_dynamic_batching_tpu.ops import decode_attention as da
from ray_dynamic_batching_tpu.ops.attention import (
    _xla_attention,
    set_attention_backend,
)
from ray_dynamic_batching_tpu.ops.tile_math import spec_scratch_pages

from tests.decode_reference import assert_served


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def lm_int8(lm):
    model = get_model("llama_tiny_int8kv", dtype=jnp.float32)
    # Same weights as the f32 fixture: only the cache dtype differs, so
    # comparisons isolate the paging + speculation changes.
    return model, lm[1]


@pytest.fixture(scope="module")
def draft_lm():
    """A DIFFERENT tiny model as the draft: random-init weights disagree
    with the target's greedy choices, so acceptance sits near zero —
    the adversarial arm that proves exactness never depends on the
    draft being right."""
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(7))
    return model, params


def _workload(queue, model_name, seed=7, n=6):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(3, 30))
        r = Request(model=model_name, payload={
            "tokens": rng.integers(1, 500, plen).tolist(),
            "max_new_tokens": int(rng.integers(4, 12)),
        }, slo_ms=60_000.0)
        queue.add_request(r)
        reqs.append(r)
    return reqs


def _run(model, params, *, draft=None, **kw):
    queue = RequestQueue(model.name, max_len=256)
    defaults = dict(
        num_slots=4, max_len=64, prompt_buckets=[8, 16], eos_token_id=None,
        default_max_new_tokens=8, decode_horizon=4, page_size=128,
    )
    if draft is not None:
        dmodel, dparams = draft
        defaults.update(draft_model=dmodel, draft_params=dparams,
                        spec_tokens=3)
    defaults.update(kw)
    engine = DecodeEngine(model, params, queue, **defaults)
    reqs = _workload(queue, model.name)
    engine.run_until_idle(timeout_s=300)
    tokens = [tuple(r.future.result(timeout=5).tokens) for r in reqs]
    return tokens, engine, reqs


class TestTokenExactness:
    @pytest.mark.parametrize("case", ["f32", "int8_kv"])
    def test_spec_serves_the_reference_and_the_plain_engines_tokens(
            self, case, lm, lm_int8, draft_lm):
        """The ISSUE 13 acceptance pin: the same prompts through the spec
        engine and the same engine without the draft give one token
        stream, the model-level reference's — with a DIVERGENT draft so
        partial acceptance is exercised."""
        model, params = lm_int8 if case == "int8_kv" else lm
        plain, _, _ = _run(model, params)
        spec, engine, reqs = _run(model, params, draft=draft_lm)
        assert spec == plain
        assert_served(model, params, reqs, spec,
                      cached=case == "int8_kv")
        engine._allocator.check()
        assert engine._allocator.free_pages == engine.num_pages

    def test_paged_spec_pallas_kernel_matches_xla(self, lm, draft_lm):
        """The staircase paged kernel (CPU interpret mode) must emit the
        same tokens as the XLA gather fallback — the fused verify window
        is a pure layout change."""
        model, params = lm
        set_attention_backend("pallas")
        try:
            kernel_toks, _, _ = _run(model, params, draft=draft_lm)
        finally:
            set_attention_backend("auto")
        xla_toks, _, _ = _run(model, params, draft=draft_lm)
        assert kernel_toks == xla_toks

    def test_paged_spec_pallas_kernel_int8(self, lm_int8, draft_lm):
        model, params = lm_int8
        set_attention_backend("pallas")
        try:
            kernel_toks, _, _ = _run(model, params, draft=draft_lm)
        finally:
            set_attention_backend("auto")
        xla_toks, _, _ = _run(model, params, draft=draft_lm)
        assert kernel_toks == xla_toks

    def test_self_draft_accepts_everything_paged(self, lm):
        """draft == target on the paged pool: every proposal verifies,
        each round lands spec_tokens+1 tokens, and the acceptance gauge
        reads 1.0."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=64,
            prompt_buckets=[8], eos_token_id=None, paged=True,
            page_size=128, draft_model=model, draft_params=params,
            spec_tokens=3,
        )
        r = Request(model=model.name, payload={
            "tokens": [1, 2, 3], "max_new_tokens": 12,
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine.run_until_idle(timeout_s=120)
        assert len(r.future.result(timeout=5).tokens) == 12
        # 12 tokens: 1 from prefill + rounds of 4 -> 3 spec rounds.
        assert engine.steps == 3
        assert engine.spec_acceptance() == 1.0


class TestSpliceSemantics:
    def _long_run(self, lm, draft, max_new=24):
        model, params = lm
        dmodel, dparams = draft
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=192,
            prompt_buckets=[128], eos_token_id=None,
            default_max_new_tokens=max_new, decode_horizon=4,
            paged=True, page_size=128,
            draft_model=dmodel, draft_params=dparams, spec_tokens=3,
        )
        rng = np.random.default_rng(3)
        r = Request(model=model.name, payload={
            "tokens": rng.integers(1, 500, 120).tolist(),
            "max_new_tokens": max_new,
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine.run_until_idle(timeout_s=300)
        toks = r.future.result(timeout=5).tokens
        kinds = [ev["kind"] for ev in engine._page_journal.snapshot()]
        return toks, kinds, engine

    def test_accept_path_splices_without_copy(self, lm):
        """A generation crossing a page boundary under a self-draft
        (everything accepted): the scratch page commits by table splice
        — the journal shows ``spec_commit`` re-pointing and ZERO
        ``cow_copy`` on the accept path — and the allocator conserves."""
        toks, kinds, engine = self._long_run(lm, lm)
        assert len(toks) == 24
        assert "spec_commit" in kinds
        assert "cow_copy" not in kinds
        assert "spec_reject" not in kinds  # nothing to reject at alpha=1
        engine._allocator.check()
        assert engine._allocator.free_pages == engine.num_pages

    def test_reject_path_frees_scratch(self, lm, draft_lm):
        """A divergent draft near a page boundary: rejected tails free
        back to the pool (``spec_reject``), tokens stay exact vs the
        self-draft run, and nothing leaks."""
        exact, _, _ = self._long_run(lm, lm)
        toks, kinds, engine = self._long_run(lm, draft_lm)
        assert toks == exact  # greedy-exact regardless of the draft
        assert "spec_reject" in kinds
        engine._allocator.check()
        assert engine._allocator.free_pages == engine.num_pages

    def test_counter_conservation_accepted_plus_rejected_is_drafted(
        self, lm, draft_lm
    ):
        """accepted + rejected == drafted, pinned from the LIVE counters
        across a real multi-slot run (the ISSUE 13 observability
        satellite)."""
        model, _ = lm
        tags = {"model": model.name, "paged": "true"}
        before = (SPEC_ACCEPTED.get(tags=tags), SPEC_REJECTED.get(tags=tags),
                  SPEC_DRAFTED.get(tags=tags))
        _run(model, lm[1], draft=draft_lm)
        a = SPEC_ACCEPTED.get(tags=tags) - before[0]
        rj = SPEC_REJECTED.get(tags=tags) - before[1]
        d = SPEC_DRAFTED.get(tags=tags) - before[2]
        assert d > 0
        assert a + rj == d
        # The gauge reflects the engine's rolling window.
        assert 0.0 <= SPEC_ACCEPTANCE.get(tags=tags) <= 1.0

    def test_pool_pressure_degrades_to_plain_rounds(self, lm):
        """A pool too tight for a verify window falls back to PLAIN
        paged steps — the round is skipped, not the stream. With the
        pool's second page held externally (an unreclaimable pin), the
        spec reserve starts failing at len >= 125 (window 4 would cross
        the page boundary), yet the stream keeps emitting through the
        fallback until the PLAIN path's own boundary — the same
        capacity-finish a non-spec engine hits — never an error, never a
        hang, and the round bookkeeping leaks nothing."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=192,
            prompt_buckets=[128], eos_token_id=None,
            default_max_new_tokens=40, decode_horizon=1,
            paged=True, page_size=128, kv_pool_pages=2,
            draft_model=model, draft_params=params, spec_tokens=3,
        )
        held = engine._allocator.alloc(1)  # the pool's other page
        rng = np.random.default_rng(5)
        r = Request(model=model.name, payload={
            "tokens": rng.integers(1, 500, 120).tolist(),
            "max_new_tokens": 40,
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine.run_until_idle(timeout_s=300)
        result = r.future.result(timeout=5)
        # Page 1 covers positions < 128; registration leaves len == 121.
        # Spec reserve fails from len 125, so reaching the plain bound
        # proves plain-fallback rounds kept the stream alive.
        assert result.finish_reason == "capacity"
        assert len(result.tokens) >= 5
        assert not engine._spec_scratch  # no round left in flight
        engine._allocator.decref(held)
        engine._allocator.check()
        assert engine._allocator.free_pages == 2

    def test_admission_reserves_spec_window_headroom(self, lm):
        """The ISSUE 13 admission rule — pages_for(len + spec_tokens +
        1), THE shared spec_scratch_pages rule with len = prompt size
        (the pending first token is row 0 OF the window): a 126-token
        prompt on a 128-page spec engine takes TWO pages at admission
        (126+4 crosses the boundary) where a plain engine takes one,
        while a 124-token prompt takes exactly ONE (124+4 == 128 — the
        review-caught off-by-one would have demanded two)."""
        model, params = lm
        for spec, plen, expect in ((False, 126, 1), (True, 126, 2),
                                   (True, 124, 1)):
            queue = RequestQueue(model.name, max_len=256)
            kw = dict(num_slots=2, max_len=192, prompt_buckets=[128],
                      eos_token_id=None, default_max_new_tokens=4,
                      decode_horizon=1, paged=True, page_size=128)
            if spec:
                kw.update(draft_model=model, draft_params=params,
                          spec_tokens=3)
            engine = DecodeEngine(model, params, queue, **kw)
            r = Request(model=model.name, payload={
                "tokens": list(range(1, plen + 1)), "max_new_tokens": 4,
            }, slo_ms=60_000.0)
            queue.add_request(r)
            engine._admit()
            engine._drain_prefill()  # chunked-universal: grants land here
            assert engine._allocator.allocated_pages == expect, (
                spec, plen)
            engine.run_until_idle(timeout_s=120)
            r.future.result(timeout=5)

    def test_crashed_dispatch_rolls_scratch_back_immediately(self, lm):
        """Review regression: a spec dispatch that raises must resolve
        the round's scratch ON the error path — speculation may never
        run again (a sampled row pins _use_spec() False), and stranded
        scratch would shadow-occupy the pool for the engine's
        lifetime."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=256,
            prompt_buckets=[128], eos_token_id=None,
            default_max_new_tokens=8, decode_horizon=1,
            paged=True, page_size=128,
            draft_model=model, draft_params=params, spec_tokens=3,
        )
        r = Request(model=model.name, payload={
            "tokens": list(range(1, 125)), "max_new_tokens": 8,
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine._admit()
        engine._drain_prefill()
        engine._len_host[0] = 126  # window crosses -> scratch needed
        allocated_before = engine._allocator.allocated_pages

        def boom(*a, **k):
            raise RuntimeError("injected dispatch failure")

        real_fn = engine._spec_fn
        engine._spec_fn = boom
        with pytest.raises(RuntimeError, match="injected"):
            engine._spec_step()
        engine._spec_fn = real_fn
        # Scratch resolved on the error path: nothing in flight, no
        # extra pages held, table row rebuilt from the slot's own run.
        assert not engine._spec_scratch
        assert engine._allocator.allocated_pages == allocated_before
        engine._allocator.check()
        engine._len_host[0] = 124
        engine.run_until_idle(timeout_s=120)
        r.future.result(timeout=5)

    def test_stale_scratch_rollback_rebuilds_table_row(self, lm):
        """Review regression: a round that dies between reserve and
        splice leaves scratch behind; if the slot's table row is then
        legitimately rewritten (plain-step headroom growth), the
        deferred rollback must REBUILD the row from the slot's owned
        pages — blind sentinels over the recorded span would void the
        occupant's later KV writes and silently corrupt its stream."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=256,
            prompt_buckets=[128], eos_token_id=None,
            default_max_new_tokens=8, decode_horizon=1,
            paged=True, page_size=128,
            draft_model=model, draft_params=params, spec_tokens=3,
        )
        r = Request(model=model.name, payload={
            "tokens": list(range(1, 125)), "max_new_tokens": 8,
        }, slo_ms=60_000.0)
        queue.add_request(r)
        engine._admit()  # len 124: one page covers the first window
        engine._drain_prefill()
        # Arm a round whose window crosses into page 2 -> 1 scratch page.
        engine._len_host[0] = 126
        assert engine._reserve_spec_scratch()
        assert engine._spec_scratch  # scratch armed, round "dies" here
        # The slot legitimately grows its own page 2 (plain-step path).
        grown = engine._allocator.alloc(1)
        engine._slots[0].pages.extend(grown)
        from ray_dynamic_batching_tpu.engine.paging import table_array
        engine._table_host[0] = table_array(
            engine._slots[0].pages, engine._n_table_entries,
            engine.num_pages,
        )
        # The next spec round's stale rollback must keep the grown page.
        engine._rollback_spec_scratch()
        assert engine._table_host[0, 1] == grown[0]  # NOT the sentinel
        engine._allocator.check()
        # Clean teardown: drop the synthetic state and drain.
        engine._len_host[0] = 124
        engine.run_until_idle(timeout_s=120)
        r.future.result(timeout=5)
        engine._allocator.check()


class TestExclusions:
    def test_paged_spec_mesh_raises_loudly(self, lm, draft_lm):
        from ray_dynamic_batching_tpu.parallel.mesh import (
            MeshConfig,
            build_mesh,
        )

        model, params = lm
        mesh = build_mesh(MeshConfig(tp=2), jax.devices()[:2])
        queue = RequestQueue(model.name, max_len=16)
        with pytest.raises(ValueError, match="TP-mesh paged pool"):
            DecodeEngine(
                model, params, queue, paged=True, mesh=mesh,
                draft_model=draft_lm[0], draft_params=draft_lm[1],
            )

    def test_paged_with_draft_constructs(self, lm):
        """The PR 7 exclusion is LIFTED: paged + draft builds (the old
        raise would have fired in __init__ before any compile)."""
        model, params = lm
        queue = RequestQueue(model.name, max_len=16)
        engine = DecodeEngine(
            model, params, queue, paged=True, page_size=128,
            draft_model=model, draft_params=params,
        )
        assert engine.snapshot()["paged"] is True
        assert engine.draft_model is not None

    def test_llm_deployment_accepts_paged_spec(self):
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

        dep = LLMDeployment("llama_tiny", paged=True,
                            draft_model_name="llama_tiny")
        assert dep.draft_model_name == "llama_tiny"


class TestPagedWindowKernel:
    """The Tq>1 staircase extension of the page-table kernel: window row
    t attends positions <= lengths + t, kernel vs gather reference."""

    def _pool(self, dtype, Tq, seed=0):
        rng = np.random.default_rng(seed)
        B, N, K, H, P, ps, NP = 3, 8, 4, 32, 10, 128, 2
        q = jnp.asarray(rng.standard_normal((B, Tq, N, H)), jnp.float32)
        if dtype == jnp.int8:
            k = jnp.asarray(rng.integers(-127, 127, (P, ps, K, H)), jnp.int8)
            v = jnp.asarray(rng.integers(-127, 127, (P, ps, K, H)), jnp.int8)
            ks = jnp.asarray(rng.uniform(0.01, 0.1, (P, ps, K)), jnp.float32)
            vs = jnp.asarray(rng.uniform(0.01, 0.1, (P, ps, K)), jnp.float32)
        else:
            k = jnp.asarray(rng.standard_normal((P, ps, K, H)), jnp.float32)
            v = jnp.asarray(rng.standard_normal((P, ps, K, H)), jnp.float32)
            ks = vs = None
        pt = jnp.asarray([[3, 7], [1, P], [5, 0]], jnp.int32)
        # Lengths near a page boundary so the staircase crosses pages.
        lens = jnp.asarray([200, 100, 126], jnp.int32)
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
        win = paged_window_mask(lens, NP * ps, q.shape[1])
        return _xla_attention(
            q, kg, vg, causal=False, mask=win, scale=None,
        )

    @pytest.mark.parametrize("Tq", [2, 4])
    def test_window_kernel_matches_gather_f32(self, Tq):
        q, k, v, ks, vs, pt, lens, dims = self._pool(jnp.float32, Tq)
        out = da.paged_decode_attention(q, k, v, pt, lens, interpret=True)
        assert out is not None
        ref = self._gather_ref(q, k, v, ks, vs, pt, lens, dims)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-3, rtol=1e-3
        )

    def test_window_kernel_matches_gather_int8(self):
        q, k, v, ks, vs, pt, lens, dims = self._pool(jnp.int8, 4)
        out = da.paged_decode_attention(
            q, k, v, pt, lens, k_scale=ks, v_scale=vs, interpret=True
        )
        assert out is not None
        ref = self._gather_ref(q, k, v, ks, vs, pt, lens, dims)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-2, rtol=1e-2
        )

    def test_window_one_is_decode_mask(self):
        """paged_window_mask(…, 1) is exactly decode_mask — the staircase
        rule's degenerate case, so plain decode semantics are untouched."""
        from ray_dynamic_batching_tpu.models.decoder import decode_mask

        lens = jnp.asarray([0, 5, 255], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(paged_window_mask(lens, 256, 1)),
            np.asarray(decode_mask(lens, 256)),
        )

    def test_kernel_declines_past_window_cap(self):
        q, k, v, _ks, _vs, pt, lens, _ = self._pool(jnp.float32, 9)
        # Past MAX_WINDOW_FOR_KERNEL: prefill-shaped, gather path.
        assert da.paged_decode_attention(
            q, k, v, pt, lens, interpret=True
        ) is None

    def test_scratch_page_math(self):
        # Mid-page window: covered by the partial page, no extra pages.
        assert spec_scratch_pages(10, 4, 128, 256) == 1
        # Boundary crossing: the window demands the next page.
        assert spec_scratch_pages(126, 4, 128, 256) == 2
        # Clamped at logical capacity.
        assert spec_scratch_pages(254, 4, 128, 256) == 2
