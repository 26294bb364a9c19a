"""Int8 KV cache: quantized storage parity against the bf16/f32 cache.

Decode is HBM-bound on the cache scan (every substep reads the full
capacity), so int8 halves the dominant traffic. These tests pin the
storage semantics: per-(token, head) absmax quantization at write,
dequantized read feeding the same attention, across every cache write
path (prefill, decode scatter, speculative per-row scatter, chunked
prefill at a traced offset). The reference has no decode engine to
compare against; the quantization design follows the weight-only int8
path already in models/quant.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from ray_dynamic_batching_tpu.models.causal_lm import CausalLM, TINY_LM
from ray_dynamic_batching_tpu.models.decoder import prefill_mask
from ray_dynamic_batching_tpu.models.kv_state import (
    dequantize_kv,
    quantize_kv_rows,
)


def _models():
    ref = CausalLM(TINY_LM, name="ref", dtype=jnp.float32)
    q = CausalLM(TINY_LM, name="q", dtype=jnp.float32, kv_dtype=jnp.int8)
    params = ref.init(jax.random.PRNGKey(0))
    return ref, q, params


def _prefill(model, params, B=2, T=8):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 500)
    attn = jnp.ones((B, T), jnp.int32)
    cache = model.make_cache(B, 32)
    logits, cache = model.prefill(params, tokens, attn, cache)
    return logits, cache


class TestQuantizePrimitives:
    def test_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 6, 3, 16)) * 5.0
        codes, scale = quantize_kv_rows(x)
        assert codes.dtype == jnp.int8 and scale.shape == x.shape[:-1]
        err = jnp.abs(dequantize_kv(codes, scale, jnp.float32) - x)
        # absmax/127 per row is the max quantization step
        bound = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        assert bool(jnp.all(err <= bound * 1.01))

    def test_zero_rows_stay_zero(self):
        codes, scale = quantize_kv_rows(jnp.zeros((2, 3, 4)))
        assert bool(jnp.all(codes == 0)) and bool(jnp.all(scale == 1.0))


class TestCacheShapes:
    def test_int8_cache_allocates_scales(self):
        _, q, _ = _models()
        cache = q.make_cache(2, 16)
        assert cache.quantized and cache.k.dtype == jnp.int8
        assert cache.k_scale.shape == cache.k.shape[:-1]
        assert cache.k_scale.dtype == jnp.float32

    def test_bf16_cache_has_no_scales(self):
        ref, _, _ = _models()
        assert not ref.make_cache(2, 16).quantized

    def test_kv_bytes_accounting(self):
        ref, q, _ = _models()
        c = TINY_LM
        bf = ref.kv_bytes_per_slot(32)
        i8 = q.kv_bytes_per_slot(32)
        assert bf == 2 * c.num_layers * 32 * c.num_kv_heads * c.head_dim * 4
        assert i8 == 2 * c.num_layers * 32 * c.num_kv_heads * (
            c.head_dim + 4
        )
        assert i8 < bf


class TestDecodeParity:
    def test_prefill_logits_close(self):
        ref, q, params = _models()
        ref_logits, _ = _prefill(ref, params)
        q_logits, _ = _prefill(q, params)
        # One quantized read per layer; tiny-model logits are O(5).
        np.testing.assert_allclose(
            np.asarray(q_logits), np.asarray(ref_logits), atol=0.35,
        )

    def test_teacher_forced_decode_parity(self):
        """Both caches decode the SAME token stream (the reference's
        greedy choices) so per-step quantization error is measured in
        isolation instead of compounding through diverged sequences —
        random-init tiny-model logits are near-ties, so a free-running
        comparison measures tie-breaking, not storage fidelity."""
        ref, q, params = _models()
        _, ref_cache = _prefill(ref, params)
        _, q_cache = _prefill(q, params)
        agree = 0
        worst = 0.0
        steps = 12
        tok = jnp.asarray([[3], [7]], jnp.int32)
        active = jnp.asarray([True, True])
        for _ in range(steps):
            ref_logits, ref_cache = ref.decode_step(
                params, tok, ref_cache, active
            )
            q_logits, q_cache = q.decode_step(params, tok, q_cache, active)
            worst = max(worst, float(jnp.max(jnp.abs(
                q_logits - ref_logits))))
            agree += int(jnp.sum(
                jnp.argmax(ref_logits, -1) == jnp.argmax(q_logits, -1)))
            tok = jnp.argmax(ref_logits, axis=-1)[:, None]
        assert worst < 0.5, f"per-step logit drift {worst}"
        assert agree >= int(0.75 * 2 * steps), \
            f"agreement {agree}/{2 * steps} (near-tie flips only)"
        assert bool(jnp.all(q_cache.lengths == ref_cache.lengths))

    def test_verify_step_scatter_writes_scales(self):
        ref, q, params = _models()
        _, q_cache = _prefill(q, params)
        tokens = jnp.asarray([[4, 5, 6], [9, 1, 2]], jnp.int32)
        active = jnp.asarray([True, True])
        logits, new_cache = q.verify_step(params, tokens, q_cache, active)
        assert jnp.isfinite(logits).all()
        # the window rows' scales landed at each row's own offset
        for b, start in enumerate(np.asarray(q_cache.lengths)):
            row = np.asarray(new_cache.k_scale[0, b, start:start + 3])
            assert (row > 0).all() and not np.allclose(row, 0.0)

    def test_engine_serves_with_quantized_cache(self):
        """End to end through the replica: admission (chunk program
        must carry scale planes), decode scan, completion."""
        from ray_dynamic_batching_tpu.engine.request import (
            Request, TokenStream,
        )
        from ray_dynamic_batching_tpu.serve.controller import (
            DeploymentConfig,
        )
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

        dep = LLMDeployment(
            "llama_tiny", num_slots=2, max_len=32, prompt_buckets=[8],
            default_max_new_tokens=6, dtype=jnp.float32, quantize_kv=True,
        )
        rep = dep.make_replica("kv8#0", DeploymentConfig(name="kv8"))
        assert rep.engine._cache.quantized
        rep.start()
        try:
            reqs = []
            for prompt in ([1, 5, 9], [2, 7]):
                r = Request(model="kv8", payload={"tokens": prompt},
                            slo_ms=60_000.0, stream=TokenStream())
                assert rep.assign(r)
                reqs.append(r)
            for r in reqs:
                toks = list(r.stream)
                assert len(toks) == 6 and all(
                    0 <= t < 512 for t in toks), toks
        finally:
            rep.stop()

    def test_speculative_decode_with_quantized_target_cache(self):
        """Draft proposes (bf16 draft cache), target verifies through
        the int8 cache's per-row scatter (verify_step scales path)."""
        from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
        from ray_dynamic_batching_tpu.engine.queue import RequestQueue
        from ray_dynamic_batching_tpu.engine.request import Request
        from ray_dynamic_batching_tpu.models.base import get_model
        from ray_dynamic_batching_tpu.models import registry  # noqa: F401

        target = get_model("llama_tiny", dtype=jnp.float32,
                           kv_dtype=jnp.int8)
        draft = get_model("llama_tiny", dtype=jnp.float32)
        params = target.init(jax.random.PRNGKey(0))
        queue = RequestQueue("llama_tiny", max_len=16)
        eng = DecodeEngine(
            target, params, queue, num_slots=2, max_len=32,
            prompt_buckets=[8], default_max_new_tokens=6,
            draft_model=draft, draft_params=params, spec_tokens=3,
        )
        reqs = []
        for prompt in ([1, 2, 3], [4, 5]):
            r = Request(model="llama_tiny",
                        payload={"tokens": np.asarray(prompt, np.int32),
                                 "max_new_tokens": 6},
                        slo_ms=60_000.0)
            queue.add_request(r)
            reqs.append(r)
        eng.run_until_idle(timeout_s=120)
        for r in reqs:
            assert len(r.future.result(timeout=5).tokens) == 6

    def test_auto_slot_sizing_sees_halved_kv_bytes(self, monkeypatch):
        """The HBM planner must size the continuous batch from the
        QUANTIZED cache's bytes — the capacity half of the int8 win.
        A small budget makes HBM the binding constraint (the default
        budget hits the slot cap for the tiny model either way)."""
        monkeypatch.setenv("RDB_HBM_BUDGET_BYTES", str(20 * 1024 * 1024))
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

        def slots(quantize_kv):
            dep = LLMDeployment(
                "llama_tiny", max_len=2048, dtype=jnp.float32,
                quantize_kv=quantize_kv,
            )
            return dep.auto_num_slots(max_len=2048)

        bf16_slots, int8_slots = slots(False), slots(True)
        assert int8_slots >= 2 * bf16_slots, (bf16_slots, int8_slots)

    def test_injected_model_without_kv_dtype_rejected(self):
        """quantize_kv with a model INSTANCE that wasn't built int8 must
        fail loudly — silently serving a full-precision cache would skew
        every HBM/slot-count decision downstream."""
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

        ref, _, params = _models()
        dep = LLMDeployment("llama_tiny", num_slots=2, max_len=32,
                            model=ref, params=params, quantize_kv=True)
        with pytest.raises(ValueError, match="kv_dtype"):
            dep._ensure_model()

    def _int8_engine(self, **kwargs):
        from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
        from ray_dynamic_batching_tpu.engine.queue import RequestQueue
        from ray_dynamic_batching_tpu.models.base import get_model
        from ray_dynamic_batching_tpu.models import registry  # noqa: F401

        model = get_model("llama_tiny", dtype=jnp.float32,
                          kv_dtype=jnp.int8)
        params = model.init(jax.random.PRNGKey(0))
        queue = RequestQueue("llama_tiny", max_len=32)
        defaults = dict(num_slots=2, max_len=96, prompt_buckets=[8],
                        default_max_new_tokens=5)
        defaults.update(kwargs)
        return DecodeEngine(model, params, queue, **defaults), queue

    @staticmethod
    def _submit(queue, prompt, **payload):
        import numpy as np
        from ray_dynamic_batching_tpu.engine.request import Request

        req = Request(
            model="llama_tiny",
            payload={"tokens": np.asarray(prompt, np.int32), **payload},
            slo_ms=60_000.0,
        )
        queue.add_request(req)
        return req

    def test_session_continuation_with_quantized_cache(self):
        """Multi-turn chat over an int8 pool: the stored turn's pages
        hold codes AND scale planes — turn 2 continues from the borrowed
        page and matches the model's own int8 ``KVCache`` on the full
        history."""
        from tests.decode_reference import cached_greedy
        from tests.test_decode import count_chunk_dispatches

        sess, q1 = self._int8_engine(max_len=192, session_cache_size=4)
        turn1 = [(i * 7) % 50 + 1 for i in range(130)]
        r1 = self._submit(q1, turn1, max_new_tokens=5,
                          session_id="chat-1")
        sess.run_until_idle(timeout_s=120)
        gen1 = r1.future.result(timeout=5).tokens
        assert len(sess.paged_sessions) == 1
        turn2 = turn1 + gen1 + [17, 23, 29]
        chunk_calls = count_chunk_dispatches(sess)
        r2 = self._submit(q1, turn2, max_new_tokens=5,
                          session_id="chat-1")
        sess.run_until_idle(timeout_s=120)
        # the REUSE path ran: one whole page (128 of the 134 stored
        # positions) was borrowed and only positions 128..137 prefilled
        # (two 8-wide chunks) — a silent cache miss would re-chunk the
        # whole 138-token history (18 chunks) and still match tokens.
        assert len(chunk_calls) == 2, chunk_calls
        assert (r2.future.result(timeout=5).tokens
                == cached_greedy(sess.model, sess.params, turn2, 5))

    def test_prefix_cache_with_quantized_cache(self):
        """Shared-prefix reuse over an int8 pool: the published page's
        codes AND scales serve the second admission by reference, which
        must match the model's own int8 ``KVCache`` exactly."""
        from tests.decode_reference import cached_greedy
        from tests.test_decode import count_chunk_dispatches

        shared = [(i * 7) % 50 + 1 for i in range(128)]  # = one page
        p1 = shared + [(i * 3) % 40 + 1 for i in range(10)]
        p2 = shared + [(i * 11) % 40 + 1 for i in range(7)]
        cached, q1 = self._int8_engine(max_len=192, prefix_cache_size=4)
        chunk_calls = count_chunk_dispatches(cached)
        r1 = self._submit(q1, p1, max_new_tokens=4)
        cached.run_until_idle(timeout_s=120)
        first_calls = len(chunk_calls)  # miss: all 18 chunks computed
        assert len(cached.paged_prefix) == 1
        assert cached._cache.k_scale is not None
        r2 = self._submit(q1, p2, max_new_tokens=4)
        cached.run_until_idle(timeout_s=120)
        # the hit skipped the shared page: p2's 7-token tail paid one.
        assert len(chunk_calls) - first_calls == 1, chunk_calls
        for p, r in ((p1, r1), (p2, r2)):
            assert r.future.result(timeout=5).tokens == \
                cached_greedy(cached.model, cached.params, p, 4)

    def test_engine_under_pallas_backend_matches_xla_backend(self):
        """The quantized cache must serve equivalent streams whether the
        decode scan rides the int8 kernel (pallas backend, interpret on
        CPU) or the dispatcher's dequantize-to-XLA path. The two paths
        round differently (in-dot scaling + online softmax vs dense),
        and random-init tiny-model logits are near-ties, so a rare
        greedy flip is tolerated — wholesale divergence is not."""
        import numpy as np
        from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
        from ray_dynamic_batching_tpu.engine.queue import RequestQueue
        from ray_dynamic_batching_tpu.engine.request import Request
        from ray_dynamic_batching_tpu.models.base import get_model
        from ray_dynamic_batching_tpu.models import registry  # noqa: F401
        from ray_dynamic_batching_tpu.ops.attention import (
            set_attention_backend,
        )

        model = get_model("llama_tiny", dtype=jnp.float32,
                          kv_dtype=jnp.int8)
        params = model.init(jax.random.PRNGKey(0))

        def run(backend):
            set_attention_backend(backend)
            try:
                queue = RequestQueue("llama_tiny", max_len=16)
                eng = DecodeEngine(
                    model, params, queue, num_slots=2, max_len=32,
                    prompt_buckets=[8], default_max_new_tokens=6,
                )
                reqs = []
                for prompt in ([1, 2, 3], [4, 5]):
                    r = Request(
                        model="llama_tiny",
                        payload={"tokens": np.asarray(prompt, np.int32),
                                 "max_new_tokens": 6},
                        slo_ms=60_000.0)
                    queue.add_request(r)
                    reqs.append(r)
                eng.run_until_idle(timeout_s=120)
                return [r.future.result(timeout=5).tokens for r in reqs]
            finally:
                set_attention_backend("auto")

        got_p, got_x = run("pallas"), run("xla")
        assert [len(t) for t in got_p] == [len(t) for t in got_x]
        agree = sum(
            int(a == b)
            for tp, tx in zip(got_p, got_x) for a, b in zip(tp, tx)
        )
        total = sum(len(t) for t in got_x)
        assert agree >= int(0.75 * total), f"{agree}/{total} tokens agree"

    def test_colocated_int8_engines_serve_together(self):
        """Two quantized engines share one chip through the colocation
        executor (deficit-weighted turns treat engines opaquely — this
        pins the cross-feature path actually serving)."""
        import numpy as np
        from ray_dynamic_batching_tpu.engine.colocate import (
            ColocatedLLMEngines,
        )
        from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
        from ray_dynamic_batching_tpu.engine.queue import RequestQueue
        from ray_dynamic_batching_tpu.engine.request import Request
        from ray_dynamic_batching_tpu.models.base import get_model
        from ray_dynamic_batching_tpu.models import registry  # noqa: F401

        model = get_model("llama_tiny", dtype=jnp.float32,
                          kv_dtype=jnp.int8)
        params = model.init(jax.random.PRNGKey(0))
        ex = ColocatedLLMEngines(name="int8chip")
        reqs = []
        try:
            for name in ("a", "b"):
                q = RequestQueue(name, max_len=32)
                e = DecodeEngine(model, params, q, num_slots=2,
                                 max_len=32, prompt_buckets=[8],
                                 default_max_new_tokens=5,
                                 decode_horizon=1)
                assert e._cache.quantized
                ex.attach(name, e, None)
                r = Request(model=name,
                            payload={"tokens": np.asarray([1, 2, 3],
                                                          np.int32),
                                     "max_new_tokens": 5},
                            slo_ms=600_000.0)
                q.add_request(r)
                reqs.append(r)
            for _ in range(300):
                ex.step_once()
                if all(r.future.done() for r in reqs):
                    break
            for r in reqs:
                assert len(r.future.result(timeout=5).tokens) == 5
        finally:
            ex.shutdown()

    def test_tp_mesh_shards_scale_planes(self):
        """make_sharded_paged_cache must shard the quantized pool's scale
        planes alongside k/v (a hand-listed constructor dropped them
        once) and TP decode must run with the int8 cache."""
        import numpy as np
        from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
        from ray_dynamic_batching_tpu.engine.queue import RequestQueue
        from ray_dynamic_batching_tpu.engine.request import Request
        from ray_dynamic_batching_tpu.models.base import get_model
        from ray_dynamic_batching_tpu.models import registry  # noqa: F401
        from ray_dynamic_batching_tpu.parallel.mesh import (
            MeshConfig, build_mesh,
        )

        model = get_model("llama_tiny", dtype=jnp.float32,
                          kv_dtype=jnp.int8)
        params = model.init(jax.random.PRNGKey(0))
        mesh = build_mesh(MeshConfig(tp=2), jax.devices()[:2])
        queue = RequestQueue("llama_tiny", max_len=16)
        eng = DecodeEngine(model, params, queue, num_slots=2, max_len=32,
                           prompt_buckets=[8], default_max_new_tokens=6,
                           mesh=mesh)
        assert eng._cache.quantized
        # scale planes actually live on the mesh, split over tp
        assert len(eng._cache.k_scale.sharding.device_set) == 2
        r = Request(model="llama_tiny",
                    payload={"tokens": np.asarray([1, 2, 3], np.int32),
                             "max_new_tokens": 6},
                    slo_ms=60_000.0)
        queue.add_request(r)
        eng.run_until_idle(timeout_s=120)
        assert len(r.future.result(timeout=5).tokens) == 6

    def test_chunked_prefill_traced_offset(self):
        _, q, params = _models()
        B, C = 2, 4
        cache = q.make_cache(B, 32)
        full = jax.random.randint(jax.random.PRNGKey(5), (B, 2 * C), 0, 500)
        attn = jnp.ones((B, C), jnp.int32)
        for chunk in range(2):
            toks = full[:, chunk * C:(chunk + 1) * C]
            logits, cache = q.prefill_chunk(
                params, toks, attn, cache,
                jnp.asarray(chunk * C, jnp.int32),
                jnp.asarray(C - 1, jnp.int32),
            )
        assert jnp.isfinite(logits).all()
        assert bool(jnp.all(cache.lengths == 2 * C))
        scales = np.asarray(cache.k_scale[0, :, :2 * C])
        assert (scales > 0).all()
