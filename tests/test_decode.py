"""Continuous-batching decode engine tests (tiny decoder, CPU devices).

Covers the capability matrix of SURVEY.md §7 stage 7: slot admission,
prompt-bucket padding correctness, EOS / length / capacity finishes, cache
reuse after eviction, mid-stream joins (continuous batching), and parity of
incremental decode against full-sequence teacher forcing.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy (fast lane excludes)

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine, DecodeResult
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401 — registers models
from ray_dynamic_batching_tpu.models.base import get_model

from tests.decode_reference import teacher_forced


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_engine(lm, **kwargs):
    model, params = lm
    queue = RequestQueue(model.name, max_len=256)
    defaults = dict(
        num_slots=4, max_len=64, prompt_buckets=[8, 16], eos_token_id=None,
        default_max_new_tokens=8,
    )
    defaults.update(kwargs)
    return DecodeEngine(model, params, queue, **defaults), queue


def submit(queue, prompt, slo_ms=60_000.0, **payload):
    req = Request(
        model="llama_tiny",
        payload={"tokens": np.asarray(prompt, dtype=np.int32), **payload},
        slo_ms=slo_ms,
    )
    queue.add_request(req)
    return req


def count_chunk_dispatches(engine):
    """Wrap the COMPILED chunk fn so every dispatch counts (wrapping the
    impl would count jit traces — one per shape — not dispatches)."""
    calls = []
    real = engine._chunk_paged_fn
    engine._chunk_paged_fn = lambda *a: (calls.append(1), real(*a))[1]
    return calls


def admit(engine):
    """Dequeue into chunk trains and run them to their first tokens: the
    admission as a hand-driven test sees it (the serving loop spends one
    prefill budget a turn instead)."""
    n = engine._admit()
    engine._drain_prefill()
    return n


class TestDecodeEngine:
    def test_single_request_generates(self, lm):
        engine, queue = make_engine(lm)
        req = submit(queue, [1, 2, 3], max_new_tokens=5)
        engine.run_until_idle()
        result = req.future.result(timeout=5)
        assert isinstance(result, DecodeResult)
        assert len(result.tokens) == 5
        assert result.finish_reason == "length"
        assert result.ttft_ms >= 0
        assert engine.completed == 1

    def test_greedy_matches_teacher_forcing(self, lm):
        """Incremental KV-cache decode must equal running the full prefix
        through the prefill path each step (numerical parity, fp32)."""
        model, params = lm
        engine, queue = make_engine(lm, num_slots=2, max_len=32)
        prompt = [5, 9, 2, 7]
        req = submit(queue, prompt, max_new_tokens=6)
        engine.run_until_idle()
        got = req.future.result(timeout=5).tokens

        # Teacher forcing: feed the growing sequence through apply().
        seq = list(prompt)
        expect = []
        for _ in range(6):
            tokens = jnp.asarray([seq], dtype=jnp.int32)
            mask = jnp.ones_like(tokens)
            logits = model.apply(params, tokens, mask)
            nxt = int(jnp.argmax(logits[0, -1]))
            expect.append(nxt)
            seq.append(nxt)
        assert got == expect

    def test_continuous_join_and_leave(self, lm):
        """Requests admitted mid-stream decode correctly alongside tenants."""
        engine, queue = make_engine(lm, num_slots=2, max_len=32)
        first = submit(queue, [1, 2], max_new_tokens=10)
        admit(engine)
        for _ in range(3):
            engine._step()
        # Join a second request while the first is mid-decode.
        second = submit(queue, [3, 4, 5], max_new_tokens=4)
        engine.run_until_idle()
        r1 = first.future.result(timeout=5)
        r2 = second.future.result(timeout=5)
        assert len(r1.tokens) == 10
        assert len(r2.tokens) == 4
        # Parity for the late joiner vs a fresh single-request engine.
        solo_engine, solo_q = make_engine(lm, num_slots=1, max_len=32)
        solo = submit(solo_q, [3, 4, 5], max_new_tokens=4)
        solo_engine.run_until_idle()
        assert solo.future.result(timeout=5).tokens == r2.tokens

    def test_slot_reuse_after_eviction(self, lm):
        """More requests than slots: slots must recycle with no state bleed."""
        engine, queue = make_engine(lm, num_slots=2, max_len=32)
        reqs = [submit(queue, [i + 1, i + 2], max_new_tokens=3) for i in range(5)]
        engine.run_until_idle()
        for r in reqs:
            assert len(r.future.result(timeout=5).tokens) == 3
        assert engine.completed == 5
        assert engine.active_slots == 0

    def test_eos_stops_generation(self, lm):
        model, params = lm
        engine, queue = make_engine(lm, num_slots=1, max_len=32)
        probe = submit(queue, [1, 2, 3], max_new_tokens=4)
        engine.run_until_idle()
        tokens = probe.future.result(timeout=5).tokens
        # Re-run with eos set to the second token: generation stops there.
        engine2, queue2 = make_engine(
            lm, num_slots=1, max_len=32, eos_token_id=tokens[1]
        )
        req = submit(queue2, [1, 2, 3], max_new_tokens=10)
        engine2.run_until_idle()
        result = req.future.result(timeout=5)
        assert result.finish_reason == "eos"
        assert result.tokens == tokens[:2]

    def test_capacity_finish(self, lm):
        """Cache exhaustion ends the sequence with reason=capacity."""
        engine, queue = make_engine(
            lm, num_slots=1, max_len=16, prompt_buckets=[8]
        )
        req = submit(queue, [1] * 8, max_new_tokens=1000)
        engine.run_until_idle()
        result = req.future.result(timeout=5)
        assert result.finish_reason == "capacity"
        # 8 prompt tokens leave 8 cache rows; prefill emits token 1, each
        # decode step writes one row.
        assert len(result.tokens) <= 16 - 8 + 1

    def test_prompt_filling_cache_exactly(self, lm):
        """A prompt of exactly max_len tokens leaves no decode room: the
        engine must return just the prefill token with reason=capacity, not
        an argmax-of-garbage extra token."""
        engine, queue = make_engine(
            lm, num_slots=1, max_len=8, prompt_buckets=[8]
        )
        req = submit(queue, [1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=10)
        engine.run_until_idle()
        result = req.future.result(timeout=5)
        assert result.finish_reason == "capacity"
        assert len(result.tokens) == 1

    def test_oversized_prompt_rejected(self, lm):
        """Beyond-bucket prompts now admit via chunked prefill; only
        beyond-CAPACITY prompts are rejected."""
        engine, queue = make_engine(lm, prompt_buckets=[8])  # max_len=64
        req = submit(queue, [t % 50 + 1 for t in range(70)])
        engine.run_until_idle()
        with pytest.raises(ValueError, match="exceeds KV capacity"):
            req.future.result(timeout=5)
        assert engine.active_slots == 0

    def test_threaded_lifecycle(self, lm):
        engine, queue = make_engine(lm, num_slots=2, max_len=32)
        engine.start()
        try:
            reqs = [submit(queue, [7, i], max_new_tokens=4) for i in range(4)]
            for r in reqs:
                assert len(r.future.result(timeout=30).tokens) == 4
        finally:
            engine.stop()

    def test_warmup_compiles_then_serves(self, lm):
        engine, queue = make_engine(lm, num_slots=2, max_len=32)
        engine.warmup()
        req = submit(queue, [1, 2, 3], max_new_tokens=3)
        engine.run_until_idle()
        assert len(req.future.result(timeout=5).tokens) == 3


class TestLogitBias:
    def test_banned_tokens_never_generated(self, lm):
        """Ban the tokens greedy WOULD pick: generation must route around
        them on every path (prefill first token + decode steps)."""
        probe, pq = make_engine(lm)
        r = submit(pq, [5, 9, 2, 7], max_new_tokens=6)
        probe.run_until_idle()
        natural = r.future.result(timeout=5).tokens
        banned = list(dict.fromkeys(natural))[:3]
        engine, queue = make_engine(lm)
        req = submit(queue, [5, 9, 2, 7], max_new_tokens=6,
                     banned_tokens=banned)
        engine.run_until_idle()
        got = req.future.result(timeout=5).tokens
        assert not set(got) & set(banned)
        assert got != natural

    def test_positive_bias_forces_token(self, lm):
        """A +1e9 bias on one token makes greedy pick it everywhere."""
        engine, queue = make_engine(lm)
        req = submit(queue, [1, 2, 3], max_new_tokens=4,
                     logit_bias={41: 1e9})
        engine.run_until_idle()
        assert req.future.result(timeout=5).tokens == [41, 41, 41, 41]

    def test_bias_spec_exactness(self, lm):
        """Biased greedy under SPECULATIVE decoding must equal biased
        greedy under plain decoding (verify applies the same bias)."""
        model, params = lm
        q1 = RequestQueue(model.name, max_len=256)
        q2 = RequestQueue(model.name, max_len=256)
        common = dict(num_slots=2, max_len=64, prompt_buckets=[8],
                      default_max_new_tokens=8)
        spec = DecodeEngine(model, params, q1, draft_model=model,
                            draft_params=params, spec_tokens=3, **common)
        plain = DecodeEngine(model, params, q2, **common)
        probe = submit(q2, [5, 9, 2, 7], max_new_tokens=8)
        plain.run_until_idle()
        ban = probe.future.result(timeout=5).tokens[2]
        r1 = submit(q1, [5, 9, 2, 7], max_new_tokens=8,
                    banned_tokens=[ban])
        r2 = submit(q2, [5, 9, 2, 7], max_new_tokens=8,
                    banned_tokens=[ban])
        spec.run_until_idle(timeout_s=120)
        plain.run_until_idle(timeout_s=120)
        assert (r1.future.result(timeout=5).tokens
                == r2.future.result(timeout=5).tokens)

    def test_bias_validation(self, lm):
        engine, queue = make_engine(lm)
        req = submit(queue, [1, 2], logit_bias={i: 1.0 for i in range(40)})
        engine._admit()
        with pytest.raises(ValueError, match="exceed the limit"):
            req.future.result(timeout=5)
        req2 = submit(queue, [1, 2], logit_bias={10_000_000: 1.0})
        engine._admit()
        with pytest.raises(ValueError, match="out of vocab"):
            req2.future.result(timeout=5)


class TestTopP:
    def test_tiny_nucleus_collapses_to_greedy(self, lm):
        """top_p -> 0 keeps only the argmax in the nucleus: sampled output
        must equal greedy despite temperature > 0."""
        plain, q0 = make_engine(lm)
        base = submit(q0, [5, 9, 2, 7], max_new_tokens=6)
        plain.run_until_idle()
        greedy = base.future.result(timeout=5).tokens
        engine, queue = make_engine(lm)
        r = submit(queue, [5, 9, 2, 7], max_new_tokens=6,
                   temperature=1.5, top_p=1e-6, seed=3)
        engine.run_until_idle()
        assert r.future.result(timeout=5).tokens == greedy

    def test_top_p_reproducible_and_diverse(self, lm):
        """Same seed + same top_p -> identical stream; a wide nucleus with
        high temperature must actually SAMPLE (differ from greedy for at
        least one seed, or the nucleus collapsed)."""
        plain, q0 = make_engine(lm)
        base = submit(q0, [1, 2, 3], max_new_tokens=8)
        plain.run_until_idle()
        greedy = base.future.result(timeout=5).tokens
        outs = []
        for seed in (11, 11, 12, 13):
            engine, queue = make_engine(lm)
            r = submit(queue, [1, 2, 3], max_new_tokens=8,
                       temperature=1.5, top_p=0.95, seed=seed)
            engine.run_until_idle()
            outs.append(r.future.result(timeout=5).tokens)
        assert outs[0] == outs[1]                       # reproducible
        assert any(o != greedy for o in outs)           # actually samples

    def test_top_p_zero_is_near_deterministic(self, lm):
        """OpenAI's wire shape allows top_p=0: the nucleus collapses to
        the argmax, so output equals greedy even at high temperature."""
        plain, q0 = make_engine(lm)
        base = submit(q0, [5, 9, 2, 7], max_new_tokens=6)
        plain.run_until_idle()
        greedy = base.future.result(timeout=5).tokens
        engine, queue = make_engine(lm)
        r = submit(queue, [5, 9, 2, 7], max_new_tokens=6,
                   temperature=2.0, top_p=0.0, seed=5)
        engine.run_until_idle()
        assert r.future.result(timeout=5).tokens == greedy

    def test_top_p_validation(self, lm):
        engine, queue = make_engine(lm)
        req = submit(queue, [1, 2], top_p=1.5)
        engine._admit()
        with pytest.raises(ValueError, match="top_p"):
            req.future.result(timeout=5)
        req2 = submit(queue, [1, 2], top_p=-0.1)
        engine._admit()
        with pytest.raises(ValueError, match="top_p"):
            req2.future.result(timeout=5)


class TestPenalties:
    def test_frequency_penalty_breaks_repetition(self, lm):
        """Greedy llama_tiny repeats; a frequency penalty must force
        distinct continuations while zero-penalty output is unchanged."""
        plain, q0 = make_engine(lm)
        base = submit(q0, [5, 9, 2, 7], max_new_tokens=6)
        plain.run_until_idle()
        natural = base.future.result(timeout=5).tokens
        assert len(set(natural)) < len(natural)  # it DOES repeat

        engine, queue = make_engine(lm)
        r_pen = submit(queue, [5, 9, 2, 7], max_new_tokens=6,
                       frequency_penalty=100.0)
        r_zero = submit(queue, [5, 9, 2, 7], max_new_tokens=6)
        engine.run_until_idle()
        penalized = r_pen.future.result(timeout=5).tokens
        assert len(set(penalized)) == len(penalized)  # no repeats at all
        # Zero-penalty neighbor in the same batch is untouched.
        assert r_zero.future.result(timeout=5).tokens == natural

    def test_presence_penalty_slot_reuse_is_clean(self, lm):
        """A penalty request reusing a slot must not inherit the previous
        tenant's token counts (rows zero lazily on penalty admission)."""
        engine, queue = make_engine(lm, num_slots=1)
        first = submit(queue, [5, 9, 2, 7], max_new_tokens=6,
                       presence_penalty=50.0)
        engine.run_until_idle()
        t1 = first.future.result(timeout=5).tokens
        second = submit(queue, [5, 9, 2, 7], max_new_tokens=6,
                        presence_penalty=50.0)
        engine.run_until_idle()
        t2 = second.future.result(timeout=5).tokens
        assert t1 == t2  # identical run -> identical output, no carryover

    def test_penalty_rows_bypass_speculation(self, lm):
        model, params = lm
        q = RequestQueue(model.name, max_len=256)
        engine = DecodeEngine(model, params, q, num_slots=2, max_len=64,
                              prompt_buckets=[8], draft_model=model,
                              draft_params=params, spec_tokens=3)
        submit(q, [1, 2, 3], max_new_tokens=8, frequency_penalty=2.0)
        admit(engine)
        assert not engine._use_spec()
        engine.run_until_idle(timeout_s=120)
        assert engine.completed == 1


class TestMoEDecode:
    def test_moe_decode_matches_teacher_forcing(self):
        """A Mixture-of-Experts decoder serves through the SAME continuous-
        batching engine (top-k routing runs per decode step); incremental
        KV decode must equal full-prefix teacher forcing."""
        model = get_model("moe_tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0))
        queue = RequestQueue(model.name, max_len=64)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=32,
            prompt_buckets=[8], default_max_new_tokens=6,
        )
        prompt = [5, 9, 2, 7]
        req = Request(
            model=model.name,
            payload={"tokens": np.asarray(prompt, np.int32),
                     "max_new_tokens": 6},
            slo_ms=60_000.0,
        )
        queue.add_request(req)
        engine.run_until_idle(timeout_s=120)
        got = req.future.result(timeout=5).tokens

        seq = list(prompt)
        expect = []
        for _ in range(6):
            logits = model.apply(
                params, jnp.asarray([seq]),
                jnp.ones((1, len(seq)), jnp.int32),
            )
            nxt = int(jnp.argmax(logits[0, -1]))
            expect.append(nxt)
            seq.append(nxt)
        assert got == expect


class TestSessionCache:
    def test_multi_turn_parity_and_tail_only_prefill(self, lm):
        """Turn 2 resends the whole history with the same session_id: the
        engine must continue from the stored turn's pages (chunk
        dispatches cover only what lies past the last WHOLE shared page)
        and generate exactly what the model's full forward does on the
        full prompt."""
        model, params = lm
        sess, q1 = make_engine(lm, prompt_buckets=[8], max_len=192,
                               session_cache_size=4)
        turn1 = [(i * 7) % 50 + 1 for i in range(130)]
        r1 = submit(q1, turn1, max_new_tokens=5, session_id="chat-1")
        sess.run_until_idle(timeout_s=120)
        gen1 = r1.future.result(timeout=5).tokens
        assert len(sess.paged_sessions) == 1
        # Turn 2: history + reply + new user tokens (chat shape).
        turn2 = turn1 + gen1 + [17, 23, 29]
        chunk_calls = count_chunk_dispatches(sess)
        r2 = submit(q1, turn2, max_new_tokens=5, session_id="chat-1")
        sess.run_until_idle(timeout_s=120)
        assert (r2.future.result(timeout=5).tokens
                == teacher_forced(model, params, turn2, 5))
        # Stored history = turn1 + gen1[:-1] = 134 positions: one whole
        # 128-position page is borrowed, positions 128..137 are computed
        # -> TWO 8-wide chunks, not the eighteen of the whole prompt.
        assert len(chunk_calls) == 2, chunk_calls

    def test_session_mismatched_history_falls_back(self, lm):
        """Same session id but a DIFFERENT history prefix must miss (full
        prefill) and still produce correct output."""
        sess, q1 = make_engine(lm, prompt_buckets=[8], max_len=64,
                               session_cache_size=4)
        plain, q2 = make_engine(lm, prompt_buckets=[8], max_len=64)
        r1 = submit(q1, [1, 2, 3, 4], max_new_tokens=4, session_id="s")
        sess.run_until_idle(timeout_s=120)
        r1.future.result(timeout=5)
        divergent = [9, 9, 9, 9, 9, 9]  # not an extension of turn 1
        r2 = submit(q1, divergent, max_new_tokens=4, session_id="s")
        ref = submit(q2, divergent, max_new_tokens=4)
        sess.run_until_idle(timeout_s=120)
        plain.run_until_idle(timeout_s=120)
        assert (r2.future.result(timeout=5).tokens
                == ref.future.result(timeout=5).tokens)


@pytest.fixture(scope="module")
def draft_lm():
    """A DIFFERENT tiny model as the draft: disagrees with the target often
    enough to exercise partial acceptance."""
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(42))  # different weights
    return model, params


class TestSpeculativeDecode:
    def _engines(self, lm, draft, **kw):
        model, params = lm
        dmodel, dparams = draft
        q1 = RequestQueue(model.name, max_len=256)
        q2 = RequestQueue(model.name, max_len=256)
        base = dict(num_slots=4, max_len=64, prompt_buckets=[8, 16],
                    default_max_new_tokens=8)
        base.update(kw)
        spec = DecodeEngine(model, params, q1, draft_model=dmodel,
                            draft_params=dparams, spec_tokens=3, **base)
        plain = DecodeEngine(model, params, q2, **base)
        return spec, q1, plain, q2

    def test_exact_greedy_with_divergent_draft(self, lm, draft_lm):
        """A draft with different weights yields partial acceptance, but
        verified output must still be EXACTLY plain greedy."""
        spec, q1, plain, q2 = self._engines(lm, draft_lm)
        prompts = [[5, 9, 2, 7], [3, 1, 4], [11, 13], [2, 4, 6, 8, 10]]
        r1 = [submit(q1, p, max_new_tokens=12) for p in prompts]
        r2 = [submit(q2, p, max_new_tokens=12) for p in prompts]
        spec.run_until_idle(timeout_s=180)
        plain.run_until_idle(timeout_s=180)
        for a, b in zip(r1, r2):
            assert (a.future.result(timeout=5).tokens
                    == b.future.result(timeout=5).tokens)

    def test_self_draft_accepts_everything(self, lm):
        """draft == target: every proposal verifies, so each round lands
        spec_tokens+1 tokens and the round count collapses."""
        model, params = lm
        q = RequestQueue(model.name, max_len=256)
        spec = DecodeEngine(model, params, q, num_slots=2, max_len=64,
                            prompt_buckets=[8], draft_model=model,
                            draft_params=params, spec_tokens=3)
        req = submit(q, [5, 9, 2, 7], max_new_tokens=12)
        spec.run_until_idle(timeout_s=120)
        assert len(req.future.result(timeout=5).tokens) == 12
        # 12 tokens: 1 from prefill + rounds of 4 -> 3 spec rounds.
        assert spec.steps == 3

    def test_sampled_rows_fall_back_to_plain_decode(self, lm, draft_lm):
        """temperature > 0 in the batch must bypass the speculative path
        (exactness only holds for greedy)."""
        spec, q1, _, _ = self._engines(lm, draft_lm)
        req = submit(q1, [1, 2, 3], max_new_tokens=6, temperature=0.8,
                     seed=7)
        admit(spec)
        assert not spec._use_spec()
        spec.run_until_idle(timeout_s=120)
        assert len(req.future.result(timeout=5).tokens) == 6

    def test_draft_stays_synced_through_plain_intervals(self, lm):
        """Plain decode steps (chunked-prefill interleave) must catch the
        DRAFT cache up; with draft == target, speculation afterwards still
        accepts EVERY proposal — a desynced draft would collapse to ~0."""
        from ray_dynamic_batching_tpu.engine.decode import (
            SPEC_ACCEPTED,
            SPEC_ROUNDS,
        )
        model, params = lm
        q = RequestQueue(model.name, max_len=256)
        spec = DecodeEngine(model, params, q, num_slots=2, max_len=96,
                            prompt_buckets=[8], draft_model=model,
                            draft_params=params, spec_tokens=3)
        # Greedy request decoding...
        r1 = submit(q, [5, 9, 2, 7], max_new_tokens=30)
        admit(spec)
        spec._step()
        # ...then a long admission forces plain interleave steps.
        r2 = submit(q, [(i * 7) % 50 + 1 for i in range(20)],
                    max_new_tokens=30)
        # The counters carry a ``paged`` tag, always "true": a
        # model-only read keys a series nothing ever increments.
        tags = {"model": model.name, "paged": "true"}
        rounds0 = SPEC_ROUNDS.get(tags=tags)
        acc0 = SPEC_ACCEPTED.get(tags=tags)
        spec.run_until_idle(timeout_s=180)
        rounds = SPEC_ROUNDS.get(tags=tags) - rounds0
        acc = SPEC_ACCEPTED.get(tags=tags) - acc0
        assert len(r1.future.result(timeout=5).tokens) == 30
        assert len(r2.future.result(timeout=5).tokens) == 30
        # Self-draft: every verified round must accept all 3 proposals
        # (per active row). With 2 rows active much of the time, accepted
        # averages > 3 per round; a desynced draft would give ~0.
        assert rounds > 0
        assert acc >= rounds * 3, (acc, rounds)

    def test_spec_with_long_prompt_and_eos(self, lm, draft_lm):
        """Chunked admission fills the DRAFT cache too; stop tokens cut a
        round's accepted run mid-window exactly like plain decode."""
        spec, q1, plain, q2 = self._engines(lm, draft_lm)
        long_prompt = [(i * 7) % 50 + 1 for i in range(20)]
        probe = submit(q2, long_prompt, max_new_tokens=8)
        plain.run_until_idle(timeout_s=180)
        toks = probe.future.result(timeout=5).tokens
        stop = toks[4]  # force a stop mid-generation
        r1 = submit(q1, long_prompt, max_new_tokens=8,
                    stop_token_ids=[stop])
        r2 = submit(q2, long_prompt, max_new_tokens=8,
                    stop_token_ids=[stop])
        spec.run_until_idle(timeout_s=180)
        plain.run_until_idle(timeout_s=180)
        assert (r1.future.result(timeout=5).tokens
                == r2.future.result(timeout=5).tokens)


class TestStreamingAndHorizon:
    def test_tokens_stream_before_completion(self, lm):
        """Streaming contract (ref serve/batching.py:209-276): tokens must
        be observable on the TokenStream while generation is still running."""
        from ray_dynamic_batching_tpu.engine.request import TokenStream

        engine, queue = make_engine(lm, decode_horizon=1)
        req = Request(
            model="llama_tiny",
            payload={"tokens": np.asarray([1, 2, 3], np.int32),
                     "max_new_tokens": 6},
            slo_ms=60_000.0,
            stream=TokenStream(),
        )
        queue.add_request(req)

        seen_before_done = []
        admit(engine)                      # prefill -> first token
        assert not req.future.done()
        seen_before_done.append(req.stream.get(timeout_s=5))
        engine._step(horizon=1)            # second token, still unfinished
        assert not req.future.done()
        seen_before_done.append(req.stream.get(timeout_s=5))
        engine.run_until_idle()
        result = req.future.result(timeout=5)
        streamed = seen_before_done + req.stream.drain()
        assert streamed == result.tokens
        assert len(seen_before_done) >= 2  # arrived incrementally

    def test_horizon_matches_single_step(self, lm):
        """Greedy decode is deterministic: a scan horizon of 4 must produce
        exactly the tokens of four single steps."""
        single, q1 = make_engine(lm, decode_horizon=1)
        multi, q2 = make_engine(lm, decode_horizon=4)
        # Count device dispatches (host round-trips) on the horizon engine.
        real_fn = multi._decode_fn
        dispatches = []

        def counting_fn(*args):
            dispatches.append(args[3])  # the static horizon argument
            return real_fn(*args)

        multi._decode_fn = counting_fn
        # Four prompts fill the 4-slot batch: the full horizon tier runs.
        prompts = [[5, 9, 2, 7], [3, 1, 4], [11, 13], [6, 8, 10]]
        reqs1 = [submit(q1, p, max_new_tokens=9) for p in prompts]
        reqs2 = [submit(q2, p, max_new_tokens=9) for p in prompts]
        single.run_until_idle()
        multi.run_until_idle()
        for r1, r2 in zip(reqs1, reqs2):
            t1 = r1.future.result(timeout=5).tokens
            t2 = r2.future.result(timeout=5).tokens
            assert t1 == t2
        # The scan path must actually amortize: at least one multi-step
        # dispatch, and fewer dispatches than tokens generated (36).
        assert any(h > 1 for h in dispatches)
        assert len(dispatches) < 36

    def test_three_tier_horizon_policy(self, lm):
        """Full scan only when the batch is full; the short ttft_horizon
        while slots are free with an empty queue (bounds admission latency);
        single steps while requests wait for a slot."""
        engine, queue = make_engine(
            lm, num_slots=2, decode_horizon=8, ttft_horizon=2
        )
        assert engine.ttft_horizon == 2
        # Free slots + empty queue -> ttft tier.
        r1 = submit(queue, [1, 2], max_new_tokens=16)
        admit(engine)
        assert engine._pick_horizon() == 2
        # Batch full -> full horizon regardless of the queue.
        r2 = submit(queue, [3, 4], max_new_tokens=16)
        admit(engine)
        assert not engine._free_slots()
        assert engine._pick_horizon() == 8
        # Free slot + waiting request -> single step (admit ASAP).
        submit(queue, [5, 6], max_new_tokens=4)
        engine._finish(0, "length")
        assert engine._pick_horizon() == 1
        engine.run_until_idle()
        assert engine.completed == 3
        # ttft_horizon is clamped to decode_horizon and derived when omitted.
        derived, _ = make_engine(lm, decode_horizon=8)
        assert derived.ttft_horizon == 2
        clamped, _ = make_engine(lm, decode_horizon=2, ttft_horizon=64)
        assert clamped.ttft_horizon == 2

    def test_long_prompt_chunked_parity(self, lm):
        """A prompt longer than every bucket admits via chunked prefill and
        must generate exactly the tokens of a one-shot-bucketed engine."""
        long_prompt = [(i * 7) % 50 + 1 for i in range(21)]
        chunked, q1 = make_engine(lm, prompt_buckets=[8], max_len=64)
        oneshot, q2 = make_engine(lm, prompt_buckets=[32], max_len=64)
        r1 = submit(q1, long_prompt, max_new_tokens=6)
        r2 = submit(q2, long_prompt, max_new_tokens=6)
        chunked.run_until_idle(timeout_s=120)
        oneshot.run_until_idle(timeout_s=120)
        t1 = r1.future.result(timeout=5).tokens
        t2 = r2.future.result(timeout=5).tokens
        assert t1 == t2
        assert len(t1) == 6

    def test_long_prompt_interleaves_decode(self, lm):
        """Active slots must advance BETWEEN prefill chunks: a long
        admission may stall decode by at most one chunk, not the whole
        prompt."""
        engine, queue = make_engine(
            lm, num_slots=2, prompt_buckets=[8], max_len=64,
            decode_horizon=1,
        )
        short = submit(queue, [1, 2, 3], max_new_tokens=40)
        assert admit(engine) == 1
        engine._step()  # short request actively decoding
        engine.reset_ttft_window()
        submit(queue, [(i * 3) % 40 + 1 for i in range(20)],
               max_new_tokens=4)
        engine.run_until_idle(timeout_s=120)
        # 20 tokens / 8-chunks = 3 chunks, a decode turn after each: the
        # ring never shows two chunk dispatches in a row.
        kinds = [t.kind for t in engine.turns]
        assert kinds.count("chunk") == 3
        assert all(a != "chunk" or b == "turn"
                   for a, b in zip(kinds, kinds[1:]))
        assert len(short.future.result(timeout=5).tokens) == 40

    def test_long_prompt_capacity_not_chunk_multiple(self, lm):
        """max_len NOT a multiple of the chunk width: the final chunk's
        write must not clamp backward and corrupt earlier cache positions
        (row cache rounds up to whole chunks; commit slices down)."""
        long_prompt = [(i * 7) % 50 + 1 for i in range(19)]
        chunked, q1 = make_engine(lm, prompt_buckets=[8], max_len=20)
        oneshot, q2 = make_engine(lm, prompt_buckets=[32], max_len=32)
        r1 = submit(q1, long_prompt, max_new_tokens=1)
        r2 = submit(q2, long_prompt, max_new_tokens=1)
        chunked.run_until_idle(timeout_s=120)
        oneshot.run_until_idle(timeout_s=120)
        assert (r1.future.result(timeout=5).tokens
                == r2.future.result(timeout=5).tokens)

    def test_prefix_cache_hit_parity_and_skip(self, lm):
        """Two long prompts sharing their first page: the second admission
        must borrow the published page by reference (its chunk dispatches
        cover the tail only) and generate exactly the tokens of the
        model's full forward."""
        model, params = lm
        shared = [(i * 7) % 50 + 1 for i in range(128)]    # = one page
        p1 = shared + [(i * 3) % 40 + 1 for i in range(10)]
        p2 = shared + [(i * 11) % 40 + 1 for i in range(7)]
        cached, q1 = make_engine(lm, prompt_buckets=[8], max_len=192,
                                 prefix_cache_size=4)
        chunk_calls = count_chunk_dispatches(cached)
        r1 = submit(q1, p1, max_new_tokens=4)
        cached.run_until_idle(timeout_s=120)
        first_calls = len(chunk_calls)   # miss: every chunk computed
        assert first_calls == 18         # p1 = 138 tokens / 8-chunks
        r2 = submit(q1, p2, max_new_tokens=4)
        cached.run_until_idle(timeout_s=120)
        # p2 = 135 tokens; the hit skips the shared page -> the 7-token
        # tail is exactly 1 chunk.
        assert len(chunk_calls) - first_calls == 1
        assert len(cached.paged_prefix) == 1
        for p, r in ((p1, r1), (p2, r2)):
            assert r.future.result(timeout=5).tokens == \
                teacher_forced(model, params, p, 4)

    def test_prompt_beyond_capacity_rejected(self, lm):
        engine, queue = make_engine(lm, prompt_buckets=[8], max_len=16)
        req = submit(queue, list(range(1, 18)), max_new_tokens=2)
        engine._admit()
        with pytest.raises(ValueError, match="exceeds KV capacity"):
            req.future.result(timeout=5)

    def test_eos_mid_horizon(self, lm):
        """A slot hitting EOS inside a scan horizon stops exactly at EOS and
        the discarded tail never reaches the caller."""
        model, params = lm
        # Find what greedy generates so we can set eos to the 3rd token.
        probe_engine, probe_q = make_engine(lm, decode_horizon=1)
        probe = submit(probe_q, [5, 9, 2, 7], max_new_tokens=8)
        probe_engine.run_until_idle()
        toks = probe.future.result(timeout=5).tokens
        # First position whose token hasn't occurred earlier makes an
        # unambiguous eos marker.
        k = next(
            (i for i in range(1, len(toks) - 1) if toks[i] not in toks[:i]),
            None,
        )
        assert k is not None, f"degenerate greedy output {toks}"
        eos = toks[k]

        engine, queue = make_engine(
            lm, decode_horizon=8, eos_token_id=eos
        )
        req = submit(queue, [5, 9, 2, 7], max_new_tokens=8)
        engine.run_until_idle()
        result = req.future.result(timeout=5)
        assert result.finish_reason == "eos"
        assert result.tokens == toks[: k + 1]


class TestAdmissionErrors:
    def test_bad_max_new_tokens_rejects_not_dangles(self, lm):
        """A malformed payload discovered after dequeue must reject the
        request's future, never leave it dangling (and must not poison the
        rest of the admission batch)."""
        engine, queue = make_engine(lm)
        bad = Request(
            model="llama_tiny",
            payload={"tokens": np.asarray([1, 2], np.int32),
                     "max_new_tokens": "ten"},
            slo_ms=60_000.0,
        )
        queue.add_request(bad)
        good = submit(queue, [3, 4], max_new_tokens=3)
        engine.run_until_idle()
        with pytest.raises(ValueError):
            bad.future.result(timeout=5)
        assert len(good.future.result(timeout=5).tokens) == 3


class TestSampling:
    def test_seeded_sampling_reproducible(self, lm):
        """Same seed + temperature -> identical sequences across engines —
        INCLUDING an engine with prior traffic (keys derive from the
        request's own token indices, never global engine state); different
        seeds -> (overwhelmingly) different sequences."""
        outs = []
        for i, seed in enumerate((7, 7, 99)):
            engine, queue = make_engine(lm, num_slots=2)
            if i == 1:
                # Prior traffic: steps/admissions advance before the probe.
                warm = submit(queue, [9, 8, 7], max_new_tokens=5,
                              temperature=0.8, seed=1)
                engine.run_until_idle()
                assert len(warm.future.result(timeout=5).tokens) == 5
            req = submit(queue, [1, 2, 3], max_new_tokens=12,
                         temperature=1.0, seed=seed)
            engine.run_until_idle()
            outs.append(req.future.result(timeout=5).tokens)
        assert outs[0] == outs[1]          # reproducible despite traffic
        assert outs[0] != outs[2]          # seed-sensitive

    def test_temperature_zero_is_greedy(self, lm):
        engine, queue = make_engine(lm, num_slots=2)
        greedy = submit(queue, [5, 9, 2], max_new_tokens=6)
        explicit = submit(queue, [5, 9, 2], max_new_tokens=6,
                          temperature=0.0, seed=123)
        engine.run_until_idle()
        assert (greedy.future.result(timeout=5).tokens
                == explicit.future.result(timeout=5).tokens)

    def test_top_k_one_equals_greedy(self, lm):
        """top_k=1 leaves only the argmax in the support: any temperature
        must reproduce greedy."""
        engine, queue = make_engine(lm, num_slots=2)
        greedy = submit(queue, [4, 8], max_new_tokens=8)
        k1 = submit(queue, [4, 8], max_new_tokens=8,
                    temperature=5.0, top_k=1, seed=42)
        engine.run_until_idle()
        assert (greedy.future.result(timeout=5).tokens
                == k1.future.result(timeout=5).tokens)

    def test_mixed_batch_sampling_isolated(self, lm):
        """A sampled request and a greedy request share the batch; the
        greedy one must be bit-identical to a solo greedy run."""
        engine, queue = make_engine(lm, num_slots=2)
        sampled = submit(queue, [1, 2, 3], max_new_tokens=8,
                         temperature=1.3, seed=5)
        greedy = submit(queue, [5, 9, 2, 7], max_new_tokens=8)
        engine.run_until_idle()
        solo_engine, solo_q = make_engine(lm, num_slots=1)
        solo = submit(solo_q, [5, 9, 2, 7], max_new_tokens=8)
        solo_engine.run_until_idle()
        assert (greedy.future.result(timeout=5).tokens
                == solo.future.result(timeout=5).tokens)
        assert len(sampled.future.result(timeout=5).tokens) == 8

    def test_negative_temperature_rejected(self, lm):
        engine, queue = make_engine(lm)
        req = submit(queue, [1, 2], temperature=-1.0)
        engine.run_until_idle()
        with pytest.raises(ValueError, match="temperature"):
            req.future.result(timeout=5)


class TestStopTokens:
    def test_per_request_stop_token_ids(self, lm):
        """stop_token_ids finish a request exactly like EOS — but scoped to
        that request only (its batch neighbor keeps decoding)."""
        probe_engine, probe_q = make_engine(lm)
        probe = submit(probe_q, [5, 9, 2, 7], max_new_tokens=8)
        probe_engine.run_until_idle()
        toks = probe.future.result(timeout=5).tokens
        k = next(i for i in range(1, len(toks)) if toks[i] not in toks[:i])

        engine, queue = make_engine(lm, num_slots=2)
        stopped = submit(queue, [5, 9, 2, 7], max_new_tokens=8,
                         stop_token_ids=[toks[k]])
        neighbor = submit(queue, [5, 9, 2, 7], max_new_tokens=8)
        engine.run_until_idle()
        r = stopped.future.result(timeout=5)
        assert r.finish_reason == "eos"
        assert r.tokens == toks[: k + 1]
        assert neighbor.future.result(timeout=5).tokens == toks  # unaffected


class TestMidAdmissionVisibility:
    def test_admitting_requests_are_busy(self, lm):
        """Between dequeue and slot registration a request is in NEITHER
        the queue nor active_slots; `busy` must cover that window or
        drain logic aborts requests mid-prefill (found by the colocation
        demo deterministically dropping its final tail request)."""
        engine, queue = make_engine(lm, num_slots=2)
        try:
            seen = {}
            real = engine._start_train

            def spy(*args):
                seen["busy"] = engine.busy
                seen["admitting"] = engine._admitting
                return real(*args)

            engine._start_train = spy
            submit(queue, [1, 2, 3], max_new_tokens=2)
            engine._admit()
            assert seen == {"busy": True, "admitting": 1}
            # Dequeue done: the ledger is clear, the chunk train carries
            # the request (no slot is active before its first token).
            assert engine._admitting == 0
            assert engine.busy and engine.active_slots == 0
            engine._drain_prefill()
            assert engine.busy and engine.active_slots == 1
            engine.run_until_idle(timeout_s=60)
            assert not engine.busy
        finally:
            engine.release_buffers()
