"""LLM colocation EXECUTION tests — plans that run, not just print.

The decode analogue of the vision live-scheduler tests: two llama_tiny
decode engines share one device per ``pack_llm_engines``'s plan
(``ColocatedLLMEngines`` interleaves their scans), both hold their token
SLOs under load, a token-rate shift is detected and triggers a replan
that changes the packing with a live engine migration, and the planner's
``compute_fraction`` occupancy model is validated against the measured
time shares of co-resident engines (ref: plan *execution*
``293-project/src/scheduler.py:525-584`` and live rebalance ``:773-929``).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy (fast lane excludes)

from ray_dynamic_batching_tpu.engine.colocate import ColocatedLLMEngines
from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.rates import RateRegistry
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.profiles.table import BatchProfile, ProfileRow
from ray_dynamic_batching_tpu.scheduler.llm_control import LLMLiveScheduler
from ray_dynamic_batching_tpu.scheduler.nexus import worst_latency_ms


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def measured_rows(lm):
    """Solo-measured decode rows for the two engine shapes the tests use
    (the planner's ground truth — the same committed-table contract as
    profiles/cpu, measured here so the test tracks this machine)."""
    from ray_dynamic_batching_tpu.profiles.decode_profiler import (
        DecodeProfiler,
    )

    model, params = lm
    prof = DecodeProfiler(model, params, timing_iters=4, warmup_iters=1)
    return {
        (4, 64): prof.profile_decode_config(4, 64),
        (2, 32): prof.profile_decode_config(2, 32),
    }


def make_profiles(measured_rows):
    """Planner inputs: model ``tiny_a`` serves from the (4 slots, cap 64)
    config, ``tiny_b`` from (2 slots, cap 32)."""
    a = measured_rows[(4, 64)]
    b = measured_rows[(2, 32)]
    return {
        "tiny_a": BatchProfile("tiny_a_decode", [a]),
        "tiny_b": BatchProfile("tiny_b_decode", [b]),
    }


def make_factory(lm):
    model, params = lm

    def factory(name, placement, queue, device):
        return DecodeEngine(
            model, params, queue,
            num_slots=placement.num_slots, max_len=placement.capacity,
            prompt_buckets=[8], default_max_new_tokens=12,
            decode_horizon=1, device=device,
        )

    return factory


def submit(sched, model, n, max_new=12, prompt=(1, 2, 3)):
    reqs = []
    for i in range(n):
        req = Request(
            model=model,
            payload={"tokens": np.asarray(prompt, np.int32) + i % 3,
                     "max_new_tokens": max_new},
            slo_ms=600_000.0,
        )
        assert sched.submit_request(req)
        reqs.append(req)
    return reqs


def rate_for_fraction(row: ProfileRow, fraction: float) -> float:
    """Offered tok/s that makes _pick_llm_row's capacity fraction equal
    ``fraction`` for this row."""
    return fraction * 1000.0 * row.batch_size / row.latency_ms


def token_slo_for(row: ProfileRow) -> float:
    """Loose token SLO: 50x the worst-case measured substep, so f_slo is
    tiny and the capacity fraction dominates the packing decision."""
    return max(50.0, 50.0 * worst_latency_ms(row))


class TestColocatedExecution:
    def test_plan_executes_two_engines_one_device_slos_hold(
        self, lm, measured_rows
    ):
        """The packed plan RUNS: both models on one executor, interleaved
        scans, every request completes within its (loose) token SLO."""
        profiles = make_profiles(measured_rows)
        row_a, row_b = measured_rows[(4, 64)], measured_rows[(2, 32)]
        chips = [ColocatedLLMEngines(name="chip0"),
                 ColocatedLLMEngines(name="chip1")]
        sched = LLMLiveScheduler(profiles, chips, make_factory(lm))
        slo_a, slo_b = token_slo_for(row_a), token_slo_for(row_b)
        sched.register_model("tiny_a", token_slo_ms=slo_a,
                             tokens_per_request=12)
        sched.register_model("tiny_b", token_slo_ms=slo_b,
                             tokens_per_request=12)
        try:
            plan = sched.rebalance(rates={
                "tiny_a": rate_for_fraction(row_a, 0.25),
                "tiny_b": rate_for_fraction(row_b, 0.25),
            })
            assert len(plan) == 1, "low fractions must colocate"
            used = [c for c in chips if c.models()]
            assert len(used) == 1
            assert set(used[0].models()) == {"tiny_a", "tiny_b"}

            used[0].start()
            # Warmup wave: the first requests pay XLA compiles inside
            # their token gaps; SLOs are judged on warm programs (the
            # serving stack warms replicas before registering them).
            for r in submit(sched, "tiny_a", 2) + submit(
                sched, "tiny_b", 2
            ):
                r.future.result(timeout=120)

            reqs_a = submit(sched, "tiny_a", 6)
            reqs_b = submit(sched, "tiny_b", 6)
            results = [r.future.result(timeout=120)
                       for r in reqs_a + reqs_b]
            for res, slo in zip(
                results, [slo_a] * len(reqs_a) + [slo_b] * len(reqs_b)
            ):
                assert len(res.tokens) == 12
                gap = (res.total_ms - res.ttft_ms) / max(
                    1, len(res.tokens) - 1
                )
                assert gap <= slo, (
                    f"inter-token gap {gap:.1f}ms blew the {slo:.0f}ms SLO"
                )
        finally:
            sched.shutdown()

    def test_rate_shift_detected_replans_and_migrates(
        self, lm, measured_rows
    ):
        """A token-rate surge past the monitor threshold changes the
        packing (1 chip -> 2) and live-migrates an engine; traffic keeps
        completing through the migration."""
        profiles = make_profiles(measured_rows)
        row_a, row_b = measured_rows[(4, 64)], measured_rows[(2, 32)]
        fake = {"t": 1000.0}
        clock = lambda: fake["t"]  # noqa: E731
        rates = RateRegistry(window_s=10.0, clock=clock)
        chips = [ColocatedLLMEngines(name="chip0"),
                 ColocatedLLMEngines(name="chip1")]
        sched = LLMLiveScheduler(
            profiles, chips, make_factory(lm), rates=rates, clock=clock
        )
        sched.register_model("tiny_a", token_slo_ms=token_slo_for(row_a))
        sched.register_model("tiny_b", token_slo_ms=token_slo_for(row_b))
        low_a = rate_for_fraction(row_a, 0.25)
        low_b = rate_for_fraction(row_b, 0.25)
        try:
            plan = sched.rebalance(rates={"tiny_a": low_a,
                                          "tiny_b": low_b})
            assert len(plan) == 1
            host0 = next(c for c in chips if c.models())

            # Phase-1 traffic completes on the shared chip.
            reqs = submit(sched, "tiny_a", 3) + submit(sched, "tiny_b", 3)
            host0.run_until_idle(timeout_s=120)
            for r in reqs:
                assert r.future.result(timeout=5).finish_reason == "length"

            # Surge tiny_a's offered token rate to a 0.7 fraction: with
            # tiny_b at 0.25 the pair (0.95) no longer fits one chip
            # under the 0.85 headroom -> the plan must split. Spread the
            # records across fake seconds (advancing BEFORE each record
            # so covered span == record count and the window rate equals
            # the offered rate exactly) — the control plane (correctly)
            # refuses to migrate engines on a cold 1-second extrapolation.
            surge_a = int(rate_for_fraction(row_a, 0.7))
            for i in range(6):
                if i:
                    fake["t"] += 1.0
                rates.record("tiny_a", n=surge_a)
                rates.record("tiny_b", n=int(low_b))
            changed = rates.changed_models(
                sched.rate_threshold, sched.rate_decrease_multiplier,
                min_span_s=rates.window_s / 2.0,
            )
            assert "tiny_a" in changed, "surge must trip the monitor test"

            plan2 = sched.rebalance()
            assert len(plan2) == 2, "surged fractions must split chips"
            assert sched.migrations >= 1
            hosts = {m: c.name for c in chips for m in c.models()}
            assert hosts["tiny_a"] != hosts["tiny_b"]

            # Post-migration traffic serves from the NEW placement.
            reqs2 = submit(sched, "tiny_a", 2) + submit(sched, "tiny_b", 2)
            for c in chips:
                c.run_until_idle(timeout_s=120)
            for r in reqs2:
                assert r.future.result(timeout=5).finish_reason == "length"
            # The drained predecessor released its buffers.
            assert all(len(c.busy_fractions()) <= 1 for c in chips)
        finally:
            sched.shutdown()


class TestOccupancyModelValidation:
    """VERDICT r4 #4, strengthened by the deficit-weighted executor: the
    planner admits engines by compute fraction, and under sustained
    backlog the executor must DELIVER those fractions as measured chip
    time — a drifting model or scheduler fails here before production.
    Share ratios under identical load are robust to background noise
    (contention slows both tenants together), unlike absolute timings."""

    @staticmethod
    def _saturate(engine, queue, waves=2):
        for i in range(waves * engine.num_slots):
            queue.add_request(Request(
                model=engine.model.name,
                payload={"tokens": np.asarray([1, 2, 3], np.int32),
                         "max_new_tokens": engine.max_len},
                slo_ms=600_000.0,
            ))

    @staticmethod
    def _colocated_shares(lm, fractions, passes=250):
        """Run two engines (different shapes, so different step costs)
        saturated on one executor; return measured busy shares."""
        from ray_dynamic_batching_tpu.scheduler.nexus import LLMPlacement

        model, params = lm
        shapes = {"a": (4, 64), "b": (2, 32)}
        ex = ColocatedLLMEngines(name="shared")
        engines = {}
        for name, (slots, cap) in shapes.items():
            q = RequestQueue(name, max_len=256)
            e = DecodeEngine(model, params, q, num_slots=slots,
                             max_len=cap, prompt_buckets=[8],
                             decode_horizon=1)
            placement = None
            if fractions.get(name) is not None:
                placement = LLMPlacement(
                    model=name, num_slots=slots, capacity=cap,
                    step_ms=1.0, compute_fraction=fractions[name],
                    hbm_bytes=1,
                )
            ex.attach(name, e, placement)
            engines[name] = (e, q)
        for name, (e, q) in engines.items():
            TestOccupancyModelValidation._saturate(e, q, waves=8)
        for _ in range(8):  # warm: admissions + first compiles
            ex.step_once()
        ex.reset_accounting()
        done = 0
        while done < passes and all(
            e.active_slots > 0 or len(q) > 0
            for e, q in engines.values()
        ):
            ex.step_once()
            done += 1
        fr = ex.busy_fractions()
        ex.shutdown()
        assert done >= 50, "window too short to mean anything"
        return fr

    def test_planned_fractions_are_delivered(self, lm):
        """An asymmetric plan (0.7 / 0.3) must show up as chip-time
        shares — regardless of the engines' own step costs."""
        fr = self._colocated_shares(lm, {"a": 0.7, "b": 0.3})
        share = fr["a"] / max(fr["a"] + fr["b"], 1e-9)
        assert abs(share - 0.7) <= 0.12, (
            f"a's planned 0.70 of chip time measured {share:.2f}"
        )
        assert 0.8 <= fr["a"] + fr["b"] <= 1.01

    def test_long_prompt_fill_does_not_stall_cotenant(self, lm):
        """A long chunked admission on tenant A must NOT monopolize the
        shared chip: the between-chunk hook hands co-tenant B one scan
        per chunk, so B keeps producing tokens through A's whole fill."""
        model, params = lm
        ex = ColocatedLLMEngines(name="isolation")
        q_a = RequestQueue("a", max_len=64)
        e_a = DecodeEngine(model, params, q_a, num_slots=2, max_len=256,
                           prompt_buckets=[8], decode_horizon=1)
        q_b = RequestQueue("b", max_len=64)
        e_b = DecodeEngine(model, params, q_b, num_slots=2, max_len=128,
                           prompt_buckets=[8], decode_horizon=1)
        ex.attach("a", e_a)
        ex.attach("b", e_b)
        try:
            # Prime B with long-running decodes so it has active work for
            # the duration of A's fill.
            for _ in range(2):
                q_b.add_request(Request(
                    model="llama_tiny",
                    payload={"tokens": np.asarray([1, 2, 3], np.int32),
                             "max_new_tokens": 120},
                    slo_ms=600_000.0,
                ))
            while e_b.active_slots == 0:
                ex.step_once()
            # A's long prompt: 120 tokens over 8-wide chunks = a train of
            # 15 chunk dispatches, one prefill budget a turn.
            prompt = np.arange(1, 121, dtype=np.int32)
            n_chunks = (len(prompt) + 7) // 8
            q_a.add_request(Request(
                model="llama_tiny",
                payload={"tokens": prompt, "max_new_tokens": 4},
                slo_ms=600_000.0,
            ))
            b_steps0 = e_b.steps
            while e_a.active_slots == 0:
                assert ex.step_once(), "executor stalled before admission"
            # From A's dequeue to its first token its 15-chunk train ran;
            # B must have scanned between chunks (the yield after each
            # dispatch, and B's own turns between A's).
            gained = e_b.steps - b_steps0
            assert gained >= n_chunks - 3, (
                f"co-tenant starved during long fill: B stepped {gained} "
                f"times across a {n_chunks}-chunk admission"
            )
        finally:
            ex.shutdown()

    def test_unplanned_engines_split_evenly(self, lm):
        """No placements: equal weights, equal TIME shares — even though
        the (4,64) engine's scans cost more than the (2,32)'s."""
        fr = self._colocated_shares(lm, {"a": None, "b": None})
        share = fr["a"] / max(fr["a"] + fr["b"], 1e-9)
        assert abs(share - 0.5) <= 0.12, (
            f"equal split expected, a measured {share:.2f}"
        )
