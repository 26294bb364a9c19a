"""TTFT admission-latency bounds and decomposition (tiny decoder, CPU).

The north-star TTFT target (BASELINE.json: p50 < 150 ms) depends on the
three-tier decode horizon: while slots are free, an arrival during an
in-flight decode scan waits at most ``ttft_horizon`` substeps before the
engine can admit it, instead of the full ``decode_horizon`` scan. These
tests quantify that bound on CPU — substeps between arrival and admission
under the ttft tier vs a full-horizon policy — so the bound is a
regression-protected count on the CPU lane, not a one-off on-chip
measurement. The decomposition tests pin the queue/scan/prefill split the
bench LLM row publishes (bench.py ``ttft_breakdown``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy (fast lane excludes)

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_engine(lm, **kwargs):
    model, params = lm
    queue = RequestQueue(model.name, max_len=256)
    defaults = dict(
        num_slots=4, max_len=64, prompt_buckets=[8], eos_token_id=None,
        default_max_new_tokens=8,
    )
    defaults.update(kwargs)
    return DecodeEngine(model, params, queue, **defaults), queue


def submit(queue, prompt, **payload):
    req = Request(
        model="llama_tiny",
        payload={"tokens": np.asarray(prompt, dtype=np.int32), **payload},
        slo_ms=60_000.0,
    )
    queue.add_request(req)
    return req


def substeps_to_admission(engine, queue):
    """Worst-case substeps a fresh arrival waits while slots are FREE:
    one request decoding, queue empty — the engine commits to a scan of
    ``_pick_horizon()`` substeps; the arrival lands just after dispatch and
    must wait out the whole scan before the next admission point."""
    submit(queue, [1, 2, 3], max_new_tokens=500)
    assert engine._admit() == 1
    engine._drain_prefill()             # first token: the slot is active
    h = engine._pick_horizon()          # chosen with queue empty,
    steps0 = engine.steps               # slots free — the in-flight scan
    engine._step(horizon=h)             # ...during which B arrives
    req_b = submit(queue, [4, 5, 6], max_new_tokens=2)
    waited = engine.steps - steps0      # substeps between arrival & the
    assert engine._admit() == 1         # loop's next admission point
    assert req_b.admit_ms is not None
    return waited


class TestAdmissionBound:
    def test_ttft_tier_bounds_admission_wait(self, lm):
        """With slots free + queue empty the engine scans only
        ``ttft_horizon`` substeps, so an arrival mid-scan is admitted
        within that bound — 4x tighter than the full horizon."""
        engine, queue = make_engine(lm, decode_horizon=16)
        assert engine.ttft_horizon == 4  # default: decode_horizon // 4
        waited = substeps_to_admission(engine, queue)
        assert waited <= engine.ttft_horizon

        # Control: a full-horizon policy (ttft tier disabled) pays the
        # whole scan before the same arrival can be admitted.
        full, queue2 = make_engine(lm, decode_horizon=16, ttft_horizon=16)
        waited_full = substeps_to_admission(full, queue2)
        assert waited_full == full.decode_horizon
        assert waited * 4 <= waited_full

    def test_three_tier_selection(self, lm):
        """Tier transitions: full scan only when the batch is full; single
        steps while requests wait for a slot; ttft tier when idle-queued."""
        engine, queue = make_engine(lm, num_slots=2, decode_horizon=16)
        submit(queue, [1, 2, 3], max_new_tokens=500)
        engine._admit()
        assert engine._pick_horizon() == 1          # a chunk train pending
        engine._drain_prefill()
        assert engine._pick_horizon() == engine.ttft_horizon  # free + empty
        submit(queue, [4, 5], max_new_tokens=500)
        assert engine._pick_horizon() == 1                    # queued + free
        engine._admit()
        engine._drain_prefill()                               # batch now full
        submit(queue, [6, 7], max_new_tokens=2)
        assert engine._pick_horizon() == engine.decode_horizon

    def test_horizon_one_engine_always_single_steps(self, lm):
        engine, _ = make_engine(lm, decode_horizon=1)
        assert engine._pick_horizon() == 1


class TestTTFTBreakdown:
    def test_parts_recorded_and_ordered(self, lm):
        engine, queue = make_engine(lm)
        for i in range(5):
            submit(queue, [1 + i, 2, 3], max_new_tokens=3)
        engine.run_until_idle()
        bd = engine.ttft_breakdown()
        assert bd["n"] == 5
        # Per-admission invariant scan_wait <= queue_wait dominates the
        # order statistics too.
        assert bd["queue_wait_ms_p50"] >= bd["scan_wait_ms_p50"] >= 0.0
        assert bd["prefill_ms_p50"] > 0.0
        assert bd["queue_wait_ms_p95"] >= bd["queue_wait_ms_p50"]

    def test_breakdown_sums_to_ttft(self, lm):
        """queue_wait + prefill reconstructs the recorded TTFT for a lone
        request (no concurrent scans: scan_wait is part of queue_wait,
        never additive)."""
        engine, queue = make_engine(lm)
        req = submit(queue, [1, 2, 3], max_new_tokens=2)
        engine.run_until_idle()
        result = req.future.result(timeout=30)
        (queue_wait, scan_wait, prefill) = engine._ttft_parts[-1]
        assert scan_wait <= queue_wait
        assert queue_wait + prefill == pytest.approx(result.ttft_ms, abs=1.0)

    def test_window_reset(self, lm):
        engine, queue = make_engine(lm)
        submit(queue, [1, 2, 3], max_new_tokens=2)
        engine.run_until_idle()
        assert engine.ttft_breakdown()["n"] == 1
        engine.reset_ttft_window()
        assert engine.ttft_breakdown() == {"n": 0}
