"""Randomized colocation control-plane stress (fast lane — fake engines).

The slow-lane colocate tests prove real XLA engines execute plans; this
fuzz drives the REAL executor (ColocatedLLMEngines: draining renames,
identity pops, busy accounting) and REAL control loop (LLMLiveScheduler)
through hundreds of random rate shifts, submissions, and executor passes
with an instantly-serving fake engine, holding the invariants that make
migration safe:

- a model under demand is admitted by EXACTLY ONE chip (draining
  predecessors may linger, but only one engine admits from its queue);
- every submitted request terminates (served or rejected) — migration
  storms must never strand a future;
- released engines stay released (no resurrection of freed HBM);
- shutdown terminates everything.
"""

import random

import numpy as np
import pytest

from ray_dynamic_batching_tpu.engine.colocate import ColocatedLLMEngines
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.profiles.table import BatchProfile, ProfileRow
from ray_dynamic_batching_tpu.scheduler.llm_control import LLMLiveScheduler

GB = 1 << 30
MODELS = ("a", "b", "c")


class InstantEngine:
    """Serves every queued request in one 'scan' — the executor-facing
    surface of DecodeEngine with zero XLA."""

    def __init__(self, model_name, num_slots, max_len, queue):
        self.num_slots = num_slots
        self.max_len = max_len
        self.queue = queue
        self.model = type("M", (), {"name": model_name})()
        self._thread = None
        self._active_mask = np.zeros((num_slots,), dtype=bool)
        self._pending = []
        self.last_heartbeat = 0.0
        self.released = False
        self.served = 0

    def _device_ctx(self):
        import contextlib

        return contextlib.nullcontext()

    def _admit(self) -> int:
        batch = self.queue.get_batch(self.num_slots, discard_stale=False)
        self._pending.extend(batch)
        self._active_mask[: min(len(self._pending), self.num_slots)] = True
        return len(batch)

    def _pump_prefill(self) -> int:
        return 0  # admission is instant: no chunk train ever pends

    def _step(self, horizon=None) -> None:
        assert not self.released, "stepped after release_buffers"
        for req in self._pending:
            req.fulfill({"tokens": [1], "served_by": self.model.name})
            self.served += 1
        self._pending = []
        self._active_mask[:] = False

    @property
    def active_slots(self) -> int:
        return int(self._active_mask.sum())

    @property
    def busy(self) -> bool:
        return bool(self._pending)

    def abort_active(self, exc) -> None:
        for req in self._pending:
            req.reject(exc)
        self._pending = []
        self._active_mask[:] = False

    def release_buffers(self) -> None:
        self.released = True


def profile(name):
    return BatchProfile(f"{name}_decode", [
        ProfileRow(batch_size=4, seq_len=128, latency_ms=10.0,
                   latency_std_ms=0.0, hbm_bytes=GB, compile_ms=10.0),
    ])


def rate_for(fraction):
    return fraction * 1000.0 * 4 / 10.0  # slots=4, step=10ms


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_random_rate_storm_holds_invariants(seed):
    rng = random.Random(seed)
    profiles = {m: profile(m) for m in MODELS}
    chips = [ColocatedLLMEngines(name=f"chip{i}") for i in range(3)]
    engines = []

    def factory(model, placement, queue, device):
        e = InstantEngine(model, placement.num_slots, placement.capacity,
                          queue)
        engines.append(e)
        return e

    sched = LLMLiveScheduler(profiles, chips, factory)
    for m in MODELS:
        sched.register_model(m, token_slo_ms=1000.0)

    submitted = []
    for step in range(120):
        op = rng.random()
        if op < 0.45:
            # Random feasible demand vector (each fraction < headroom).
            rates = {m: rate_for(rng.choice([0.0, 0.2, 0.4, 0.6, 0.8]))
                     for m in MODELS}
            sched.rebalance(rates=rates)
        elif op < 0.75:
            m = rng.choice(MODELS)
            req = Request(model=m, payload={"tokens": [1, 2],
                                            "max_new_tokens": 4},
                          slo_ms=600_000.0)
            sched.submit_request(req)
            submitted.append(req)
        else:
            for chip in chips:
                chip.step_once()

        # Invariant: at most one NON-DRAINING engine per model across
        # the cluster (the shared queue must never feed two admitters).
        hosted = [m for chip in chips for m in chip.models()]
        assert len(hosted) == len(set(hosted)), f"double-hosted: {hosted}"

    # Every model with pending work gets served: plan for all, drain.
    sched.rebalance(rates={m: rate_for(0.3) for m in MODELS})
    for _ in range(10):
        for chip in chips:
            chip.step_once()
    for req in submitted:
        res = req.future.result(timeout=5)  # raises if stranded/rejected
        assert res["served_by"] == req.model

    # Released engines never got stepped again (InstantEngine asserts),
    # and shutdown reclaims everything.
    sched.shutdown()
    assert all(not chip.models() for chip in chips)
    assert all(e.released for e in engines)


def test_migration_storm_preserves_queued_work():
    """Flip one model's demand between two chips repeatedly; queued
    requests survive every migration and serve exactly once."""
    profiles = {m: profile(m) for m in ("a", "b")}
    chips = [ColocatedLLMEngines(name=f"chip{i}") for i in range(2)]

    def factory(model, placement, queue, device):
        return InstantEngine(model, placement.num_slots,
                             placement.capacity, queue)

    sched = LLMLiveScheduler(profiles, chips, factory)
    for m in ("a", "b"):
        sched.register_model(m, token_slo_ms=1000.0)

    reqs = []
    for i in range(30):
        # Alternate between colocated and split plans: "a" migrates.
        f_a = 0.3 if i % 2 == 0 else 0.7
        sched.rebalance(rates={"a": rate_for(f_a), "b": rate_for(0.3)})
        req = Request(model="a", payload={"tokens": [i]}, slo_ms=600_000.0)
        sched.submit_request(req)
        reqs.append(req)
        if i % 3 == 0:
            for chip in chips:
                chip.step_once()
    for _ in range(5):
        for chip in chips:
            chip.step_once()
    served = [r.future.result(timeout=5) for r in reqs]
    assert len(served) == 30
    sched.shutdown()


def test_failing_engine_does_not_starve_cotenants():
    """A persistently-raising engine must not absorb every turn: the
    scheduler charges failed turns so co-tenants keep being selected
    (round-robin's liveness property, kept under deficit weighting)."""
    from ray_dynamic_batching_tpu.engine.queue import RequestQueue

    class BrokenEngine(InstantEngine):
        def _admit(self):
            raise RuntimeError("device wedged")

    chip = ColocatedLLMEngines(name="chip0")
    q_bad = RequestQueue("bad", max_len=16)
    q_bad.add_request(Request(model="bad", payload={"tokens": [1]},
                              slo_ms=600_000.0))
    chip.attach("bad", BrokenEngine("bad", 2, 64, q_bad))
    q_ok = RequestQueue("ok", max_len=16)
    reqs = []
    for i in range(4):
        r = Request(model="ok", payload={"tokens": [i]}, slo_ms=600_000.0)
        q_ok.add_request(r)
        reqs.append(r)
    chip.attach("ok", InstantEngine("ok", 2, 64, q_ok))
    for _ in range(12):
        chip.step_once()
    for r in reqs:
        assert r.future.result(timeout=1)["served_by"] == "ok"
    chip.shutdown()


def test_stalled_engine_is_replaced_and_backlog_served():
    """Failure detection on the colocation path: an engine that keeps
    failing its turns (stale heartbeat, work queued) is rebuilt by the
    control loop's health check, the swap happens at a pass boundary on
    the executor thread, and the shared queue's backlog flows to the
    successor — the decode analogue of replica heal."""
    import time

    from ray_dynamic_batching_tpu.engine.queue import RequestQueue

    class BrokenEngine(InstantEngine):
        def _admit(self):
            raise RuntimeError("device wedged")

    profiles = {"a": profile("a")}
    chips = [ColocatedLLMEngines(name="chip0", idle_wait_s=0.001)]
    built = []

    def factory(model, placement, queue, device):
        # First build is broken; the health-path rebuild works.
        cls = BrokenEngine if not built else InstantEngine
        e = cls(model, placement.num_slots, placement.capacity, queue)
        built.append(e)
        return e

    sched = LLMLiveScheduler(profiles, chips, factory)
    sched.register_model("a", token_slo_ms=1000.0)
    try:
        sched.rebalance(rates={"a": rate_for(0.3)})
        reqs = []
        for i in range(3):
            r = Request(model="a", payload={"tokens": [i]},
                        slo_ms=600_000.0)
            sched.submit_request(r)
            reqs.append(r)
        chips[0].start()
        time.sleep(0.3)  # broken turns accrue; heartbeat stays stale
        assert sched.check_engine_health(stall_timeout_s=0.2) == 1
        deadline = time.monotonic() + 5
        for r in reqs:
            res = r.future.result(timeout=max(0.1, deadline
                                              - time.monotonic()))
            assert res["served_by"] == "a"
        assert built[0].released, "failed predecessor must be released"
        assert sched.engine_replacements == 1
    finally:
        sched.shutdown()


def test_stale_replacement_is_dropped_not_resurrected():
    """A pending health swap whose model was migrated off the chip
    before the pass boundary must be discarded (releasing its warm
    buffers), not installed as a second admitter against the shared
    queue; detach likewise cancels a queued swap."""
    from ray_dynamic_batching_tpu.engine.queue import RequestQueue

    chip = ColocatedLLMEngines(name="chip0")
    q = RequestQueue("a", max_len=16)
    chip.attach("a", InstantEngine("a", 2, 64, q))
    successor = InstantEngine("a", 2, 64, q)
    chip.replace("a", successor)
    # The model migrates away before any pass boundary runs the swap.
    chip.detach("a", drain=False)
    assert successor.released, "cancelled successor must release"
    chip.step_once()
    assert chip.models() == [], "stale successor must not resurrect"

    # Overwritten pends release the dropped successor too.
    chip.attach("a", InstantEngine("a", 2, 64, q))
    s1 = InstantEngine("a", 2, 64, q)
    s2 = InstantEngine("a", 2, 64, q)
    chip.replace("a", s1)
    chip.replace("a", s2)
    assert s1.released and not s2.released
    # And shutdown reclaims a never-installed pend.
    chip.shutdown()
    assert s2.released


def test_wedged_chip_is_quarantined_and_models_replan():
    """Chip-level failure: an executor stuck inside a 'device call'
    stops completing passes; the health check writes the chip off (its
    HBM can't be freed safely), stops its admissions, and replans the
    models onto surviving chips — queued work flows to the
    replacements through the shared queues."""
    import threading
    import time

    wedge = threading.Event()

    class WedgedEngine(InstantEngine):
        def _admit(self):
            # Pop a request first: it is now in NEITHER the queue nor a
            # slot (the mid-admission window) when the wedge hits.
            self._admitting_batch = self.queue.get_batch(
                1, discard_stale=False
            )
            wedge.wait()  # the 'device call' that never returns
            return 0

    profiles = {"a": profile("a")}
    chips = [ColocatedLLMEngines(name=f"chip{i}", idle_wait_s=0.001)
             for i in range(2)]
    built = []

    def factory(model, placement, queue, device):
        cls = WedgedEngine if not built else InstantEngine
        e = cls(model, placement.num_slots, placement.capacity, queue)
        built.append(e)
        return e

    sched = LLMLiveScheduler(profiles, chips, factory)
    sched.chip_stall_timeout_s = 0.3
    sched.register_model("a", token_slo_ms=1000.0)
    try:
        sched.rebalance(rates={"a": rate_for(0.3)})
        host = next(c for c in chips if c.models())
        spare = next(c for c in chips if c is not host)
        for c in chips:
            c.start()
        req = Request(model="a", payload={"tokens": [1]}, slo_ms=600_000.0)
        sched.submit_request(req)
        time.sleep(0.6)  # host's loop is stuck inside _admit
        sched.check_engine_health()
        assert sched.chip_quarantines == 1
        assert host not in sched.chips and host in sched.quarantined
        # The request the wedged _admit popped (neither queued nor
        # slotted) must be rejected, not stranded forever.
        with pytest.raises(Exception):
            req.future.result(timeout=2)
        # New traffic serves from the replacement on the spare.
        req2 = Request(model="a", payload={"tokens": [2]},
                       slo_ms=600_000.0)
        sched.submit_request(req2)
        assert req2.future.result(timeout=5)["served_by"] == "a"
        assert "a" in spare.models()
    finally:
        wedge.set()  # un-wedge so the daemon thread exits
        sched.shutdown()


def test_dead_executor_thread_is_restarted():
    """An executor thread that EXITS (crash path) leaves intact engine
    state with no device call in flight: the health check restarts the
    loop instead of quarantining the chip."""
    import time

    profiles = {"a": profile("a")}
    chips = [ColocatedLLMEngines(name="chip0", idle_wait_s=0.001)]

    def factory(model, placement, queue, device):
        return InstantEngine(model, placement.num_slots,
                             placement.capacity, queue)

    sched = LLMLiveScheduler(profiles, chips, factory)
    sched.register_model("a", token_slo_ms=1000.0)
    try:
        sched.rebalance(rates={"a": rate_for(0.3)})
        chips[0].start()
        # Kill the loop the way a crash would leave it: thread handle
        # set, thread dead.
        chips[0]._run.clear()
        deadline = time.monotonic() + 5
        while chips[0].running and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not chips[0].running and chips[0]._thread is not None
        chips[0]._run.set()  # a crashed loop would leave _run set
        sched.check_engine_health()
        assert chips[0].running, "dead executor must be restarted"
        req = Request(model="a", payload={"tokens": [1]}, slo_ms=600_000.0)
        sched.submit_request(req)
        assert req.future.result(timeout=5)["served_by"] == "a"
    finally:
        sched.shutdown()
