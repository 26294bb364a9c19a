"""Gray-failure defense tests (ISSUE 9): straggler detection against
peer consensus, the healthy->suspect->probation->ejected state machine,
probation routing/pricing, breaker slow strikes, and hedged dispatch —
including the at-most-once-after-first-token pin at the hedge boundary."""

import threading
import time

import pytest

from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request, TokenStream
from ray_dynamic_batching_tpu.scheduler.nexus import (
    NodePlan,
    Placement,
    Session,
)
from ray_dynamic_batching_tpu.scheduler.replan import derate_for_capacity
from ray_dynamic_batching_tpu.serve import Replica, Router
from ray_dynamic_batching_tpu.serve.failover import HedgePolicy
from ray_dynamic_batching_tpu.serve.grayhealth import (
    GrayHealthMonitor,
    GrayHealthPolicy,
    grade_observations,
)
from ray_dynamic_batching_tpu.serve.router import CircuitBreaker
from ray_dynamic_batching_tpu.utils.chaos import reset_chaos


@pytest.fixture(autouse=True)
def _clean_chaos():
    reset_chaos("")
    yield
    reset_chaos("")


# --- pure scoring -----------------------------------------------------------


class TestGrading:
    POLICY = GrayHealthPolicy(p50_ratio=3.0, p95_ratio=3.0, min_abs_ms=1.0,
                              min_samples=4, min_peers=2)

    def test_outlier_against_peer_median(self):
        verdicts = grade_observations({
            "r0": (100.0, 120.0, 10),
            "r1": (10.0, 12.0, 10),
            "r2": (11.0, 13.0, 10),
        }, self.POLICY)
        assert verdicts == {"r0": True, "r1": False, "r2": False}

    def test_p95_alone_can_flag(self):
        verdicts = grade_observations({
            "r0": (10.0, 500.0, 10),   # healthy median, rotten tail
            "r1": (10.0, 12.0, 10),
            "r2": (11.0, 13.0, 10),
        }, self.POLICY)
        assert verdicts["r0"] is True

    def test_too_few_samples_is_ungraded_not_guilty(self):
        verdicts = grade_observations({
            "r0": (100.0, 120.0, 2),   # below min_samples
            "r1": (10.0, 12.0, 10),
            "r2": (11.0, 13.0, 10),
            "r3": (10.0, 11.0, 10),
        }, self.POLICY)
        assert verdicts["r0"] is None
        # and r0 does NOT poison the peers' consensus
        assert verdicts["r1"] is False and verdicts["r2"] is False

    def test_too_few_peers_is_ungraded(self):
        # r1 lacks samples, so r0 has one graded peer < min_peers=2.
        verdicts = grade_observations({
            "r0": (100.0, 120.0, 10),
            "r1": (10.0, 12.0, 2),
            "r2": (11.0, 13.0, 10),
        }, self.POLICY)
        assert verdicts["r0"] is None and verdicts["r2"] is None

    def test_min_abs_floor_suppresses_ratio_noise(self):
        # 0.3 ms vs 0.05 ms peers is a 6x ratio — but under the 1 ms
        # floor it's timer jitter, not a straggler.
        verdicts = grade_observations({
            "r0": (0.3, 0.4, 10),
            "r1": (0.05, 0.06, 10),
            "r2": (0.05, 0.07, 10),
        }, self.POLICY)
        assert verdicts["r0"] is False


# --- hysteresis state machine ----------------------------------------------


def _mon(clock, **overrides):
    defaults = dict(min_samples=1, min_peers=1, suspect_after=2,
                    probation_after=2, heal_after=2, probe_interval_s=5.0)
    defaults.update(overrides)
    return GrayHealthMonitor("d", policy=GrayHealthPolicy(**defaults),
                             clock=clock)


OUTLIER = {"r0": (100.0, 100.0, 8), "r1": (10.0, 10.0, 8),
           "r2": (10.0, 10.0, 8)}
CLEAR = {"r0": (10.0, 10.0, 8), "r1": (10.0, 10.0, 8),
         "r2": (10.0, 10.0, 8)}


class TestGrayStateMachine:
    def setup_method(self):
        self.t = [0.0]
        self.mon = _mon(lambda: self.t[0])

    def _tick(self, obs, n=1):
        fired = []
        for _ in range(n):
            self.t[0] += 1.0
            fired.extend(self.mon.tick(obs))
        return fired

    def test_escalation_needs_consecutive_ticks(self):
        assert self._tick(OUTLIER) == []          # one tick is noise
        assert self.mon.state("r0") == "healthy"
        fired = self._tick(OUTLIER)               # second consecutive
        assert [t["to"] for t in fired] == ["suspect"]
        fired = self._tick(OUTLIER, n=2)
        assert [t["to"] for t in fired] == ["probation"]
        assert self.mon.state("r0") == "probation"
        assert self.mon.states()["r1"] == "healthy"

    def test_clear_tick_resets_the_streak(self):
        self._tick(OUTLIER)
        self._tick(CLEAR)                         # streak broken
        self._tick(OUTLIER)
        assert self.mon.state("r0") == "healthy"  # 1+1 never sums to 2

    def test_ungraded_tick_holds_state(self):
        self._tick(OUTLIER, n=2)
        assert self.mon.state("r0") == "suspect"
        starved = {"r0": (100.0, 100.0, 0), "r1": (10.0, 10.0, 8),
                   "r2": (10.0, 10.0, 8)}
        self._tick(starved, n=5)                  # no samples: no verdicts
        assert self.mon.state("r0") == "suspect"  # neither worse nor healed

    def test_probation_heals_after_clear_streak(self):
        self._tick(OUTLIER, n=4)
        assert self.mon.state("r0") == "probation"
        fired = self._tick(CLEAR, n=2)
        assert [t["to"] for t in fired] == ["healthy"]
        assert self.mon.capacity_factor("r0") == 1.0

    def test_eject_only_when_opted_in(self):
        self._tick(OUTLIER, n=20)
        assert self.mon.state("r0") == "probation"  # eject_after=0: never

    def test_eject_after_sustained_probation(self):
        self.mon = _mon(lambda: self.t[0], eject_after=3)
        self._tick(OUTLIER, n=4)
        assert self.mon.state("r0") == "probation"
        fired = self._tick(OUTLIER, n=3)
        assert [t["to"] for t in fired] == ["ejected"]
        assert self.mon.capacity_factor("r0") == 0.0
        assert not self.mon.is_candidate("r0")
        # terminal: clear ticks do not resurrect the verdict
        self._tick(CLEAR, n=10)
        assert self.mon.state("r0") == "ejected"

    def test_probation_probe_window(self):
        self._tick(OUTLIER, n=4)
        self.t[0] += 5.0                          # probe_interval_s elapses
        assert self.mon.is_candidate("r0")        # a probe is due
        self.mon.mark_probe("r0")
        assert not self.mon.is_candidate("r0")    # window consumed
        self.t[0] += 5.0                          # next window opens
        assert self.mon.is_candidate("r0")
        assert self.mon.capacity_factor("r0") == \
            self.mon.policy.probation_capacity

    def test_healthy_and_suspect_always_candidates(self):
        self._tick(OUTLIER, n=2)
        assert self.mon.state("r0") == "suspect"
        assert self.mon.is_candidate("r0") and self.mon.is_candidate("r1")

    def test_forget_resets_replacement_hardware(self):
        self._tick(OUTLIER, n=4)
        self.mon.forget("r0")
        assert self.mon.state("r0") == "healthy"

    def test_transitions_land_in_audit_ring(self):
        records = []

        class Ring:
            def record(self, trigger, **kw):
                records.append((trigger, kw))

        self.mon.audit = Ring()
        self._tick(OUTLIER, n=4)
        self._tick(CLEAR, n=2)
        triggers = [t for t, _ in records]
        assert triggers == ["gray_suspect", "gray_probation", "gray_heal"]
        assert records[1][1]["observed"]["replica"] == "r0"

    def test_snapshot_shape(self):
        self._tick(OUTLIER, n=2)
        snap = self.mon.snapshot()
        assert snap["states"]["r0"]["state"] == "suspect"
        assert snap["transitions"][-1]["to"] == "suspect"


# --- breaker slow strikes (PR-4 bugfix) -------------------------------------


class TestBreakerSlowStrikes:
    def test_slow_but_succeeding_replica_trips(self):
        """Pinned bugfix: successes used to reset ALL evidence, so a
        straggler whose every batch succeeded (slowly) held its breaker
        closed forever. Slow strikes accumulate ACROSS successes."""
        br = CircuitBreaker(threshold=3, cooldown_s=60.0, slow_threshold=3)
        assert br.record_slow() is None
        assert br.record_success() is False        # ordinary success...
        assert br.record_slow() is None
        assert br.snapshot()["slow_strikes"] == 2     # ...did NOT reset strikes
        assert br.record_slow() == 3               # trip edge
        assert br.snapshot()["state"] == "open"

    def test_open_breaker_does_not_stack_strikes(self):
        br = CircuitBreaker(slow_threshold=2, cooldown_s=60.0)
        br.record_slow()
        assert br.record_slow() == 2
        assert br.record_slow() is None            # capped: open accrues none
        assert br.snapshot()["slow_strikes"] == 0

    def test_half_open_recovery_clears_strikes(self):
        t = [0.0]
        br = CircuitBreaker(slow_threshold=2, cooldown_s=1.0,
                            clock=lambda: t[0])
        br.record_slow()
        br.record_slow()                           # open
        t[0] += 2.0                                # cooldown elapses
        assert br.eligible()                       # half-open probe allowed
        assert br.record_success() is True         # recovery edge
        st = br.snapshot()
        assert st["state"] == "closed" and st["slow_strikes"] == 0

    def test_router_records_slow_and_audits_trip(self):
        rep = Replica("r0", "d", lambda ps: [p * 2 for p in ps],
                      max_batch_size=1, batch_wait_timeout_s=0.002)
        router = Router("d", replicas=[rep], breaker_slow_threshold=2)
        records = []

        class Ring:
            def record(self, trigger, **kw):
                records.append((trigger, kw))

        router.audit = Ring()
        router.record_replica_slow("r0")
        assert router.breaker_states()["r0"]["slow_strikes"] == 1
        router.record_replica_slow("r0")
        assert router.breaker_states()["r0"]["state"] == "open"
        assert [t for t, _ in records] == ["breaker_trip"]
        assert records[0][1]["observed"]["slow_strikes"] == 2


# --- probation routing ------------------------------------------------------


def _tag_fn(tag):
    return lambda payloads: [tag for _ in payloads]


class TestProbationRouting:
    def _routed_pair(self):
        r0 = Replica("r0", "d", _tag_fn("r0"), max_batch_size=4,
                     batch_wait_timeout_s=0.002)
        r1 = Replica("r1", "d", _tag_fn("r1"), max_batch_size=4,
                     batch_wait_timeout_s=0.002)
        router = Router(
            "d", replicas=[r0, r1], max_assign_timeout_s=2.0,
            gray_policy=GrayHealthPolicy(
                min_samples=1, min_peers=1, suspect_after=1,
                probation_after=1, probe_interval_s=3600.0,
            ),
        )
        r0.start()
        r1.start()
        return r0, r1, router

    def _probation(self, router, rid):
        outlier = {"r0": (10.0, 10.0, 8), "r1": (10.0, 10.0, 8)}
        outlier[rid] = (500.0, 500.0, 8)
        router.gray.tick(outlier)
        router.gray.tick(outlier)
        assert router.gray.state(rid) == "probation"

    def test_probationed_replica_drained_from_pool(self):
        r0, r1, router = self._routed_pair()
        try:
            self._probation(router, "r0")
            router.gray.mark_probe("r0")   # probe slot consumed for an hour
            for i in range(6):
                req = Request(model="d", payload=i, slo_ms=10_000)
                assert router.assign_request(req)
                assert req.future.result(timeout=5) == "r1"
        finally:
            r0.stop()
            r1.stop()

    def test_due_probe_reaches_the_probationed_replica(self):
        r0, r1, router = self._routed_pair()
        try:
            self._probation(router, "r0")
            # never probed -> the probe is due: r0 stays in the pool until
            # one dispatch lands on it (which calls mark_probe).
            served = set()
            for i in range(24):
                req = Request(model="d", payload=i, slo_ms=10_000)
                assert router.assign_request(req)
                served.add(req.future.result(timeout=5))
            assert "r0" in served, "the probe never reached probation"
            # and after mark_probe the pool is r1-only again
            for i in range(6):
                req = Request(model="d", payload=i, slo_ms=10_000)
                assert router.assign_request(req)
                assert req.future.result(timeout=5) == "r1"
        finally:
            r0.stop()
            r1.stop()

    @pytest.mark.parametrize("uptime_s", [5.0, 10_000.0])
    def test_a_never_probed_replica_is_due_at_any_uptime(self, uptime_s):
        """``time.monotonic`` counts from the host's boot: on a host up
        for less than ``probe_interval_s`` a probationed replica that was
        never probed must still get its probe at once, and the next one
        only an interval after the first."""
        now = [uptime_s]
        mon = GrayHealthMonitor(
            "d", GrayHealthPolicy(min_samples=1, min_peers=1,
                                  suspect_after=1, probation_after=1,
                                  probe_interval_s=3600.0),
            clock=lambda: now[0])
        outlier = {"r0": (500.0, 500.0, 8), "r1": (10.0, 10.0, 8)}
        mon.tick(outlier)
        mon.tick(outlier)
        assert mon.state("r0") == "probation"
        assert mon.is_candidate("r0")
        mon.mark_probe("r0")
        assert not mon.is_candidate("r0")
        now[0] += 3599.0
        assert not mon.is_candidate("r0")
        now[0] += 1.0
        assert mon.is_candidate("r0")

    def test_all_probationed_falls_back_instead_of_blackholing(self):
        r0, r1, router = self._routed_pair()
        try:
            # Both replicas probationed, both probe slots burnt: a wrong
            # gray verdict must degrade latency, never blackhole.
            for rid in ("r0", "r1"):
                st = router.gray._st(rid)
                st.state = "probation"
                router.gray.mark_probe(rid)
            req = Request(model="d", payload=1, slo_ms=10_000)
            assert router.assign_request(req)
            assert req.future.result(timeout=5) in ("r0", "r1")
        finally:
            r0.stop()
            r1.stop()


# --- planner pricing (fractional capacity) ----------------------------------


def _plan(occ, duty=100.0, model="m"):
    s = Session(model=model, slo_ms=1000.0, rate_rps=10.0)
    return NodePlan(
        placements=[Placement(s, 8, occ * duty, occ, 0)],
        duty_cycle_ms=duty,
    )


class TestDerateForCapacity:
    def test_full_capacity_is_untouched(self):
        assignment = [_plan(0.9), _plan(0.5)]
        moved = derate_for_capacity(assignment, [1.0, 1.0])
        assert moved == {}
        assert assignment[0].occupancy == pytest.approx(0.9)

    def test_fitting_plan_stays_on_probationed_engine(self):
        assignment = [_plan(0.3), _plan(0.9)]
        moved = derate_for_capacity(assignment, [0.35, 1.0])
        assert moved == {}                      # 0.3 fits under 0.35

    def test_overfull_plan_swaps_with_lightest_fitting_peer(self):
        heavy, light = _plan(0.9, model="heavy"), _plan(0.3, model="light")
        assignment = [heavy, light]
        moved = derate_for_capacity(assignment, [0.35, 1.0])
        assert moved == {0: {"swapped_with": 1}}
        assert assignment[0] is light and assignment[1] is heavy

    def test_no_swap_candidate_folds_onto_least_occupied_peer(self):
        a, b, c = (_plan(0.9, model="a"), _plan(0.8, model="b"),
                   _plan(0.5, model="c"))
        assignment = [a, b, c]
        moved = derate_for_capacity(assignment, [0.35, 1.0, 1.0])
        assert moved == {0: {"folded_into": 2}}
        assert assignment[0] is None
        folded = assignment[2]
        assert sorted(folded.models) == ["a", "c"]
        # occupancy rescaled, absolute slice milliseconds preserved
        assert folded.duty_cycle_ms == pytest.approx(200.0)

    def test_no_full_capacity_host_keeps_the_plan(self):
        # Slow beats starved: with every engine degraded, nothing moves.
        assignment = [_plan(0.9), _plan(0.8)]
        moved = derate_for_capacity(assignment, [0.35, 0.5])
        assert moved == {}
        assert assignment[0].occupancy == pytest.approx(0.9)

    def test_decide_replan_validates_factor_arity(self):
        from ray_dynamic_batching_tpu.scheduler.replan import decide_replan
        from tests.test_sim_parity import make_packer

        packer = make_packer()
        with pytest.raises(ValueError, match="capacity_factors"):
            decide_replan(packer, [frozenset(), frozenset()], [], {},
                          capacity_factors=[1.0])


# --- hedged dispatch --------------------------------------------------------


class TestHedgedDispatch:
    def _pair(self, fn, hedge=HedgePolicy(min_threshold_ms=40.0),
              **router_kw):
        r0 = Replica("r0", "d", fn, max_batch_size=1,
                     batch_wait_timeout_s=0.002)
        r1 = Replica("r1", "d", fn, max_batch_size=1,
                     batch_wait_timeout_s=0.002)
        router = Router("d", replicas=[r0, r1], max_assign_timeout_s=2.0,
                        hedge_policy=hedge, **router_kw)
        r0.start()
        r1.start()
        return r0, r1, router

    def _teardown(self, r0, r1, router):
        router.close()
        r0.stop()
        r1.stop()

    @staticmethod
    def _interactive(payload, slo_ms=10_000):
        return Request(model="d", payload=payload, slo_ms=slo_ms,
                       qos_class="interactive")

    def _settle(self, router, timeout=5.0):
        """Wait until every dispatched hedge settled (won+lost+late ==
        fired) so outcome assertions don't race the loser's callback."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            s = router.hedge.stats()
            if s["won"] + s["lost"] + s["late"] >= s["fired"] > 0:
                return s
            time.sleep(0.01)
        return router.hedge.stats()

    def test_hedge_wins_when_primary_stalls(self):
        gate = threading.Event()
        state = {"calls": 0}
        lock = threading.Lock()

        def first_call_stalls(payloads):
            with lock:
                state["calls"] += 1
                me = state["calls"]
            if me == 1:
                gate.wait(5.0)
            return [f"call{me}" for _ in payloads]

        r0, r1, router = self._pair(first_call_stalls)
        try:
            req = self._interactive(1)
            assert router.assign_request(req)
            # the hedge (call 2) must deliver while the primary stalls
            assert req.future.result(timeout=5) == "call2"
            gate.set()
            s = self._settle(router)
            assert s["won"] == 1 and s["late"] == 0
            assert s["armed"] == s["fired"] == s["dispatched"] == 1
            # conservation: fired == dispatched + late, dispatched == won+lost
            assert s["fired"] == s["dispatched"] + s["late"]
            assert s["dispatched"] == s["won"] + s["lost"]
            # the stalled primary took a slow strike (breaker evidence)
            assert sum(b["slow_strikes"] + (b["state"] != "closed")
                       for b in router.breaker_states().values()) >= 1
        finally:
            gate.set()
            self._teardown(r0, r1, router)

    def test_hedge_loses_when_primary_finishes_first(self):
        gate = threading.Event()
        state = {"calls": 0}
        lock = threading.Lock()

        def second_call_stalls(payloads):
            with lock:
                state["calls"] += 1
                me = state["calls"]
            if me == 1:
                time.sleep(0.12)          # slow enough to arm + fire
            else:
                gate.wait(5.0)            # the hedge arm wedges
            return [f"call{me}" for _ in payloads]

        r0, r1, router = self._pair(second_call_stalls)
        try:
            req = self._interactive(1)
            assert router.assign_request(req)
            assert req.future.result(timeout=5) == "call1"
            gate.set()
            s = self._settle(router)
            assert s["lost"] == 1 and s["won"] == 0
            assert s["fired"] == s["dispatched"] + s["late"]
            assert s["dispatched"] == s["won"] + s["lost"]
        finally:
            gate.set()
            self._teardown(r0, r1, router)

    def test_timer_on_completed_request_is_late_not_dispatched(self):
        r0, r1, router = self._pair(
            lambda ps: [p * 2 for p in ps],
            hedge=HedgePolicy(min_threshold_ms=80.0),
        )
        try:
            req = self._interactive(21)
            assert router.assign_request(req)
            assert req.future.result(timeout=5) == 42
            s = self._settle(router)
            assert s["late"] == 1 and s["dispatched"] == 0
            assert s["fired"] == s["dispatched"] + s["late"]
        finally:
            self._teardown(r0, r1, router)

    def test_first_emitted_chunk_pins_out_the_hedge(self):
        """The at-most-once-after-first-token boundary: a stream that
        produced a chunk is NEVER hedged, however slow the rest is."""
        def gen(payloads):
            yield ["tok0" for _ in payloads]
            time.sleep(0.15)              # straggles AFTER first token
            yield ["tok1" for _ in payloads]

        r0, r1, router = self._pair(gen)
        try:
            req = self._interactive(1)
            req.stream = TokenStream()
            assert router.assign_request(req)
            assert req.future.result(timeout=5) == ["tok0", "tok1"]
            assert list(req.stream) == ["tok0", "tok1"]  # no duplication
            s = self._settle(router)
            assert s["dispatched"] == 0 and s["late"] == 1
            assert req.attempts == 1
        finally:
            self._teardown(r0, r1, router)

    def test_standard_class_is_not_hedged(self):
        gate = threading.Event()

        def stall_all(payloads):
            gate.wait(0.15)
            return [p for p in payloads]

        r0, r1, router = self._pair(stall_all)
        try:
            req = Request(model="d", payload=1, slo_ms=10_000,
                          qos_class="standard")
            assert router.assign_request(req)
            assert req.future.result(timeout=5) == 1
            assert router.hedge.stats()["armed"] == 0
        finally:
            gate.set()
            self._teardown(r0, r1, router)

    def test_queued_loser_frees_accounting_exactly_once(self):
        """The loser-cancellation conservation pin: a hedge shadow still
        QUEUED when the primary wins is discarded at pop time, counted
        dropped exactly once — enqueued == completed + stale + dropped +
        depth holds on the loser's queue."""
        blocker_gate = threading.Event()
        state = {"calls": 0}
        lock = threading.Lock()

        def fn(payloads):
            with lock:
                state["calls"] += 1
                me = state["calls"]
            if payloads == ["blocker"]:
                blocker_gate.wait(5.0)
                return ["blocked" for _ in payloads]
            if me <= 2:                   # the blocker + the primary
                time.sleep(0.12)
            return [f"call{me}" for _ in payloads]

        r0, r1, router = self._pair(fn)
        try:
            # Wedge r1 so the hedge shadow queues behind the blocker.
            blocker = Request(model="d", payload="blocker", slo_ms=30_000)
            assert r1.assign(blocker)
            time.sleep(0.02)              # blocker enters execution
            req = self._interactive(1)
            assert router.assign_request(req, exclude={"r1"})  # primary=r0
            assert req.future.result(timeout=5).startswith("call")
            s = self._settle(router)
            assert s["dispatched"] == 1 and s["lost"] == 1
            blocker_gate.set()
            assert blocker.future.result(timeout=5) == "blocked"
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                st = r1.queue.stats()
                if st["depth"] == 0.0 and st["dropped"] == 1.0:
                    break
                time.sleep(0.01)
            st = r1.queue.stats()
            assert st["enqueued"] == 2.0          # blocker + shadow
            assert st["completed"] == 1.0         # the blocker
            assert st["dropped"] == 1.0           # the cancelled shadow
            assert st["stale"] == 0.0 and st["depth"] == 0.0
            assert st["enqueued"] == (st["completed"] + st["stale"]
                                      + st["dropped"] + st["depth"])
        finally:
            blocker_gate.set()
            self._teardown(r0, r1, router)

    def test_single_replica_never_arms(self):
        rep = Replica("r0", "d", lambda ps: ps, max_batch_size=1,
                      batch_wait_timeout_s=0.002)
        router = Router("d", replicas=[rep],
                        hedge_policy=HedgePolicy(min_threshold_ms=1.0))
        rep.start()
        try:
            req = self._interactive([1])
            assert router.assign_request(req)
            req.future.result(timeout=5)
            assert router.hedge.stats()["armed"] == 0
        finally:
            router.close()
            rep.stop()

    def test_hedge_shadow_is_never_rehedged(self):
        req = self._interactive(1)
        shadow = Request(model="d", payload=1, slo_ms=10_000,
                         qos_class="interactive", is_hedge=True)
        r0, r1, router = self._pair(lambda ps: ps)
        try:
            assert router.hedge.eligible(req)
            assert not router.hedge.eligible(shadow)
        finally:
            self._teardown(r0, r1, router)

    def test_lost_primary_output_never_reaches_the_client(self):
        """Two-source suppression: once the shadow claims, the LOSING
        primary's resumed tokens must not interleave with the grafted
        shadow stream, and its completion must not resolve the future
        or close the stream early (truncating the winner)."""
        gate = threading.Event()
        state = {"calls": 0}
        lock = threading.Lock()

        def gen(payloads):
            with lock:
                state["calls"] += 1
                me = state["calls"]
            if me == 1:
                gate.wait(5.0)            # stalls past the hedge bar
                yield ["p-tok" for _ in payloads]   # resumes as loser
            else:
                yield ["s-tok0" for _ in payloads]  # shadow claims here
                gate.set()                # wake the loser MID-stream
                time.sleep(0.15)          # let it emit + complete
                yield ["s-tok1" for _ in payloads]

        r0, r1, router = self._pair(gen)
        try:
            req = self._interactive(1)
            req.stream = TokenStream()
            assert router.assign_request(req)
            assert req.future.result(timeout=5) == ["s-tok0", "s-tok1"]
            assert list(req.stream) == ["s-tok0", "s-tok1"]
            s = self._settle(router)
            assert s["won"] == 1 and s["lost"] == 0
        finally:
            gate.set()
            self._teardown(r0, r1, router)

    def test_assign_stamps_current_replica_for_the_hedge_timer(self):
        """The hedge timer follows a failover re-dispatch: every
        successful assign stamps the request's live location, which the
        fire path reads instead of the replica captured at arm time."""
        r0, r1, router = self._pair(lambda ps: ps)
        try:
            req = self._interactive(1)
            assert router.assign_request(req, exclude={"r1"})
            assert req._assigned_replica == "r0"
            req.future.result(timeout=5)
        finally:
            self._teardown(r0, r1, router)

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_shadow_claims_then_fails_rejects_client(self):
        """The claimed-then-failed hole: the shadow wins the first-token
        claim (primary cancelled), then its own stream dies. The client
        future must be REJECTED — the cancelled primary is discarded at
        queue pop without resolving it, so nothing else ever will."""
        gate = threading.Event()
        state = {"calls": 0}
        lock = threading.Lock()

        def gen(payloads):
            with lock:
                state["calls"] += 1
                me = state["calls"]
            if me == 1:
                gate.wait(5.0)            # primary: emits nothing
                yield ["p-tok" for _ in payloads]
            else:
                yield ["s-tok" for _ in payloads]   # shadow claims here
                raise RuntimeError("shadow replica died mid-stream")

        r0, r1, router = self._pair(gen)
        try:
            req = self._interactive(1)
            req.stream = TokenStream()
            assert router.assign_request(req)
            with pytest.raises(Exception):
                req.future.result(timeout=5)        # must not hang
            s = self._settle(router)
            assert s["dispatched"] == 1 and s["lost"] == 1
            assert s["won"] == 0
            assert s["fired"] == s["dispatched"] + s["late"]
        finally:
            gate.set()
            self._teardown(r0, r1, router)


class TestRedeployGrayKnobs:
    def test_redeploy_applies_hedge_and_eject_knobs(self):
        """Redeploying an existing deployment must reprice the ROUTER's
        gray/hedge knobs, not just record the new config: hedge on/off
        and gray_eject_after all take effect without a restart."""
        from ray_dynamic_batching_tpu.serve.controller import (
            DeploymentConfig,
            ServeController,
        )

        ctl = ServeController(control_interval_s=3600.0)
        router = ctl.deploy(
            DeploymentConfig(name="d", num_replicas=1),
            factory=lambda: (lambda ps: ps),
        )
        try:
            assert router.hedge is None
            assert router.gray.policy.eject_after == 0
            ctl.deploy(DeploymentConfig(
                name="d", num_replicas=1,
                hedge_interactive=True, gray_eject_after=3,
            ))
            assert router.hedge is not None
            assert router.gray.policy.eject_after == 3
            ctl.deploy(DeploymentConfig(name="d", num_replicas=1))
            assert router.hedge is None
            assert router.gray.policy.eject_after == 0
        finally:
            ctl.shutdown()


class TestLiveGrayProducer:
    def test_live_scheduler_detects_and_reprices_straggler(self):
        """The LIVE capacity_factors producer (ISSUE 9 review gap):
        enable_gray_monitoring arms ReplicaEngine.track_ratios, grades
        each monitor tick's observed/expected step ratios with the same
        detector/rule the sim uses, wires capacity_factors, and a
        probation verdict fires a 'gray' replan that reprices the
        straggler as a fractional chip."""
        from ray_dynamic_batching_tpu.engine.host import ModelHost
        from ray_dynamic_batching_tpu.engine.queue import QueueManager
        from ray_dynamic_batching_tpu.engine.worker import ReplicaEngine
        from ray_dynamic_batching_tpu.profiles.table import (
            BatchProfile,
            ProfileRow,
        )
        from ray_dynamic_batching_tpu.scheduler.control import LiveScheduler
        from ray_dynamic_batching_tpu.scheduler.nexus import SquishyBinPacker

        rows = [
            ProfileRow(b, 16, latency_ms=2.0, latency_std_ms=0.0,
                       hbm_bytes=50_000_000, compile_ms=100.0)
            for b in (1, 2, 4, 8)
        ]
        profiles = {"m": BatchProfile("m", rows)}
        queues = QueueManager()
        host = ModelHost()
        engines = [ReplicaEngine(f"e{i}", queues, host) for i in range(3)]
        sched = LiveScheduler(
            SquishyBinPacker(profiles, hbm_budget_bytes=16 << 30),
            engines, queues=queues,
        )
        sched.register_model("m", slo_ms=5000.0, seq_len=16)
        sched.enable_gray_monitoring(
            policy=GrayHealthPolicy(min_samples=4, min_peers=2,
                                    suspect_after=2, probation_after=2,
                                    heal_after=2)
        )
        assert all(e.track_ratios for e in engines)
        assert sched.capacity_factors is not None

        def feed(straggler_ratio):
            for e in engines:
                ratio = straggler_ratio if e.engine_id == "e0" else 1.0
                e._fresh_ratios.extend([ratio] * 4)

        # Healthy ticks: no transitions, no gray replan.
        feed(1.0)
        assert not sched.check_gray_health()
        before = sched.schedule_changes
        # Outlier ticks: 2 -> suspect (no repricing replan), 2 more ->
        # probation (replan fires, straggler priced fractional).
        for _ in range(4):
            feed(10.0)
            sched.check_gray_health()
        assert sched.gray.state("e0") == "probation"
        assert sched.gray.states()["e1"] == "healthy"
        factors = sched.capacity_factors()
        assert factors["e0"] < 1.0 and factors["e1"] == 1.0
        assert sched.schedule_changes == before + 1  # probation only
        gray_audits = [a for a in sched.audit.to_dicts()
                       if a["trigger"] == "gray"]
        assert gray_audits and (
            min(gray_audits[-1]["observed"]["capacity_factors"]) < 1.0
        )
        # Heal: the tick window (3 ticks) must flush the outlier
        # samples first, then heal_after clear verdicts readmit.
        for _ in range(4):
            feed(1.0)
            sched.check_gray_health()
        assert sched.gray.state("e0") == "healthy"
        assert sched.capacity_factors()["e0"] == 1.0


class TestCancelledQueueDiscard:
    def test_cancelled_request_discarded_and_counted_once(self):
        q = RequestQueue("m", max_len=16)
        reqs = [Request(model="m", payload=i, slo_ms=10_000)
                for i in range(3)]
        for r in reqs:
            assert q.add_request(r)
        reqs[1].cancel()
        batch = q.get_batch(10)
        assert [r.payload for r in batch] == [0, 2]
        q.record_batch_completion(batch)
        st = q.stats()
        assert st["enqueued"] == 3.0 and st["dropped"] == 1.0
        assert st["completed"] == 2.0 and st["depth"] == 0.0
        assert st["enqueued"] == (st["completed"] + st["stale"]
                                  + st["dropped"] + st["depth"])
        # the discard resolved nothing: the winner owns the future
        assert not reqs[1].future.done()

    def test_first_emit_hook_fires_exactly_once(self):
        hits = []
        stream = TokenStream()
        stream.on_first_emit = lambda: hits.append(1)
        stream.put("a")
        stream.put("b")
        stream.close()
        stream.put("late")
        assert hits == [1]
        assert stream.emitted == 2


# --- sim: degradations, detection, scenarios --------------------------------


class TestEngineDegradationSpec:
    def test_probe_ratio_includes_stall(self):
        """A stall-only straggler (factor 1.0, stall_ms > 0) must grade
        as an outlier on the synthetic probation probe — slow_factor
        alone would read 1.0 and prematurely readmit it."""
        from ray_dynamic_batching_tpu.sim.clock import (
            EventLoop,
            VirtualClock,
        )
        from ray_dynamic_batching_tpu.sim.engine import SimEngine
        from ray_dynamic_batching_tpu.sim.queue import SimQueueManager

        clock = VirtualClock()
        eng = SimEngine("chip0", SimQueueManager(clock), {},
                        EventLoop(clock), clock)
        eng._last_expected_ms = 20.0
        assert eng.probe_ratio() == 1.0
        eng.degrade(factor=1.0, stall_ms=100.0)
        assert eng.probe_ratio() == pytest.approx(6.0)   # (20+100)/20
        eng.degrade(factor=10.0)
        assert eng.probe_ratio() == pytest.approx(10.0)
        eng.heal_degradation()
        assert eng.probe_ratio() == 1.0

    def test_validation(self):
        from ray_dynamic_batching_tpu.sim.simulator import EngineDegradation

        with pytest.raises(ValueError, match="factor"):
            EngineDegradation(at_s=1.0, engine=0, factor=0.5)
        with pytest.raises(ValueError, match="heal_at_s"):
            EngineDegradation(at_s=5.0, engine=0, factor=2.0, heal_at_s=4.0)
        with pytest.raises(ValueError, match="unknown degradation key"):
            EngineDegradation.from_dict({"at_s": 1.0, "engine": 0,
                                         "factr": 2.0})

    def test_dict_roundtrip(self):
        from ray_dynamic_batching_tpu.sim.simulator import EngineDegradation

        g = EngineDegradation.from_dict(
            {"at_s": 8.0, "engine": 1, "factor": 10.0, "heal_at_s": 20.0}
        )
        assert (g.engine, g.factor, g.heal_at_s) == (1, 10.0, 20.0)

    def test_out_of_range_engine_rejected(self):
        from ray_dynamic_batching_tpu.sim.scenarios import fixture_profiles
        from ray_dynamic_batching_tpu.sim.simulator import (
            EngineDegradation,
            Scenario,
            SimModelSpec,
            Simulation,
        )
        from ray_dynamic_batching_tpu.engine.workload import RatePattern

        sc = Scenario(
            models=[SimModelSpec(name="fast", slo_ms=200.0,
                                 pattern=RatePattern("constant",
                                                     base_rps=5.0))],
            duration_s=1.0, n_engines=1,
            degradations=[EngineDegradation(at_s=0.5, engine=3,
                                            factor=2.0)],
        )
        with pytest.raises(ValueError, match="engine 3"):
            Simulation(fixture_profiles(), sc).run()

    def test_unknown_gray_key_rejected(self):
        from ray_dynamic_batching_tpu.sim.simulator import Scenario

        sc = Scenario(models=[], gray={"p50_ratioo": 3.0})
        with pytest.raises(ValueError, match="unknown gray key"):
            sc.gray_policy()


@pytest.mark.slow
class TestStragglerScenario:
    """The straggler conformance story (sim arm of the soak gate):
    detection within the tick budget, probation repricing, heal
    readmission — byte-deterministically."""

    DETECT_TICK_BUDGET = 12   # monitor ticks from onset to probation

    @classmethod
    def _report(cls):
        from ray_dynamic_batching_tpu.sim.scenarios import (
            fixture_profiles,
            straggler_scenario,
        )
        from ray_dynamic_batching_tpu.sim.simulator import Simulation

        if not hasattr(cls, "_cached"):
            cls._cached = Simulation(
                fixture_profiles(), straggler_scenario()
            ).run()
        return cls._cached

    def test_byte_deterministic(self):
        from ray_dynamic_batching_tpu.sim import render_json
        from ray_dynamic_batching_tpu.sim.scenarios import (
            fixture_profiles,
            straggler_scenario,
        )
        from ray_dynamic_batching_tpu.sim.simulator import Simulation

        blobs = [
            render_json(Simulation(fixture_profiles(),
                                   straggler_scenario()).run())
            for _ in range(2)
        ]
        assert blobs[0] == blobs[1]

    def test_probation_within_tick_budget_then_reclaim(self):
        report = self._report()
        sc_onset, sc_heal, tick_s = 8.0, 20.0, 1.0
        by_state = {}
        for t in report["gray"]["timeline"]:
            assert t["replica"] == "chip0"   # only the straggler moves
            by_state.setdefault(t["to"], t["at"])
        assert "probation" in by_state, report["gray"]["timeline"]
        ticks = (by_state["probation"] - sc_onset) / tick_s
        assert 0 < ticks <= self.DETECT_TICK_BUDGET
        # reclaimed on heal: back to healthy AFTER the injected heal
        assert by_state.get("healthy", 0.0) > sc_heal
        assert report["gray"]["final_states"] == {
            "chip0": "healthy", "chip1": "healthy", "chip2": "healthy"
        }
        assert report["chips"]["chip0"]["gray_state"] == "healthy"
        assert report["chips"]["chip0"]["degraded"] is False

    def test_interactive_attainment_floor_holds(self):
        report = self._report()
        classes = report["models"]["fast"]["classes"]
        assert classes["interactive"]["slo_attainment"] >= 0.97
        # accounting conserves per model through the whole episode
        for name, s in report["models"].items():
            assert s["arrivals"] == (s["completed"] + s["stale"]
                                     + s["dropped"] + s["pending"]), name

    def test_gray_replan_repriced_the_straggler(self):
        report = self._report()
        gray_replans = [a for a in report["audit"]
                        if a["trigger"] == "gray"]
        assert gray_replans, "probation never forced a replan"
        factors = next(
            (a["observed"]["capacity_factors"] for a in gray_replans
             if "capacity_factors" in a.get("observed", {})), None
        )
        assert factors is not None and min(factors) < 1.0

    def test_gray_timeline_report_block(self):
        from ray_dynamic_batching_tpu.sim.report import (
            format_gray_timeline,
            gray_timeline,
        )

        report = self._report()
        timeline = gray_timeline(report)
        assert list(timeline) == ["chip0"]
        assert [t["to"] for t in timeline["chip0"]][:2] == [
            "suspect", "probation"
        ]
        text = format_gray_timeline(report)
        assert "chip0" in text and "probation" in text
        assert "final:" in text

    def test_timeline_empty_without_gray_detection(self):
        from ray_dynamic_batching_tpu.sim.report import (
            format_gray_timeline,
            gray_timeline,
        )

        assert gray_timeline({"gray": None}) == {}
        assert "disabled" in format_gray_timeline({})


@pytest.mark.slow
class TestCorrelatedFailureScenario:
    def test_rack_event_heals_over_survivors(self):
        from ray_dynamic_batching_tpu.sim import render_json
        from ray_dynamic_batching_tpu.sim.scenarios import (
            correlated_failure_scenario,
            fixture_profiles,
        )
        from ray_dynamic_batching_tpu.sim.simulator import Simulation

        blobs = [
            render_json(Simulation(fixture_profiles(),
                                   correlated_failure_scenario()).run())
            for _ in range(2)
        ]
        assert blobs[0] == blobs[1]
        import json as _json

        report = _json.loads(blobs[0])
        dead = [c for c, v in report["chips"].items() if not v["alive"]]
        assert sorted(dead) == ["chip0", "chip1"]
        triggers = [a["trigger"] for a in report["audit"]]
        assert triggers.count("heal") >= 1
        for name, s in report["models"].items():
            assert s["arrivals"] == (s["completed"] + s["stale"]
                                     + s["dropped"] + s["pending"]), name
            assert s["pending"] == 0
            # comfortable provisioning: the event costs detection-window
            # sheds, never a collapse
            assert s["slo_attainment"] >= 0.9, name
