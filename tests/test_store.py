"""Replicated controller store: txn semantics, lease/epoch fencing, and
the controller-failover contract (ISSUE 11 acceptance).

The acceptance pin lives in TestControllerFailover: a standby takes over
a crashed leader's deployments by replaying the epoch-fenced log and
ADOPTING the live data plane (same router object — clients' handles keep
working; same replica objects — nothing restarts), the failover is
audited with epoch numbers, and the deposed leader's post-lease write is
provably rejected (StaleEpochError), never silently applied.
"""

import threading
import time

import pytest

from ray_dynamic_batching_tpu.serve import (
    DeploymentConfig,
    DeploymentHandle,
    ServeController,
)
from ray_dynamic_batching_tpu.serve.store import (
    CompactedLogError,
    InMemoryStore,
    LeaderLease,
    ReplicaCatalog,
    ReplicatedStore,
    StaleEpochError,
    StoreLog,
    StoreSnapshot,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def double_batch(payloads):
    return [p * 2 for p in payloads]


class TestTxn:
    def test_commit_is_atomic_batch(self):
        s = InMemoryStore()
        with s.txn() as t:
            t.put("a", "1")
            t.put("b", "2")
            assert s.get("a") is None  # staged, not yet visible
            assert t.get("a") == "1"   # read-your-writes inside the txn
        assert s.get("a") == "1" and s.get("b") == "2"
        assert s.version == 1  # one commit, not two

    def test_noop_writes_are_elided(self):
        s = InMemoryStore()
        with s.txn() as t:
            t.put("k", "v")
        v0 = s.version
        with s.txn() as t:
            t.put("k", "v")  # unchanged value
        assert s.version == v0  # empty stage: nothing committed

    def test_exception_discards_the_stage(self):
        s = InMemoryStore()
        with pytest.raises(RuntimeError):
            with s.txn() as t:
                t.put("k", "v")
                raise RuntimeError("half-done reconcile")
        assert s.get("k") is None and s.version == 0

    def test_delete_and_put_json_canonical(self):
        s = InMemoryStore()
        with s.txn() as t:
            t.put_json("j", {"b": 1, "a": 2})
        assert s.get("j") == '{"a": 2, "b": 1}'  # sorted -> elidable
        with s.txn() as t:
            t.put_json("j", {"a": 2, "b": 1})  # same dict, other order
        assert s.version == 1
        with s.txn() as t:
            t.delete("j")
        assert s.get("j") is None


class TestLeaseAndLog:
    def test_new_holder_bumps_epoch_live_holder_blocks(self):
        clock = FakeClock()
        lease = LeaderLease(duration_s=5.0, clock=clock)
        assert lease.acquire("A") == 1
        assert lease.acquire("B") is None  # A's lease is live
        assert lease.acquire("A") == 1    # re-acquire keeps the epoch
        clock.advance(6.0)                # lapse
        assert lease.acquire("B") == 2    # takeover bumps
        assert not lease.renew("A")       # deposed holder cannot renew

    def test_log_fence_rejects_stale_epochs_atomically(self):
        log = StoreLog()
        log.append(1, [("put", "k", "v")])
        log.fence_to(2)
        with pytest.raises(StaleEpochError) as ei:
            log.append(1, [("put", "k", "w")])
        assert ei.value.epoch == 1 and ei.value.fence == 2
        assert log.rejected_appends == 1
        assert log.append(2, [("put", "k", "w")]) == 1  # new epoch fine


class TestReplicatedStore:
    def _pair(self, clock):
        log = StoreLog(clock=clock)
        lease = LeaderLease(duration_s=2.0, clock=clock)
        return (log, lease,
                ReplicatedStore(log, lease, "A"),
                ReplicatedStore(log, lease, "B"))

    def test_replication_and_takeover(self):
        clock = FakeClock()
        log, lease, a, b = self._pair(clock)
        assert a.acquire_leadership() == 1
        with a.txn() as t:
            t.put("cfg", "v1")
        assert b.get("cfg") is None
        assert b.catch_up() == 1  # standby replays the leader's commit
        assert b.get("cfg") == "v1"
        clock.advance(3.0)  # A's lease lapses (crash: stops renewing)
        assert b.acquire_leadership() == 2
        # The deposed leader's write is REJECTED, not applied.
        with pytest.raises(StaleEpochError):
            with a.txn() as t:
                t.put("cfg", "v2-from-the-dead")
        assert b.get("cfg") == "v1"
        # And B, the leader, writes on.
        with b.txn() as t:
            t.put("cfg", "v2")
        assert b.get("cfg") == "v2"

    def test_non_leader_commit_refused(self):
        clock = FakeClock()
        _, _, a, b = self._pair(clock)
        a.acquire_leadership()
        with pytest.raises(StaleEpochError):
            with b.txn() as t:
                t.put("k", "v")

    def test_renew_demotes_on_lost_lease(self):
        clock = FakeClock()
        _, lease, a, b = self._pair(clock)
        a.acquire_leadership()
        assert a.renew()
        clock.advance(3.0)
        b.acquire_leadership()
        assert not a.renew()
        assert not a.is_leader()


class TestControllerStoreMirror:
    def test_deploy_persists_config_and_registry(self):
        store = InMemoryStore()
        ctl = ServeController(store=store)
        ctl.deploy(DeploymentConfig(name="doubler", num_replicas=2),
                   factory=lambda: double_batch)
        try:
            cfg = store.get_json("serve:deployments/doubler/config")
            reg = store.get_json("serve:deployments/doubler/replicas")
            assert cfg["num_replicas"] == 2
            assert sorted(reg["ids"]) == ["doubler#0", "doubler#1"]
            assert reg["ordinal"] == 2
        finally:
            ctl.shutdown()
        # Shutdown's mirror shows the drained registry.
        assert store.get_json("serve:deployments/doubler/replicas")[
            "ids"] == []

    def test_recover_from_store_without_catalog_cold_starts(self):
        store = InMemoryStore()
        ctl = ServeController(store=store)
        ctl.deploy(DeploymentConfig(name="doubler", num_replicas=2),
                   factory=lambda: double_batch)
        ctl.crash()  # no drain: registry still lists the replicas
        ctl2 = ServeController(store=store)
        ctl2.register_factory("doubler", lambda: double_batch)
        assert ctl2.recover() == ["doubler"]
        try:
            assert ctl2.status()["doubler"]["running_replicas"] == 2
            handle = DeploymentHandle(ctl2.get_router("doubler"))
            assert handle.remote(4).result(timeout=5) == 8
        finally:
            ctl2.shutdown()
            ctl.shutdown()


class TestControllerFailover:
    """The ISSUE 11 acceptance pin: controller death is a failover."""

    def _build_leader(self):
        log = StoreLog()
        lease = LeaderLease(duration_s=0.5)
        catalog = ReplicaCatalog()
        store_a = ReplicatedStore(log, lease, "ctl-A")
        assert store_a.acquire_leadership() == 1
        ctl_a = ServeController(control_interval_s=0.05, store=store_a,
                                catalog=catalog)
        router = ctl_a.deploy(
            DeploymentConfig(name="doubler", num_replicas=2,
                             max_restarts=4),
            factory=lambda: double_batch,
        )
        ctl_a.start()
        return log, lease, catalog, ctl_a, router

    def test_standby_adopts_live_data_plane_and_fences_old_leader(self):
        log, lease, catalog, ctl_a, router = self._build_leader()
        ctl_b = None
        try:
            handle = DeploymentHandle(router)
            assert handle.remote(3).result(timeout=5) == 6
            old_replicas = {r.replica_id: r for r in router.replicas()}
            ordinal_a = ctl_a._deployments["doubler"].next_replica_ordinal

            ctl_a.crash()
            lease.revoke()
            store_b = ReplicatedStore(log, lease, "ctl-B")
            ctl_b = ServeController(control_interval_s=0.05,
                                    store=store_b, catalog=catalog)
            ctl_b.register_factory("doubler", lambda: double_batch)
            assert store_b.acquire_leadership() == 2
            assert ctl_b.recover() == ["doubler"]
            ctl_b.start()

            # ADOPTION, not restart: same router object (clients' handles
            # keep routing), same replica objects (no cold start), and
            # the ordinal continues (no replica-id reuse).
            assert ctl_b.get_router("doubler") is router
            new_replicas = {r.replica_id: r
                            for r in ctl_b.get_router("doubler").replicas()}
            assert new_replicas.keys() == old_replicas.keys()
            for rid, r in new_replicas.items():
                assert r is old_replicas[rid]
            assert ctl_b._deployments["doubler"].next_replica_ordinal \
                == ordinal_a
            # The ORIGINAL handle still serves through the failover.
            assert handle.remote(7).result(timeout=5) == 14

            # Failover audited with epoch numbers.
            adopts = [a for a in ctl_b.audit.to_dicts()
                      if a["trigger"] == "failover_adopt"]
            assert adopts and adopts[0]["observed"]["epoch"] == 2

            # The deposed leader's post-lease write is REJECTED (pinned).
            with pytest.raises(StaleEpochError):
                with ctl_a.store.txn() as t:
                    t.put("serve:heartbeat", '{"owner": "ctl-A"}')
            assert log.rejected_appends >= 1
        finally:
            if ctl_b is not None:
                ctl_b.shutdown()
            ctl_a.shutdown()

    def test_slow_leader_self_fences(self):
        """The failure mode fencing exists for: a leader that is SLOW,
        not dead — it keeps running after the standby took over. Its
        next renew/commit must demote it permanently, audited."""
        log, lease, catalog, ctl_a, _router = self._build_leader()
        try:
            lease.revoke()
            usurper = ReplicatedStore(log, lease, "ctl-B")
            assert usurper.acquire_leadership() == 2
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and not ctl_a._fenced:
                time.sleep(0.02)
            assert ctl_a._fenced
            fenced = [a for a in ctl_a.audit.to_dicts()
                      if a["trigger"] == "store_fenced"]
            assert fenced
            assert not ctl_a.store.is_leader()
        finally:
            ctl_a.shutdown()

    def test_standby_is_a_functioning_controller(self):
        """Post-failover the successor must HEAL, not just serve."""
        log, lease, catalog, ctl_a, router = self._build_leader()
        ctl_b = None
        try:
            ctl_a.crash()
            lease.revoke()
            store_b = ReplicatedStore(log, lease, "ctl-B")
            ctl_b = ServeController(control_interval_s=0.05,
                                    store=store_b, catalog=catalog)
            ctl_b.register_factory("doubler", lambda: double_batch)
            assert store_b.acquire_leadership() == 2
            ctl_b.recover()
            ctl_b.start()
            victim = ctl_b.get_router("doubler").replicas()[0]
            victim.stop(timeout_s=2.0, drain=False)
            deadline = time.monotonic() + 10
            healed = False
            while time.monotonic() < deadline:
                heals = [a for a in ctl_b.audit.to_dicts()
                         if a["trigger"] == "heal"]
                live = ctl_b.get_router("doubler").replicas()
                if heals and len(live) == 2 and all(
                    r.healthy() for r in live
                ):
                    healed = True
                    break
                time.sleep(0.05)
            assert healed, "standby never replaced the killed replica"
            # The replacement's id came from the CONTINUED ordinal, not a
            # reused one.
            ids = {r.replica_id
                   for r in ctl_b.get_router("doubler").replicas()}
            assert any(rid not in ("doubler#0", "doubler#1")
                       for rid in ids)
        finally:
            if ctl_b is not None:
                ctl_b.shutdown()
            ctl_a.shutdown()

    def test_store_status_surfaces_epoch_and_fencing(self):
        log, lease, catalog, ctl_a, _router = self._build_leader()
        try:
            st = ctl_a.store_status()
            assert st["kind"] == "ReplicatedStore"
            assert st["epoch"] == 1 and st["leader"] is True
            assert st["fenced"] is False
        finally:
            ctl_a.shutdown()


class TestReplicaCatalog:
    def test_register_adopt_unregister(self):
        cat = ReplicaCatalog()
        obj = object()
        cat.register_replica("d#0", obj)
        assert cat.replica("d#0") is obj
        assert cat.replica_ids() == ["d#0"]
        cat.unregister_replica("d#0")
        assert cat.replica("d#0") is None

    def test_concurrent_access_is_safe(self):
        cat = ReplicaCatalog()
        errors = []

        def writer(i):
            try:
                for j in range(200):
                    cat.register_replica(f"r{i}-{j}", j)
                    cat.unregister_replica(f"r{i}-{j}")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors


class TestFencingOnTheReconcilePath:
    def test_fenced_write_in_reconcile_demotes_not_logs(self):
        """The review-found split-brain: a deposed leader whose LEASE
        still reads valid (fence raced ahead of expiry) hits the fence
        on its reconcile WRITES — the broad reconcile error handlers
        must re-raise StaleEpochError so the controller demotes instead
        of logging 'reconcile failed' and mutating on."""
        log = StoreLog()
        lease = LeaderLease(duration_s=60.0)  # lease stays "valid"
        store_a = ReplicatedStore(log, lease, "ctl-A")
        assert store_a.acquire_leadership() == 1
        ctl = ServeController(control_interval_s=0.05, store=store_a)
        ctl.deploy(DeploymentConfig(name="doubler", num_replicas=2,
                                    max_restarts=4),
                   factory=lambda: double_batch)
        ctl.start()
        try:
            log.fence_to(2)  # a standby fenced the log out from under A
            # Force a reconcile WRITE (heal): quiet steady-state commits
            # nothing (no-op elision) and would never hit the fence.
            ctl.get_router("doubler").replicas()[0].stop(
                timeout_s=2.0, drain=False
            )
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and not ctl._fenced:
                time.sleep(0.02)
            assert ctl._fenced, (
                "StaleEpochError was swallowed by the reconcile "
                "handlers — the deposed leader kept leading"
            )
            fenced = [a for a in ctl.audit.to_dicts()
                      if a["trigger"] == "store_fenced"]
            assert fenced
        finally:
            ctl.shutdown()


class TestDeleteThenRedeployWithCatalog:
    def test_redeploy_never_adopts_the_closed_router(self):
        catalog = ReplicaCatalog()
        ctl = ServeController(store=InMemoryStore(), catalog=catalog)
        try:
            r1 = ctl.deploy(DeploymentConfig(name="d", num_replicas=1),
                            factory=lambda: double_batch)
            ctl.delete_deployment("d")
            assert catalog.router("d") is None
            r2 = ctl.deploy(DeploymentConfig(name="d", num_replicas=1),
                            factory=lambda: double_batch)
            assert r2 is not r1  # fresh router, not the closed one
            handle = DeploymentHandle(r2)
            assert handle.remote(5).result(timeout=5) == 10
        finally:
            ctl.shutdown()


class TestPgroupCatalog:
    def test_pgroup_register_lookup_unregister(self):
        cat = ReplicaCatalog()
        pg = object()
        cat.register_pgroup("d#0", pg)
        assert cat.pgroup("d#0") is pg
        cat.unregister_pgroup("d#0")
        assert cat.pgroup("d#0") is None

    def test_failover_rebinds_chip_reservations(self, eight_devices):
        """A successor adopting chip-reserving replicas must be able to
        FREE their chips when it later retires them — the reservation
        rides the catalog like the replica itself."""
        from ray_dynamic_batching_tpu.parallel.placement import (
            PlacementManager,
        )

        placement = PlacementManager(eight_devices)
        log = StoreLog()
        lease = LeaderLease(duration_s=0.5)
        catalog = ReplicaCatalog()
        store_a = ReplicatedStore(log, lease, "ctl-A")
        store_a.acquire_leadership()
        ctl_a = ServeController(control_interval_s=0.05, store=store_a,
                                catalog=catalog, placement=placement)
        ctl_a.deploy(
            DeploymentConfig(name="chippy", num_replicas=2,
                             chips_per_replica=1),
            factory=lambda: double_batch,
        )
        ctl_b = None
        try:
            assert len(placement.resource_view()["reservations"]) == 2
            ctl_a.crash()
            lease.revoke()
            store_b = ReplicatedStore(log, lease, "ctl-B")
            ctl_b = ServeController(control_interval_s=0.05,
                                    store=store_b, catalog=catalog,
                                    placement=placement)
            ctl_b.register_factory("chippy", lambda: double_batch)
            assert store_b.acquire_leadership() == 2
            ctl_b.recover()
            # The successor re-bound the live reservations.
            state = ctl_b._deployments["chippy"]
            assert len(state.pgroups) == 2
            # Scaling to zero through the SUCCESSOR frees every chip —
            # the leak the review pinned.
            ctl_b.deploy(DeploymentConfig(name="chippy", num_replicas=0,
                                          chips_per_replica=1))
            assert placement.resource_view()["reservations"] == []
        finally:
            if ctl_b is not None:
                ctl_b.shutdown()
            ctl_a.shutdown()


class TestSecondReviewRegressions:
    def _build_leader(self, start=True, **cfg_kw):
        log = StoreLog()
        lease = LeaderLease(duration_s=0.5)
        catalog = ReplicaCatalog()
        store_a = ReplicatedStore(log, lease, "ctl-A")
        assert store_a.acquire_leadership() == 1
        ctl_a = ServeController(control_interval_s=0.05, store=store_a,
                                catalog=catalog)
        router = ctl_a.deploy(
            DeploymentConfig(name="doubler", num_replicas=2,
                             max_restarts=4, **cfg_kw),
            factory=lambda: double_batch,
        )
        if start:
            ctl_a.start()
        return log, lease, catalog, ctl_a, router

    def test_deferred_stops_still_run_when_fenced_mid_step(self):
        """A scale-down victim collected before the fence hit must still
        be stopped and released — skipping the deferred actions on
        StaleEpochError leaks its thread forever (no successor will ever
        adopt a replica the fenced step already unpublished)."""
        # The control loop's thread is not started: the steps are driven
        # by hand, so the fence lands exactly between two of them.
        log, lease, catalog, ctl, router = self._build_leader(start=False)
        try:
            # The first control steps land their one-time governor/gray
            # mirror writes; from then on steady state elides, so the
            # NEXT append is the scale-down staged below.
            for _ in range(3):
                ctl._control_step()
            assert not ctl._fenced
            log.fence_to(2)  # a standby fenced the log...
            with ctl._lock:  # ...while a scale-down is pending
                ctl._deployments["doubler"].config.num_replicas = 1
            ctl._control_step()  # its append is refused MID-step
            assert ctl._fenced
            # Exactly one replica keeps serving; the victim was STOPPED
            # (deferred ran despite the fence), not leaked.
            live = router.replicas()
            assert len(live) == 1
            victims = [r for r in (catalog.replica("doubler#0"),
                                   catalog.replica("doubler#1"))
                       if r is not None]
            assert len(victims) == 1  # the stopped one was unregistered
        finally:
            ctl.shutdown()

    def test_unclaimed_lapsed_lease_reacquires_not_fences(self):
        """A lease that merely EXPIRED (nobody took over) must be
        re-acquired by the same owner at the same epoch — the only
        controller self-destructing would end all healing forever."""
        log, lease, catalog, ctl, router = self._build_leader()
        try:
            lease.revoke()  # lapse with NO usurper
            time.sleep(0.3)  # several control ticks
            assert not ctl._fenced
            assert ctl.store.is_leader()
            assert ctl.store.epoch == 1  # same owner: no epoch bump
            # And it still heals: kill a replica, watch it replaced.
            router.replicas()[0].stop(timeout_s=2.0, drain=False)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                live = router.replicas()
                if len(live) == 2 and all(r.healthy() for r in live):
                    break
                time.sleep(0.05)
            else:
                raise AssertionError("no heal after lease re-acquire")
        finally:
            ctl.shutdown()

    def test_unhealthy_verdict_survives_failover(self):
        """'Actors stay DEAD once max_restarts is spent' holds across
        leaders: the successor must not reset the restart budget of a
        deployment the old leader declared unhealthy."""
        log, lease, catalog, ctl_a, router = self._build_leader()
        ctl_b = None
        try:
            with ctl_a._lock:
                state = ctl_a._deployments["doubler"]
                state.restarts = 4
                state.unhealthy = True
            time.sleep(0.2)  # a control step persists the registry
            ctl_a.crash()
            lease.revoke()
            store_b = ReplicatedStore(log, lease, "ctl-B")
            ctl_b = ServeController(control_interval_s=0.05,
                                    store=store_b, catalog=catalog)
            ctl_b.register_factory("doubler", lambda: double_batch)
            assert store_b.acquire_leadership() == 2
            ctl_b.recover()
            st = ctl_b._deployments["doubler"]
            assert st.unhealthy and st.restarts == 4
            assert ctl_b.status()["doubler"]["healthy"] is False
        finally:
            if ctl_b is not None:
                ctl_b.shutdown()
            ctl_a.shutdown()

    def test_degraded_governor_survives_failover(self):
        """The successor keeps enforcing the old leader's degraded-mode
        declaration instead of re-admitting the flood until its own
        hysteresis re-detects it."""
        log, lease, catalog, ctl_a, router = self._build_leader(
            admission_rate_rps=10.0
        )
        ctl_b = None
        try:
            # Crash first, THEN stamp the mirror the way a flood-time
            # crash leaves it: under real overload the governor stays
            # degraded (ongoing rejects block recovery), so the durable
            # mirror at death reads "degraded" — a live idle loop here
            # would immediately hysteresis-recover and overwrite it.
            ctl_a.crash()
            with ctl_a.store.txn() as t:
                t.put_json("serve:governor/doubler",
                           {"state": "degraded"})
            lease.revoke()
            store_b = ReplicatedStore(log, lease, "ctl-B")
            ctl_b = ServeController(control_interval_s=0.05,
                                    store=store_b, catalog=catalog)
            ctl_b.register_factory("doubler", lambda: double_batch)
            assert store_b.acquire_leadership() == 2
            ctl_b.recover()
            assert ctl_b.admission.degraded("doubler") is True
        finally:
            if ctl_b is not None:
                ctl_b.shutdown()
            ctl_a.shutdown()


# --- clock unification (ISSUE 12 satellite) --------------------------------


class TestOneControlClock:
    def test_log_and_lease_share_one_injected_clock(self):
        """StoreLog record stamps, lease expiry, and the replicated
        store's demotion window all read ONE clock — no time.time /
        time.monotonic mixture (the PR 12 bugfix)."""
        clock = FakeClock(100.0)
        log = StoreLog(clock=clock)
        lease = LeaderLease(duration_s=2.0, clock=clock)
        store = ReplicatedStore(log, lease, "A", clock=clock)
        assert store.acquire_leadership() == 1
        with store.txn() as t:
            t.put("k", "v")
        (rec,) = log.read_from(0)
        assert rec.wall_time == 100.0  # the shared clock, not wall time

    def test_replicated_store_defaults_to_the_lease_clock(self):
        clock = FakeClock(7.0)
        store = ReplicatedStore(StoreLog(clock=clock),
                                LeaderLease(duration_s=2.0, clock=clock),
                                "A")
        assert store._clock() == 7.0

    def test_skewed_renewer_cannot_outlive_the_grantor_clock(self):
        """Expiry is judged on the LEASE's injected clock — the
        grantor's — at call time. A renewer whose own clock runs fast
        (or that renews in a tight burst) gets exactly duration_s of
        grantor time per renewal, never more: renewals do not stack,
        and no renewer-supplied timestamp exists to lie with."""
        grantor = FakeClock()
        lease = LeaderLease(duration_s=2.0, clock=grantor)
        assert lease.acquire("A") == 1
        for _ in range(50):             # frantic burst of renewals
            assert lease.renew("A")
        grantor.advance(2.5)            # one window of GRANTOR time
        assert lease.expired()
        assert lease.holder() is None
        assert not lease.renew("A")     # real leadership really ended
        assert lease.acquire("B") == 2


# --- snapshots + log compaction (ISSUE 12) ---------------------------------


class TestSnapshotCompaction:
    def _leader(self, clock, snapshot_every=4):
        log = StoreLog(clock=clock)
        lease = LeaderLease(duration_s=30.0, clock=clock)
        store = ReplicatedStore(log, lease, "A", clock=clock,
                                snapshot_every=snapshot_every)
        assert store.acquire_leadership() == 1
        return log, lease, store

    def test_snapshot_at_commit_point_truncates_the_log(self):
        clock = FakeClock()
        log, lease, store = self._leader(clock, snapshot_every=4)
        for i in range(10):
            with store.txn() as t:
                t.put("k", f"v{i}")
        assert store.snapshots_taken >= 2
        snap = log.latest_snapshot()
        assert snap is not None and snap.epoch == 1
        assert log.first_index == snap.index
        assert len(log) < 10              # truncated behind the snapshot
        assert log.appended_total == 10   # history accounting survives

    def test_read_from_compacted_index_fails_loudly(self):
        clock = FakeClock()
        log, lease, store = self._leader(clock, snapshot_every=4)
        for i in range(8):
            with store.txn() as t:
                t.put("k", f"v{i}")
        with pytest.raises(CompactedLogError) as ei:
            log.read_from(0)
        assert ei.value.first_index == log.first_index
        assert ei.value.snapshot_index == log.latest_snapshot().index
        # The horizon itself (and beyond) still reads fine.
        assert log.read_from(log.first_index) is not None

    def test_cold_standby_recovers_by_snapshot_plus_tail(self):
        clock = FakeClock()
        log, lease, store = self._leader(clock, snapshot_every=16)
        for i in range(50):
            with store.txn() as t:
                t.put(f"k{i % 7}", f"v{i}")
        standby = ReplicatedStore(log, lease, "B", clock=clock)
        standby.catch_up()
        assert standby.snapshot() == store.snapshot()
        assert standby.version == store.version
        assert standby.last_recovery["snapshot_index"] >= 0
        # O(tail): the replay is bounded by the compaction interval,
        # never the 50-record history.
        assert standby.max_tail_replayed <= 16

    def test_snapshot_racing_takeover_replays_never_double_applies(self):
        """A standby restores an epoch-1 snapshot while epoch-2 records
        are already in the tail: the newer-epoch tail must replay
        exactly once on top of the image (version arithmetic pins
        exactly-once: each record bumps version by 1)."""
        clock = FakeClock()
        log, lease, a = self._leader(clock, snapshot_every=4)
        for i in range(6):
            with a.txn() as t:
                t.put("k", f"v{i}")
        # Takeover: B replays (via snapshot), fences epoch 2, and
        # appends MORE records beyond the epoch-1 snapshot.
        lease.revoke()
        b = ReplicatedStore(log, lease, "B", clock=clock,
                            snapshot_every=4)
        assert b.acquire_leadership() == 2
        with b.txn() as t:
            t.put("k2", "w1")
        with b.txn() as t:
            t.put("k2", "w2")
        snap = log.latest_snapshot()
        # Cold replica C: restores SOME snapshot, replays the rest —
        # including any epoch-2 tail — exactly once.
        c = ReplicatedStore(log, lease, "C", clock=clock)
        c.catch_up()
        assert c.snapshot() == b.snapshot()
        assert c.version == b.version       # exactly-once: no double-apply
        assert c._repl.applied_index == b._repl.applied_index
        assert snap is not None

    def test_truncation_never_orphans_an_unsnapshotted_suffix(self):
        clock = FakeClock()
        log = StoreLog(clock=clock)
        log.append(1, [("put", "a", "1")])
        log.append(1, [("put", "b", "2")])
        with pytest.raises(ValueError):
            # Claims records the log never committed: refused.
            log.install_snapshot(StoreSnapshot(
                index=5, epoch=1, version=5, data={}))
        ok = StoreSnapshot(index=2, epoch=1, version=2,
                           data={"a": "1", "b": "2"})
        log.install_snapshot(ok)
        with pytest.raises(ValueError):
            # Regressing behind the horizon: refused too.
            log.install_snapshot(StoreSnapshot(
                index=1, epoch=1, version=1, data={"a": "1"}))

    def test_restore_is_wholesale_not_a_merge(self):
        """A standby that replayed a PREFIX (including keys later
        deleted) and then fell behind the compaction horizon must end
        up byte-identical to the leader — deletions included."""
        clock = FakeClock()
        log, lease, a = self._leader(clock, snapshot_every=100)
        with a.txn() as t:
            t.put("doomed", "x")
        standby = ReplicatedStore(log, lease, "B", clock=clock)
        standby.catch_up()
        assert standby.get("doomed") == "x"
        with a.txn() as t:
            t.delete("doomed")
        for i in range(99):
            with a.txn() as t:
                t.put("k", f"v{i}")
        # The leader's compaction has left the standby's cursor behind
        # the horizon.
        assert log.first_index > standby._repl.applied_index
        standby.catch_up()
        assert standby.get("doomed") is None
        assert standby.snapshot() == a.snapshot()

    def test_catch_up_survives_compaction_racing_the_restore(self):
        """The leader keeps committing (and compacting) WHILE a standby
        recovers: the snapshot the standby fetched can be truncated
        past before its tail read. catch_up must loop — restore the
        newer snapshot and retry — not crash with CompactedLogError."""
        clock = FakeClock()
        log, lease, leader = self._leader(clock, snapshot_every=4)
        for i in range(6):
            with leader.txn() as t:
                t.put("k", f"v{i}")

        class RacingFabric:
            """Passthrough that lets the leader commit 6 more records
            (advancing the compaction horizon) right after handing the
            standby its FIRST — now stale — snapshot."""

            def __init__(self):
                self.snapshot_fetches = 0

            def call(self, edge, fn, *args, src="", dst="", **kwargs):
                out = fn(*args, **kwargs)
                if edge == "store.snapshot":
                    self.snapshot_fetches += 1
                    if self.snapshot_fetches == 1:
                        for i in range(6):
                            with leader.txn() as t:
                                t.put("k", f"race{i}")
                return out

        standby = ReplicatedStore(log, lease, "B", clock=clock,
                                  fabric=RacingFabric())
        standby.catch_up()  # must not raise
        assert standby.snapshot() == leader.snapshot()
        assert standby.version == leader.version
