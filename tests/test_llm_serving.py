"""End-to-end LLM decode serving: proxy → router → LLMReplica → DecodeEngine.

The north-star wiring (VERDICT.md missing #1/#2): continuous-batching decode
reachable through the exact path the reference serves every request
(``serve/_private/replica.py:515-544`` → ``serve/batching.py:146``), plus
token streaming end to end (ref ``serve/batching.py:209-276`` generator
batches and the streaming proxy path ``_private/proxy.py:959``).
"""

import json
import socket

import jax
import numpy as np
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy (fast lane excludes)

from ray_dynamic_batching_tpu.engine.decode import DecodeResult
from ray_dynamic_batching_tpu.serve.controller import (
    DeploymentConfig,
    ServeController,
)
from ray_dynamic_batching_tpu.serve.handle import DeploymentHandle
from ray_dynamic_batching_tpu.serve.llm import LLMDeployment
from ray_dynamic_batching_tpu.serve.proxy import HTTPProxy, ProxyRouter
from ray_dynamic_batching_tpu.serve.replica import Replica
from ray_dynamic_batching_tpu.engine.request import Request, TokenStream


@pytest.fixture(scope="module")
def llm_stack():
    """Controller serving llama_tiny decode on the CPU fake chip."""
    controller = ServeController(control_interval_s=0.1)
    deployment = LLMDeployment(
        "llama_tiny",
        num_slots=4,
        max_len=64,
        prompt_buckets=[8, 16],
        default_max_new_tokens=8,
        decode_horizon=4,
        dtype=jnp.float32,
    )
    router = controller.deploy(
        DeploymentConfig(name="llama_tiny", num_replicas=1),
        factory=deployment,
    )
    controller.start()
    handle = DeploymentHandle(router)
    yield controller, handle
    controller.shutdown()


class TestLLMDeployment:
    def test_handle_roundtrip(self, llm_stack):
        _, handle = llm_stack
        fut = handle.remote({"tokens": [1, 2, 3], "max_new_tokens": 5})
        result = fut.result(timeout=30)
        assert isinstance(result, DecodeResult)
        assert len(result.tokens) == 5
        assert result.finish_reason == "length"

    def test_concurrent_requests_share_engine(self, llm_stack):
        _, handle = llm_stack
        futs = [
            handle.remote({"tokens": [i + 1, i + 2], "max_new_tokens": 4})
            for i in range(8)
        ]
        results = [f.result(timeout=30) for f in futs]
        assert all(len(r.tokens) == 4 for r in results)

    def test_streaming_through_handle(self, llm_stack):
        _, handle = llm_stack
        stream, fut = handle.remote_stream(
            {"tokens": [1, 2, 3], "max_new_tokens": 6}
        )
        first = stream.get(timeout_s=30)   # must arrive pre-completion
        rest = stream.drain(timeout_s=30)
        result = fut.result(timeout=30)
        assert [first] + rest == result.tokens

    def test_long_prompt_served_via_chunked_prefill(self, llm_stack):
        """A prompt past every bucket (16) but within KV capacity (64)
        flows through the full serving path via chunked admission."""
        _, handle = llm_stack
        prompt = [(i * 5) % 40 + 1 for i in range(30)]
        fut = handle.remote({"tokens": prompt, "max_new_tokens": 4})
        result = fut.result(timeout=120)
        assert len(result.tokens) == 4
        assert result.finish_reason == "length"

    def test_session_continuation_through_stack(self, llm_stack):
        """Multi-turn chat with session_id: turn 2 continues from stored
        KV and matches the sessionless result for the full history."""
        _, plain_handle = llm_stack
        controller = ServeController(control_interval_s=0.1)
        dep = LLMDeployment(
            "llama_tiny", num_slots=2, max_len=96, prompt_buckets=[8],
            default_max_new_tokens=5, dtype=jnp.float32,
            session_cache_size=8,
        )
        router = controller.deploy(
            DeploymentConfig(name="llama_sess"), factory=dep
        )
        controller.start()
        try:
            handle = DeploymentHandle(router)
            turn1 = [5, 9, 2, 7, 11, 13]
            r1 = handle.remote({
                "tokens": turn1, "max_new_tokens": 5, "session_id": "c1",
            }).result(timeout=120)
            turn2 = turn1 + r1.tokens + [17, 23]
            r2 = handle.remote({
                "tokens": turn2, "max_new_tokens": 5, "session_id": "c1",
            }).result(timeout=120)
            ref = plain_handle.remote({
                "tokens": turn2, "max_new_tokens": 5,
            }).result(timeout=120)
            assert r2.tokens == ref.tokens
        finally:
            controller.shutdown()

    def test_checkpoint_loaded_weights_serve(self, llm_stack, tmp_path):
        """LLMDeployment(checkpoint_dir=...) must serve with the RESTORED
        weights: output equals the checkpointed model's greedy decode, and
        differs from a fresh random init."""
        from ray_dynamic_batching_tpu.runtime.checkpoint import (
            CheckpointManager,
        )
        from ray_dynamic_batching_tpu.models.base import get_model

        _, plain_handle = llm_stack  # serves PRNGKey(0)-init weights
        model = get_model("llama_tiny", dtype=jnp.float32)
        trained = model.init(jax.random.PRNGKey(123))  # "trained" weights
        CheckpointManager(str(tmp_path)).save(step=7, tree=trained)

        controller = ServeController(control_interval_s=0.1)
        dep = LLMDeployment(
            "llama_tiny", num_slots=2, max_len=64, prompt_buckets=[8],
            default_max_new_tokens=8, dtype=jnp.float32,
            checkpoint_dir=str(tmp_path),
        )
        router = controller.deploy(
            DeploymentConfig(name="llama_ckpt"), factory=dep
        )
        controller.start()
        try:
            handle = DeploymentHandle(router)
            payload = {"tokens": [5, 9, 2, 7], "max_new_tokens": 8}
            served = handle.remote(dict(payload)).result(timeout=120)
            fresh = plain_handle.remote(dict(payload)).result(timeout=120)
            # Reference decode with the checkpointed weights, engine-free.
            import numpy as np
            seq = [5, 9, 2, 7]
            expect = []
            for _ in range(8):
                logits = model.apply(
                    trained,
                    jnp.asarray([seq]), jnp.ones((1, len(seq)), jnp.int32),
                )
                nxt = int(jnp.argmax(logits[0, -1]))
                expect.append(nxt)
                seq.append(nxt)
            assert served.tokens == expect
            assert served.tokens != fresh.tokens
        finally:
            controller.shutdown()

    def test_speculative_deployment_matches_plain(self, llm_stack):
        """LLMDeployment(draft_model_name=...) serves greedy-identical
        output through the full stack."""
        _, plain_handle = llm_stack
        controller = ServeController(control_interval_s=0.1)
        dep = LLMDeployment(
            "llama_tiny", num_slots=4, max_len=64, prompt_buckets=[8, 16],
            default_max_new_tokens=8, dtype=jnp.float32,
            draft_model_name="llama_tiny", spec_tokens=3,
        )
        router = controller.deploy(
            DeploymentConfig(name="llama_spec"), factory=dep
        )
        controller.start()
        try:
            spec_handle = DeploymentHandle(router)
            payload = {"tokens": [5, 9, 2, 7], "max_new_tokens": 10}
            a = spec_handle.remote(dict(payload)).result(timeout=120)
            b = plain_handle.remote(dict(payload)).result(timeout=120)
            assert a.tokens == b.tokens
        finally:
            controller.shutdown()

    def test_redeploy_reconfigures_running_llm_replica(self, llm_stack):
        """Redeploying an LLM deployment must reconfigure live replicas
        (base-contract kwargs incl. user_config) without a TypeError."""
        controller, handle = llm_stack
        router = controller.deploy(
            DeploymentConfig(name="llama_tiny", max_ongoing_requests=128,
                             user_config={"note": "redeploy"}),
        )
        replica = router.replicas()[0]
        assert replica.max_ongoing_requests == 128
        out = handle.remote({"tokens": [1, 2, 3], "max_new_tokens": 3})
        assert len(out.result(timeout=60).tokens) == 3

    def test_controller_status_reports_engine(self, llm_stack):
        controller, _ = llm_stack
        status = controller.status()["llama_tiny"]
        assert status["running_replicas"] == 1
        replica_stats = next(iter(status["replicas"].values()))
        assert "active_slots" in replica_stats
        assert "decode_steps" in replica_stats


class TestGeneratorBatching:
    def test_generator_fn_streams_chunks(self):
        """A generator callable yields per-request chunk lists; chunks must
        reach streams incrementally and futures get the collected lists."""

        def spell(payloads):
            # yield each payload's characters one step at a time
            longest = max(len(p) for p in payloads)
            for i in range(longest):
                yield [p[i] if i < len(p) else None for p in payloads]

        replica = Replica("gen#0", "spell", spell, max_batch_size=4,
                          batch_wait_timeout_s=0.01)
        reqs = [
            Request(model="spell", payload=word, slo_ms=5_000.0,
                    stream=TokenStream())
            for word in ("hi", "there")
        ]
        for r in reqs:
            assert replica.assign(r)
        replica.start()
        try:
            assert reqs[0].future.result(timeout=5) == ["h", "i"]
            assert reqs[1].future.result(timeout=5) == list("there")
            assert reqs[0].stream.drain() == ["h", "i"]
            assert reqs[1].stream.drain() == list("there")
        finally:
            replica.stop()

    def test_generator_wrong_width_rejects(self):
        def bad(payloads):
            yield [1]  # always one chunk regardless of batch size

        replica = Replica("gen#1", "bad", bad, max_batch_size=4,
                          batch_wait_timeout_s=0.01)
        reqs = [
            Request(model="bad", payload=i, slo_ms=5_000.0) for i in range(2)
        ]
        for r in reqs:
            assert replica.assign(r)
        replica.start()
        try:
            with pytest.raises(ValueError):
                reqs[0].future.result(timeout=5)
        finally:
            replica.stop()


def _http(sock_addr, method, path, body=None, timeout=30.0):
    """Minimal HTTP client returning (code, headers, raw_body_bytes)."""
    host, port = sock_addr
    data = json.dumps(body).encode() if body is not None else b""
    req = (
        f"{method} {path} HTTP/1.1\r\nHost: {host}\r\n"
        f"Content-Length: {len(data)}\r\nConnection: keep-alive\r\n\r\n"
    ).encode() + data
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.sendall(req)
        s.settimeout(timeout)
        buf = b""
        while b"\r\n\r\n" not in buf:
            buf += s.recv(65536)
        head, rest = buf.split(b"\r\n\r\n", 1)
        lines = head.decode().split("\r\n")
        code = int(lines[0].split(" ")[1])
        headers = dict(
            (k.strip().lower(), v.strip())
            for k, v in (l.split(":", 1) for l in lines[1:] if ":" in l)
        )
        if "content-length" in headers:
            want = int(headers["content-length"])
            while len(rest) < want:
                rest += s.recv(65536)
            return code, headers, rest[:want]
        # chunked: read until the 0-length terminator
        while not rest.endswith(b"0\r\n\r\n"):
            chunk = s.recv(65536)
            if not chunk:
                break
            rest += chunk
        return code, headers, rest


def _dechunk(raw: bytes) -> bytes:
    out = b""
    while raw:
        if b"\r\n" not in raw:
            break
        size_line, raw = raw.split(b"\r\n", 1)
        size = int(size_line, 16)
        if size == 0:
            break
        out += raw[:size]
        raw = raw[size + 2:]  # skip payload + trailing CRLF
    return out


class TestProxyLLM:
    @pytest.fixture(scope="class")
    def proxy_stack(self, llm_stack):
        _, handle = llm_stack
        prouter = ProxyRouter()
        prouter.set_route("/api/llama_tiny", handle)
        proxy = HTTPProxy(prouter, port=0).start()
        yield (proxy.host, proxy.port)
        proxy.stop()

    def test_buffered_request(self, proxy_stack):
        code, _, body = _http(
            proxy_stack, "POST", "/api/llama_tiny",
            {"tokens": [1, 2, 3], "max_new_tokens": 4},
        )
        assert code == 200
        result = json.loads(body)["result"]
        assert len(result["tokens"]) == 4

    def test_streaming_request(self, proxy_stack):
        code, headers, raw = _http(
            proxy_stack, "POST", "/api/llama_tiny",
            {"tokens": [1, 2, 3], "max_new_tokens": 6, "stream": True},
        )
        assert code == 200
        assert headers.get("transfer-encoding") == "chunked"
        lines = [
            json.loads(l) for l in _dechunk(raw).decode().splitlines() if l
        ]
        chunks = [l["chunk"] for l in lines if "chunk" in l]
        finals = [l for l in lines if "result" in l]
        assert len(finals) == 1
        assert chunks == finals[0]["result"]["tokens"]
        assert len(chunks) == 6  # every token arrived as its own line


class TestLLMReplicaLifecycle:
    def test_stop_aborts_active_slots(self):
        """Replica death must reject in-flight decode requests — futures and
        streams never dangle (ref: replicas drain-then-stop; undrained work
        is rejected)."""
        import jax.numpy as jnp
        from ray_dynamic_batching_tpu.engine.request import RequestDropped
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

        dep = LLMDeployment(
            "llama_tiny", num_slots=2, max_len=4096, prompt_buckets=[8],
            default_max_new_tokens=8, dtype=jnp.float32,
        )
        cfg = DeploymentConfig(name="abort_test")
        replica = dep.make_replica("abort#0", cfg)
        req = Request(
            model="abort_test",
            payload={"tokens": [1, 2], "max_new_tokens": 500_000},
            slo_ms=60_000.0,
            stream=TokenStream(),
        )
        assert replica.assign(req)
        replica.start()
        req.stream.get(timeout_s=30)  # wait until it's mid-decode
        replica.stop(timeout_s=0.2)   # drain can't finish: must abort
        with pytest.raises(RequestDropped):
            req.future.result(timeout=5)
        with pytest.raises(RequestDropped):
            req.stream.drain(timeout_s=5)

    def test_healthy_detects_stalled_engine(self):
        """A live thread that stops making progress must read unhealthy so
        the controller replaces it (engine heartbeat contract)."""
        import time
        import jax.numpy as jnp
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

        dep = LLMDeployment(
            "llama_tiny", num_slots=2, max_len=64, prompt_buckets=[8],
            default_max_new_tokens=4, dtype=jnp.float32,
        )
        cfg = DeploymentConfig(name="stall_test")
        replica = dep.make_replica("stall#0", cfg)
        replica.start()
        try:
            time.sleep(0.05)
            assert replica.healthy(stall_timeout_s=60.0)
            # Simulate a wedged loop: freeze the heartbeat in the past.
            replica.engine.last_heartbeat -= 120.0
            assert not replica.healthy(stall_timeout_s=60.0)
        finally:
            replica.stop(timeout_s=0.5)


class TestAutoSlots:
    def test_num_slots_sized_from_hbm_budget(self):
        """num_slots<=0 derives the continuous-batch size from the HBM
        budget minus weights, in KV-row units, rounded to a power of two."""
        from ray_dynamic_batching_tpu.serve.llm import LLMDeployment
        from ray_dynamic_batching_tpu.utils.config import (
            RDBConfig,
            set_config,
        )

        set_config(RDBConfig.from_env(hbm_budget_bytes=1 << 30))  # 1 GB
        dep = LLMDeployment(
            "llama_tiny", num_slots=0, max_len=64, prompt_buckets=[8],
            dtype=jnp.float32, warmup=False,
        )
        n1 = dep.auto_num_slots(1)
        assert n1 >= 1
        assert n1 & (n1 - 1) == 0  # power of two
        # The chosen count must actually fit the budget.
        kv_total = n1 * dep.pool_bytes_per_slot(dep._model, 64)
        assert kv_total <= (1 << 30)
        # A tighter budget yields fewer slots.
        set_config(RDBConfig.from_env(hbm_budget_bytes=64 << 20))
        assert dep.auto_num_slots(1) <= n1
        # TP shards weights + KV per chip -> more slots fit per chip.
        set_config(RDBConfig.from_env(hbm_budget_bytes=64 << 20))
        assert dep.auto_num_slots(4) >= dep.auto_num_slots(1)


class TestTracePropagation:
    def test_spans_join_one_trace_across_the_serving_path(self, llm_stack):
        """handle.remote -> replica/engine: spans propagate the caller's
        trace id via request.trace_ctx (ref task-metadata propagation,
        tracing_helper.py:165-411)."""
        from ray_dynamic_batching_tpu.utils.tracing import tracer

        exported = []
        tracer().set_exporter(exported.append)
        try:
            _, handle = llm_stack
            fut = handle.remote({"tokens": [1, 2, 3], "max_new_tokens": 3})
            fut.result(timeout=30)
            deadline = __import__("time").monotonic() + 5
            while __import__("time").monotonic() < deadline:
                names = {s.name for s in exported}
                if {"handle.remote", "decode.sequence"} <= names:
                    break
            by_name = {s.name: s for s in exported}
            client = by_name["handle.remote"]
            seq = by_name["decode.sequence"]
            assert seq.trace_id == client.trace_id
            assert seq.parent_id == client.span_id
            assert seq.attributes["tokens"] == 3
            assert seq.attributes["finish_reason"] == "length"
        finally:
            tracer().reset()


class TestLLMHeal:
    @pytest.mark.timeout(240)
    def test_wedged_engine_replaced_and_serving_resumes(self):
        """The controller's standard heal path must recover an LLM
        deployment whose engine loop wedges (engine heartbeat goes stale),
        and requests after the replacement must serve normally."""
        import time

        controller = ServeController(control_interval_s=0.1)
        dep = LLMDeployment(
            "llama_tiny", num_slots=2, max_len=32, prompt_buckets=[8],
            default_max_new_tokens=4, dtype=jnp.float32,
        )
        router = controller.deploy(
            DeploymentConfig(name="healme", num_replicas=1, max_restarts=2),
            factory=dep,
        )
        controller.start()
        handle = DeploymentHandle(router, default_slo_ms=60_000.0)
        try:
            assert len(
                handle.remote({"tokens": [1, 2]}).result(timeout=60).tokens
            ) == 4
            victim = controller._deployments["healme"].replicas[0]
            # Wedge: stop the loop AND freeze its heartbeat in the past so
            # healthy() (thread dead or stalled) goes false either way.
            victim.engine._run.clear()
            victim.engine.last_heartbeat -= 3600.0
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                reps = controller._deployments["healme"].replicas
                if reps and reps[0] is not victim and reps[0].healthy():
                    break
                time.sleep(0.1)
            else:
                pytest.fail("wedged LLM replica was not replaced")
            out = handle.remote({"tokens": [3, 4]}).result(timeout=60)
            assert len(out.tokens) == 4
        finally:
            controller.shutdown()


class TestLengthBuckets:
    @pytest.mark.timeout(240)
    def test_requests_route_to_smallest_fitting_cache(self):
        """Capacity-bucketed engines (the static-shape alternative to paged
        KV): short requests decode in the small cache, long ones in the
        large; oversized falls back to the largest and finishes by
        capacity."""
        controller = ServeController(control_interval_s=0.2)
        dep = LLMDeployment(
            "llama_tiny", num_slots=2, max_len=64, prompt_buckets=[8],
            default_max_new_tokens=4, dtype=jnp.float32,
            length_buckets=[16, 64],
        )
        router = controller.deploy(
            DeploymentConfig(name="buckets", num_replicas=1), factory=dep,
        )
        handle = DeploymentHandle(router, default_slo_ms=60_000.0)
        import time as _time

        def wait_completed(engine, n, timeout=10.0):
            # completed increments AFTER the future fulfills — poll briefly
            deadline = _time.monotonic() + timeout
            while engine.completed < n and _time.monotonic() < deadline:
                _time.sleep(0.01)
            assert engine.completed == n

        try:
            replica = controller._deployments["buckets"].replicas[0]
            assert sorted(replica.engines) == [16, 64]
            # prompt 3 + max_new 4 = 7 <= 16 -> small engine
            short = handle.remote({"tokens": [1, 2, 3], "max_new_tokens": 4})
            assert len(short.result(timeout=60).tokens) == 4
            wait_completed(replica.engines[16], 1)
            assert replica.engines[64].completed == 0
            # prompt 6 + max_new 20 = 26 > 16 -> large engine
            long = handle.remote(
                {"tokens": [1, 2, 3, 4, 5, 6], "max_new_tokens": 20}
            )
            assert len(long.result(timeout=60).tokens) == 20
            wait_completed(replica.engines[64], 1)
            # oversized (needs 8 + 200 > 64): largest engine, capacity finish
            over = handle.remote(
                {"tokens": [1] * 8, "max_new_tokens": 200}
            )
            result = over.result(timeout=60)
            assert result.finish_reason == "capacity"
            wait_completed(replica.engines[64], 2)
            # per-bucket stats surface
            stats = replica.stats()
            assert stats["bucket_16"]["completed"] == 1.0
            assert stats["bucket_64"]["completed"] == 2.0
            assert stats["completed"] == 3.0
        finally:
            controller.shutdown()


class TestLLMRollingUpdate:
    def test_versioned_rollout_drains_inflight_generation(self):
        """Rolling update over the LLM path (VERDICT r3 #7 x #3): a
        generation mid-decode on the v1 replica completes through the
        rollout's graceful drain (LLMReplica.queue_len counts active
        slots, so the stop wait covers in-flight decodes), and the v2
        deployment — different default_max_new_tokens — serves afterward."""
        import time

        controller = ServeController(control_interval_s=3600.0)

        def dep(max_new):
            return LLMDeployment(
                "llama_tiny", num_slots=2, max_len=64, prompt_buckets=[8],
                default_max_new_tokens=max_new, decode_horizon=2,
                dtype=jnp.float32, warmup=False,
            )

        router = controller.deploy(
            DeploymentConfig(name="llm_roll", num_replicas=1, version="v1"),
            factory=dep(6),
        )
        try:
            handle = DeploymentHandle(router, default_slo_ms=120_000.0)
            old_replica = router.replicas()[0]
            # Throwaway request first: compiles v1's programs so the drain
            # window below covers only the 24 decode tokens, not an XLA
            # compile (warmup=False keeps the test start fast).
            warm = handle.remote({"tokens": [7, 8], "max_new_tokens": 2})
            assert len(warm.result(timeout=120).tokens) == 2
            inflight = handle.remote({"tokens": [1, 2, 3],
                                      "max_new_tokens": 24})
            deadline = time.monotonic() + 60
            while (old_replica.engine.active_slots == 0
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert old_replica.engine.active_slots > 0  # admitted, decoding

            controller.deploy(
                DeploymentConfig(name="llm_roll", num_replicas=1,
                                 version="v2"),
                factory=dep(9),
            )
            # deploy() ran the deferred graceful stop: the in-flight
            # request finished on the retired v1 replica, not rejected.
            assert len(inflight.result(timeout=60).tokens) == 24
            assert controller.status()["llm_roll"]["versions"] == {"v2": 1}
            # The new code serves: v2's default_max_new_tokens applies.
            fresh = handle.remote({"tokens": [4, 5, 6]})
            assert len(fresh.result(timeout=120).tokens) == 9
        finally:
            controller.shutdown()
