"""LLM serving deployment — continuous-batching decode behind the serve stack.

This is the north-star wiring (BASELINE.json: "the per-replica ModelRunner's
torch forward becomes a jax.jit call"): a deployment whose replicas each own
a :class:`~ray_dynamic_batching_tpu.engine.decode.DecodeEngine` driving
prefill + continuous-batching decode on one chip (or one mesh slice), fed
through the standard proxy → router → handle path the reference uses for
every deployment (``serve/_private/replica.py:515-544`` — the replica's
``handle_request``/``_streaming`` entry points; here the request queue IS the
engine's admission queue, so router assignment and engine admission compose
without a second hop).

The replica surface (queue_len / accepting / assign / healthy / stats) is
inherited from :class:`~ray_dynamic_batching_tpu.serve.replica.Replica`, so
the pow-2 router, autoscaler, and controller state machine treat LLM
replicas exactly like batch replicas. Only the execution loop differs: the
decode engine's own thread replaces the opportunistic-batch loop.

Payload contract (JSON-safe, the proxy passes it straight through)::

    {"tokens": [1, 2, 3],          # prompt token ids (required)
     "max_new_tokens": 64,          # optional
     "temperature": 0.8,            # optional: 0 (default) = greedy
     "top_k": 40,                   # optional: 0 (default) = full vocab
     "seed": 1234,                  # optional: reproducible sampling
     "stream": true}                # optional: tokens stream incrementally

Result: ``DecodeResult`` (tokens, finish_reason, ttft_ms, total_ms).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from ray_dynamic_batching_tpu.engine.decode import (
    DEFAULT_PROMPT_BUCKETS,
    DecodeEngine,
    require_paged,
)
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request, RequestDropped
from ray_dynamic_batching_tpu.serve.replica import Replica
from ray_dynamic_batching_tpu.utils.compile_ledger import get_ledger
from ray_dynamic_batching_tpu.utils.logging import get_logger

logger = get_logger("serve.llm")


class LLMReplica(Replica):
    """One or more decode engines behind the standard replica surface.

    ``engine_builders`` maps a KV-capacity bucket (max_len) to a builder
    that receives that bucket's request queue and returns a ready
    (constructed, un-started) :class:`DecodeEngine` — weights loaded and
    sharded however the deployment wants (single chip, TP mesh slice).

    **Capacity buckets**: with several engines at different max_len,
    admission routes each request to the smallest cache that fits prompt
    + max_new_tokens. They date from the time when decode attention read
    the FULL cache capacity every step; the paged kernel now stops at a
    slot's length (a scan costs what its live pages cost), so what a
    bucket still buys is a narrower page table and a smaller pool per
    length class. No benchmark cell uses more than one (ROADMAP D16).

    Engine warmup (XLA compiles for every prompt bucket + both decode
    horizons) runs at construction, mirroring how the controller treats
    slow replica starts: a replica is registered with the router only after
    it can serve its first request at full speed.
    """

    def __init__(
        self,
        replica_id: str,
        deployment: str,
        engine_builders: Dict[int, Callable[[RequestQueue], DecodeEngine]],
        max_ongoing_requests: int = 256,
        warmup: bool = True,
        default_max_new_tokens: int = 64,
    ) -> None:
        super().__init__(
            replica_id=replica_id,
            deployment=deployment,
            fn=self._reject_batch_path,  # engines own execution, not the loop
            max_ongoing_requests=max_ongoing_requests,
        )
        self.default_max_new_tokens = default_max_new_tokens
        # The base class's queue carries no traffic here (admission routes
        # straight to the per-bucket queues below); close it so nothing can
        # mistake it for a live path.
        self.queue.close()
        self.engines: Dict[int, DecodeEngine] = {}
        self._queues: Dict[int, RequestQueue] = {}
        # A warmed replica's whole start-up — weight placement, cache
        # allocation, then warmup — is one warmup phase of the compile
        # ledger (depth-counted; engine.warmup() nests inside). The
        # ledger is process-wide: without the bracket, the second replica
        # of a process allocates its cache after the first one's warmup
        # armed the steady-state mark, and every fill it compiles is
        # charged as a serving-time violation.
        with (get_ledger().warming() if warmup
              else contextlib.nullcontext()):
            for bucket in sorted(engine_builders):
                q = RequestQueue(
                    f"{deployment}:{bucket}", max_len=max_ongoing_requests
                )
                self._queues[bucket] = q
                self.engines[bucket] = engine_builders[bucket](q)
            if warmup:
                for engine in self.engines.values():
                    engine.warmup()

    @property
    def engine(self) -> DecodeEngine:
        """The largest-capacity engine (single-engine deployments have
        exactly one; multi-bucket callers should use :attr:`engines`)."""
        return self.engines[max(self.engines)]

    @staticmethod
    def _reject_batch_path(payloads: List[Any]) -> Sequence[Any]:
        raise RuntimeError("LLMReplica executes via its DecodeEngines")

    # --- admission: route by required KV capacity --------------------------
    def _required_capacity(self, payload: Any) -> int:
        max_new = self.default_max_new_tokens
        tokens = payload
        if isinstance(payload, dict):
            tokens = payload.get("tokens", ())
            max_new = int(payload.get("max_new_tokens", max_new))
        try:
            prompt_len = len(tokens)
        except TypeError:
            prompt_len = 1
        return prompt_len + max_new

    def _engine_for(self, payload: Any) -> int:
        need = self._required_capacity(payload)
        for bucket in sorted(self.engines):
            if bucket >= need:
                return bucket
        # Oversized: the largest engine finishes it with reason=capacity
        # (same contract as a single-engine replica).
        return max(self.engines)

    def assign(self, request: Request) -> bool:
        if not self.accepting():
            return False
        q = self._queues[self._engine_for(request.payload)]
        ok = q.add_request(request, reject_on_full=False)
        if ok and request.multiplexed_model_id:
            # Same contract as the base class: warm-model routing needs the
            # LRU recorded on every accepted assignment.
            self.record_multiplexed_model(request.multiplexed_model_id)
        return ok

    # --- lifecycle: the engine loops replace the batch loop ----------------
    def start(self) -> None:
        for engine in self.engines.values():
            engine.start()

    def stop(self, timeout_s: float = 5.0, drain: bool = True) -> None:
        self._stopped = True
        if drain:
            deadline = time.monotonic() + timeout_s
            while self.queue_len() > 0 and time.monotonic() < deadline:
                time.sleep(0.01)  # rdb-lint: disable=event-loop-blocking (control-plane stop() drain poll on the controller's thread; no event loop involved)
        exc = RequestDropped(f"{self.replica_id} stopped")
        # Signal every loop BEFORE joining any, then join under one shared
        # deadline — N wedged engines must cost ~timeout_s total, not
        # N * timeout_s of control-plane stall.
        for engine in self.engines.values():
            engine._run.clear()
        join_deadline = time.monotonic() + timeout_s
        for engine in self.engines.values():
            engine.stop(max(0.1, join_deadline - time.monotonic()))
        for bucket, q in self._queues.items():
            q.close()
            # Requests still mid-decode in engine slots terminate with a
            # rejection — futures/streams must never dangle past death.
            self.engines[bucket].abort_active(exc)
        for req in self.drain_queue():
            # Shed accounting conserves through teardown: drained work is
            # a counted drop, not a vanished request.
            self._queues[self._engine_for(req.payload)].count_external_drop(
                req, reason="closed"
            )
            req.reject(exc)
        # Free HBM (params + caches) so a replacement on the same chip
        # doesn't OOM against this replica's dead buffers — but only if the
        # loop actually exited; a wedged device call may still be touching
        # them, and dropping the references mid-flight trades a leak for a
        # use-after-free-style crash.
        for engine in self.engines.values():
            t = engine._thread
            if t is None or not t.is_alive():
                engine.release_buffers()

    def drain_queue(self) -> List[Request]:
        self._stopped = True
        out: List[Request] = []
        for q in self._queues.values():
            while len(q) > 0:
                out.extend(
                    q.get_batch(self.max_ongoing_requests,
                                discard_stale=False)
                )
        return out

    def slo_compliance(self) -> float:
        """Worst recent compliance across the bucket queues that carry
        this replica's traffic (the base class's queue is closed here, so
        its idle 1.0 would blind the overload governor's compliance
        signal)."""
        qs = list(self._queues.values())
        return min((q.slo_compliance() for q in qs), default=1.0)

    def latency_observation(self) -> tuple:
        """Merged recent-latency sketch across the bucket queues (the
        closed base queue would leave this replica permanently ungraded
        by the gray detector and pin the hedge bar at its floor —
        exactly the blindness :meth:`slo_compliance` fixes for the
        governor)."""
        from ray_dynamic_batching_tpu.utils.sketch import QuantileSketch

        views = [q.latency_window.view() for q in self._queues.values()]
        merged = QuantileSketch.merged(views)
        return (merged.percentile(0.5), merged.percentile(0.95),
                len(merged))

    def prefix_digests(self, limit: int = 128) -> Optional[dict]:
        """Bounded prefix-page digest publication merged across this
        replica's bucket engines (cluster-wide prefix routing, ISSUE 11).
        The controller collects this each control step and pushes it to
        the router's digest directory over the long-poll channel."""
        merged: dict = {}
        page_size = None
        reloaded: List[str] = []
        for engine in self.engines.values():
            fn = getattr(engine, "prefix_digests", None)
            if fn is None:
                continue
            pub = fn(limit)
            if pub is None:
                continue
            page_size = pub["page_size"]
            # Spill round-trip republish (page fabric, satellite fix):
            # forwarded so the controller can force a directory push even
            # when the advertised union is unchanged.
            reloaded.extend(pub.get("reloaded", ()))
            for key, n in pub["digests"].items():
                if len(merged) >= limit:
                    break
                merged.setdefault(key, n)
        if page_size is None:
            return None
        out: dict = {"page_size": page_size, "digests": merged}
        if reloaded:
            out["reloaded"] = reloaded
        return out

    # --- page fabric surface (live migration + prefix push) ---------------
    def live_stream_ids(self) -> List[str]:
        """Migration-eligible stream ids across this replica's bucket
        engines."""
        out: List[str] = []
        for engine in self.engines.values():
            out.extend(engine.live_stream_ids())
        return out

    def request_migration(self, request_id: str, deliver) -> bool:
        """Ask whichever bucket engine holds ``request_id`` to migrate it
        out through ``deliver`` (see DecodeEngine.request_migration)."""
        for engine in self.engines.values():
            if engine.request_migration(request_id, deliver):
                return True
        return False

    def accept_parcel(self, parcel) -> bool:
        """Destination half of the courier edge at replica granularity:
        stream parcels route to the smallest capacity bucket that fits
        the stream's resume length (same bandwidth-per-token rule as
        fresh admissions), falling back to any accepting engine; prefix
        parcels go to the largest engine (where long prompts land)."""
        if self._stopped:
            return False
        if parcel.kind == "stream":
            need = parcel.resume_len
            for bucket in sorted(self.engines):
                if bucket >= need and self.engines[bucket].accept_parcel(
                        parcel):
                    return True
            for bucket in sorted(self.engines, reverse=True):
                if self.engines[bucket].accept_parcel(parcel):
                    return True
            return False
        return self.engine.accept_parcel(parcel)

    def hot_prefixes(self, limit: int = 8) -> List[tuple]:
        """Hit-ranked resident prefix entries across bucket engines, as
        ``(digest_hex, n_pages, hits)`` — the push planner's ranking."""
        out: List[tuple] = []
        for engine in self.engines.values():
            cache = getattr(engine, "paged_prefix", None)
            if cache is None:
                continue
            out.extend(cache.hot(limit))
        out.sort(key=lambda t: -t[2])
        return out[:limit]

    def request_prefix_push(self, digest_hex: str, deliver) -> bool:
        """Export the prefix entry addressed by ``digest_hex`` through
        ``deliver`` from whichever engine holds it."""
        key = bytes.fromhex(digest_hex)
        for engine in self.engines.values():
            cache = getattr(engine, "paged_prefix", None)
            if cache is None or key not in cache._entries:
                continue
            if engine.request_prefix_push(key, deliver):
                return True
        return False

    # --- router-facing surface --------------------------------------------
    def queue_len(self) -> int:
        return sum(
            len(q) + self.engines[b].active_slots
            + self.engines[b]._admitting
            for b, q in self._queues.items()
        )

    def healthy(self, stall_timeout_s: float = 60.0) -> bool:
        """Thread liveness + progress for EVERY engine: the loop refreshes
        its heartbeat only on successful iterations, so a perpetually-
        failing or wedged _step reads unhealthy and the controller replaces
        the replica (same stall contract as the base class)."""
        for engine in self.engines.values():
            t = engine._thread
            if t is None or not t.is_alive():
                return False
            if (time.monotonic() - engine.last_heartbeat) >= stall_timeout_s:
                return False
        return True

    def reconfigure(
        self,
        max_batch_size: Optional[int] = None,
        batch_wait_timeout_s: Optional[float] = None,
        max_ongoing_requests: Optional[int] = None,
        user_config: Optional[dict] = None,
    ) -> None:
        # Slot count / buckets are compile-shape decisions and can't change
        # on a live engine; only admission-side knobs apply. user_config is
        # accepted for base-contract compatibility (the controller passes
        # it to every replica kind) but has no user callable to deliver to.
        if max_ongoing_requests is not None:
            self.max_ongoing_requests = max_ongoing_requests
            for q in self._queues.values():
                q.max_len = max_ongoing_requests

    def stats(self) -> dict:
        s: dict = {}
        if len(self._queues) == 1:
            # Single-bucket replicas keep the flat queue-stat shape external
            # monitors already read (depth, slo_compliance, latency pcts).
            s.update(next(iter(self._queues.values())).stats())
        for bucket, q in self._queues.items():
            engine = self.engines[bucket]
            s[f"bucket_{bucket}"] = {
                **q.stats(),
                "active_slots": float(engine.active_slots),
                "decode_steps": float(engine.steps),
                "completed": float(engine.completed),
            }
        s["ongoing"] = float(self.queue_len())
        s["active_slots"] = float(
            sum(e.active_slots for e in self.engines.values())
        )
        s["decode_steps"] = float(sum(e.steps for e in self.engines.values()))
        s["completed"] = float(
            sum(e.completed for e in self.engines.values())
        )
        return s


class LLMDeployment:
    """Deployment factory the controller consumes via ``make_replica``.

    Builds the model + params ONCE and shares them across replicas (weights
    are immutable at inference; on a single host the HBM cost is paid once —
    the reference reloads weights per worker because CUDA contexts don't
    share, a constraint TPU+JAX doesn't have).
    """

    def __init__(
        self,
        model_name: str,
        num_slots: int = 8,
        max_len: int = 256,
        prompt_buckets: Optional[Sequence[int]] = None,
        eos_token_id: Optional[int] = None,
        default_max_new_tokens: int = 64,
        decode_horizon: int = 8,
        ttft_horizon: Optional[int] = None,
        max_admissions_per_step: int = 2,
        prefix_cache_size: int = 0,
        session_cache_size: int = 0,
        dtype: Any = None,
        params: Any = None,
        model: Any = None,
        warmup: bool = True,
        length_buckets: Optional[Sequence[int]] = None,
        draft_model_name: Optional[str] = None,
        draft_params: Any = None,
        spec_tokens: int = 4,
        checkpoint_dir: Optional[str] = None,
        checkpoint_step: Optional[int] = None,
        quantize_weights: bool = False,
        quantize_kv: bool = False,
        profiles_dir: Optional[str] = None,
        token_slo_ms: Optional[float] = None,
        ttft_slo_ms: Optional[float] = None,
        paged: bool = True,
        page_size: int = 128,
        kv_pool_pages: Optional[int] = None,
        host_spill_pages: int = 0,
        prefill_token_budget: Optional[int] = None,
    ) -> None:
        self.model_name = model_name
        self.num_slots = num_slots
        self.max_len = max_len
        self.prompt_buckets = prompt_buckets
        self.eos_token_id = eos_token_id
        self.default_max_new_tokens = default_max_new_tokens
        self.decode_horizon = decode_horizon
        self.ttft_horizon = ttft_horizon
        self.max_admissions_per_step = max_admissions_per_step
        self.prefix_cache_size = prefix_cache_size
        # HBM -> host-RAM spill tier for shed prefix pins (ISSUE 11):
        # pages of host residency per engine; 0 = off.
        self.host_spill_pages = host_spill_pages
        # Session rows are PER ENGINE: handle-level affinity steers a
        # session's turns back to the replica holding its row, but a
        # conversation that outgrows its length bucket lands on a larger
        # engine and re-prefills once (its old entry ages out via LRU) —
        # with multiple length buckets each engine budgets its own cache.
        self.session_cache_size = session_cache_size
        self.warmup = warmup
        # KV-capacity buckets: one engine per entry, requests routed to the
        # smallest cache fitting prompt + max_new (LLMReplica docstring).
        # Default: one engine at max_len.
        self.length_buckets = sorted(length_buckets or [max_len])
        # Speculative decoding: a smaller registry model drafts, the target
        # verifies (greedy-exact; see DecodeEngine._spec_impl).
        self.draft_model_name = draft_model_name
        self.spec_tokens = spec_tokens
        self._draft_model = None
        self._draft_params = draft_params
        # Real weights: restored from the checkpoint subsystem instead of a
        # fresh init (the reference reloads torchvision weights per worker,
        # scheduler.py:507-515; here orbax-style trees restore once and are
        # shared across replicas).
        if checkpoint_dir is not None and params is not None:
            raise ValueError(
                "pass either params or checkpoint_dir, not both — the "
                "checkpoint would be silently ignored"
            )
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_step = checkpoint_step
        # Weight-only int8 for the decode engines (engine-owned transform;
        # TP meshes unsupported — see DecodeEngine).
        self.quantize_weights = quantize_weights
        # Int8 KV cache (codes + per-row scales, KVCache docstring):
        # auto slot sizing sees the smaller pool bytes per slot and fits
        # ~2x the slots in the same HBM; the decode-scan bandwidth win
        # additionally requires the dequant fused into the attention
        # read (kernel path) — see KVCache.
        self.quantize_kv = quantize_kv
        # Paged KV pool (ISSUE 7): HBM occupancy follows cached tokens,
        # admission waits on pages, prefix/session reuse is by reference
        # (CoW). Draft models compose (ISSUE 13): speculative rounds
        # draft into scratch pages and commit accepted prefixes by
        # page-table splice — except on a multi-chip (TP) replica, where
        # the pool shards over the mesh's kv-head axis and spec+mesh
        # stays excluded (DecodeEngine raises loudly at build).
        # ``paged`` is a key with one legal value (ROADMAP D15).
        require_paged(paged)
        self.page_size = int(page_size)
        self.kv_pool_pages = kv_pool_pages
        # Token-budget chunked admission (ISSUE 15): the most prefill
        # tokens one scheduler round spends between decode turns.
        self.prefill_token_budget = prefill_token_budget
        self._dtype = dtype
        self._model = model
        self._params = params
        # Measured-table control (ref nexus.py:129-296 — profiled-latency-
        # driven planning): when ``profiles_dir`` holds committed
        # ``<model>_decode_summary.csv`` / ``<model>_prefill_summary.csv``
        # tables (tools/run_profiles.py --decode), single-chip engines
        # derive num_slots (if not pinned), decode_horizon, and
        # ttft_horizon from measurement + the token/TTFT SLOs instead of
        # the analytic HBM model — see plan_from_tables.
        self.profiles_dir = profiles_dir
        self.token_slo_ms = token_slo_ms
        self.ttft_slo_ms = ttft_slo_ms
        self._table_plans: Dict[int, Dict[str, int]] = {}
        self._init_lock = threading.Lock()

    def _ensure_model(self) -> None:
        with self._init_lock:
            if self._model is None:
                from ray_dynamic_batching_tpu.models import registry  # noqa: F401
                from ray_dynamic_batching_tpu.models.base import get_model

                kwargs = {"dtype": self._dtype} if self._dtype is not None else {}
                if self.quantize_kv:
                    import jax.numpy as jnp

                    kwargs["kv_dtype"] = jnp.int8
                self._model = get_model(self.model_name, **kwargs)
            elif self.quantize_kv:
                import jax.numpy as jnp

                if getattr(self._model, "kv_dtype", None) is None or (
                        jnp.dtype(self._model.kv_dtype)
                        != jnp.dtype(jnp.int8)):
                    # An injected model instance owns its cache dtype;
                    # silently serving a full-precision cache while the
                    # operator believes int8 is on would skew every
                    # HBM/slot-count decision downstream.
                    raise ValueError(
                        "quantize_kv=True but the injected model was not "
                        "built with kv_dtype=int8 — construct it with "
                        "CausalLM(..., kv_dtype=jnp.int8) or pass "
                        "model_name and let the deployment build it"
                    )
            if self._params is None:
                import jax

                self._params = self._model.init(jax.random.PRNGKey(0))
                if self.checkpoint_dir is not None:
                    from ray_dynamic_batching_tpu.runtime.checkpoint import (
                        CheckpointManager,
                    )

                    self._params = CheckpointManager(
                        self.checkpoint_dir
                    ).restore(self._params, step=self.checkpoint_step)
            if self.quantize_weights:
                from ray_dynamic_batching_tpu.models.quant import (
                    quantize_tree,
                )

                # Quantize ONCE here (idempotent): every length-bucket
                # engine shares the same int8 tree — per-engine
                # quantization would multiply resident copies by the
                # bucket count.
                self._params = quantize_tree(self._params)
            if self.draft_model_name is not None and self._draft_model is None:
                from ray_dynamic_batching_tpu.models.base import get_model

                kwargs = (
                    {"dtype": self._dtype} if self._dtype is not None else {}
                )
                self._draft_model = get_model(self.draft_model_name, **kwargs)
                if self._draft_params is None:
                    import jax

                    self._draft_params = self._draft_model.init(
                        jax.random.PRNGKey(1)
                    )

    def _prompt_buckets_for(self, max_len: int) -> Optional[List[int]]:
        """The prompt buckets an engine of ``max_len`` is built with
        (None: the engine's own)."""
        if self.prompt_buckets is None:
            return None
        fitting = [b for b in self.prompt_buckets if b <= max_len]
        return fitting or [max_len]

    def pool_bytes_per_slot(self, model: Any, max_len: int,
                            tp: int = 1) -> int:
        """What one slot's full page run occupies in the engine's pool,
        over all ``tp`` chips of its mesh:
        the bytes ``model.make_paged_cache`` allocates for
        ``pages_for(max_len)`` pages — read from the shapes it would make
        (rows of whole heads side by side or of one lane-padded head,
        ``pool_heads_per_row``; whole pages; the scale planes of an int8
        pool), not from a second formula. A 64-wide head that cannot pair
        off (an int8 pool, an odd head count) holds twice what
        ``kv_bytes_per_slot`` counts."""
        import jax

        from ray_dynamic_batching_tpu.ops.tile_math import pages_for

        n = pages_for(max_len, self.page_size)
        # a slot's ring (state by layer kind) is sized for the widest chunk
        buckets = self._prompt_buckets_for(max_len) or [
            b for b in DEFAULT_PROMPT_BUCKETS if b <= max_len]
        return jax.eval_shape(lambda: model.make_paged_cache(
            1, n, self.page_size, n * self.page_size,
            widest_chunk=max(buckets, default=None), tp=tp)).logical_bytes()

    def auto_num_slots(self, n_chips: int = 1,
                       max_len: Optional[int] = None,
                       budget_fraction: float = 1.0) -> int:
        """Size the continuous batch from the HBM budget (directive: slots
        from profile/HBM, not a guess): per CHIP, subtract this chip's
        weight shard, apply the planner's HBM fraction
        (``RDB_HBM_PLAN_FRACTION`` — same knob the Nexus packer uses), and
        fill the rest with slots, each priced at its full page run in the
        pool (:meth:`pool_bytes_per_slot`). TP replicas shard both weights
        and KV 1/n_chips, so per-chip terms divide through. Rounded down
        to a power of two (aligns prefill group widths). Prefix and
        session reuse pin pages INSIDE the pool (shed under pressure), so
        they cost no HBM of their own."""
        import jax
        import numpy as np

        from ray_dynamic_batching_tpu.utils.config import get_config

        self._ensure_model()
        cfg = get_config()

        from ray_dynamic_batching_tpu.models.quant import (
            tree_weight_bytes as tree_bytes,
        )

        # Snapshot the write-once model state under the init lock: these
        # attrs are published by _ensure_model under it, and a planner
        # thread may size slots while another deployment thread is still
        # initializing the draft pair.
        with self._init_lock:
            model, params = self._model, self._params
            draft_model = self._draft_model
            draft_params = self._draft_params

        # _ensure_model already quantized the params when requested, so a
        # plain byte count is exact for both modes.
        weights_bytes = tree_bytes(params) / max(1, n_chips)
        budget = float(cfg.hbm_budget_bytes)
        per_slot = float(
            self.pool_bytes_per_slot(model, max_len or self.max_len,
                                     tp=max(1, n_chips))
        ) / max(1, n_chips)
        if draft_model is not None:
            # Speculative decoding doubles the residency story: the draft's
            # weights leave the budget, and every slot also carries a draft
            # KV row (a slab row with spec-token headroom, not pages) —
            # omit either and the "fits" answer OOMs on the chip.
            weights_bytes += tree_bytes(draft_params) / max(1, n_chips)
            per_slot += float(
                draft_model.kv_bytes_per_slot(
                    (max_len or self.max_len) + self.spec_tokens + 1
                )
            ) / max(1, n_chips)
        usable = (
            (budget - weights_bytes) * cfg.hbm_plan_fraction * budget_fraction
        )
        n = int(max(1.0, usable / max(per_slot, 1.0)))
        n = min(n, 256)
        n = 2 ** int(np.log2(n)) if n > 1 else 1
        logger.info(
            "%s: auto num_slots=%d (%d chip(s), weights %.0f MB/chip, "
            "%.2f MB/slot/chip, budget %.0f GB/chip x %.2f)",
            self.model_name, n, n_chips, weights_bytes / 1e6,
            per_slot / 1e6, budget / 1e9, cfg.hbm_plan_fraction,
        )
        return n

    def plan_from_tables(
        self,
        decode_profile,
        prefill_profile=None,
        *,
        max_len: Optional[int] = None,
        token_slo_ms: Optional[float] = None,
        ttft_slo_ms: Optional[float] = None,
        num_slots: Optional[int] = None,
    ) -> Dict[str, int]:
        """Derive (num_slots, decode_horizon, ttft_horizon) from MEASURED
        decode tables + SLOs — the reference's profiled-latency control
        theory (``293-project/src/nexus.py:129-296``: committed tables
        drive admission/packing) applied to the decode phase, replacing
        the analytic HBM model of :meth:`auto_num_slots`:

        - **num_slots**: among measured (slots, capacity) configs whose
          program fits the planner's HBM budget and whose per-substep
          latency respects the token SLO, the one with the highest
          full-occupancy token throughput.
        - **decode_horizon**: tokens reach the host only at scan end, so a
          full-batch scan of ``h`` substeps delivers bursts with gaps of
          ``h x step_ms`` — the token-latency SLO bounds ``h``.
        - **ttft_horizon**: an idle-queue arrival waits out at most one
          ttft-tier scan, then prefills; the TTFT budget left after the
          measured prefill latency (largest prompt bucket, group 1),
          with 20% headroom for queue/dispatch, bounds the tier.

        ``num_slots`` pins the slot count (the colocation planner's
        placement dictates it): horizons are then derived from THAT
        config's measured step — horizons computed for a different batch
        size would silently re-break the SLO the scan length encodes.

        Tables are profiled at the model's default (bf16) cache. Planning
        an int8-KV deployment (``quantize_kv=True``) from them is SAFE
        but conservative: the quantized scan is faster and smaller than
        the measured rows, so slot counts and horizons under-promise —
        re-profile with the quantized model to plan at its true capacity.
        """
        from ray_dynamic_batching_tpu.utils.config import get_config

        cfg = get_config()
        budget = cfg.hbm_budget_bytes * cfg.hbm_plan_fraction / max(
            1, len(self.length_buckets)
        )
        max_len = max_len or self.max_len
        token_slo_ms = token_slo_ms or self.token_slo_ms
        ttft_slo_ms = ttft_slo_ms or self.ttft_slo_ms
        candidates = [
            r for r in decode_profile.rows
            if r.seq_len == max_len and r.hbm_bytes > 0
        ]
        if num_slots is not None:
            # Pin BEFORE the budget filter: a caller-pinned config (the
            # colocation planner's placement) was already validated
            # against the planner's own HBM budget — re-filtering it
            # against the deployment's per-bucket slice would reject a
            # measured row that exists and silently fall back to default
            # horizons, the exact burst-SLO breach the pin prevents.
            rows = [r for r in candidates if r.batch_size == num_slots]
            if not rows:
                raise ValueError(
                    f"{self.model_name}: no measured decode row at "
                    f"(slots={num_slots}, cap={max_len}) to derive "
                    "horizons from"
                )
        else:
            rows = [r for r in candidates if r.hbm_bytes <= budget]
        if token_slo_ms is not None:
            fitting = [r for r in rows if r.latency_ms <= token_slo_ms]
            if not fitting and rows:
                # Nothing meets the SLO: serve with the fastest config
                # rather than refusing (the SLO viewer will show red).
                fitting = [min(rows, key=lambda r: r.latency_ms)]
            rows = fitting
        if not rows:
            raise ValueError(
                f"{self.model_name}: no measured decode config at "
                f"capacity {max_len} fits the HBM budget "
                f"({budget / 1e9:.1f} GB) — re-run the decode profiler"
            )
        best = max(rows, key=lambda r: r.batch_size / r.latency_ms)
        step_ms = best.latency_ms
        plan: Dict[str, int] = {"num_slots": int(best.batch_size)}
        horizon = self.decode_horizon
        if token_slo_ms is not None:
            horizon = max(1, int(token_slo_ms // step_ms))
            plan["decode_horizon"] = horizon
        if ttft_slo_ms is not None:
            prefill_ms = 0.0
            if prefill_profile is not None and prefill_profile.rows:
                largest = max(r.seq_len for r in prefill_profile.rows)
                singles = [
                    r for r in prefill_profile.rows
                    if r.seq_len == largest and r.batch_size == 1
                ] or [r for r in prefill_profile.rows
                      if r.seq_len == largest]
                prefill_ms = singles[0].latency_ms
            scan_budget = 0.8 * ttft_slo_ms - prefill_ms
            plan["ttft_horizon"] = int(
                min(max(1, scan_budget // step_ms), horizon)
            )
        logger.info(
            "%s: table plan at cap %d -> %s (step %.2f ms, %d candidate "
            "rows)", self.model_name, max_len, plan, step_ms, len(rows),
        )
        return plan

    def _table_plan(
        self, max_len: int, num_slots: Optional[int] = None,
    ) -> Optional[Dict[str, int]]:
        """Load committed tables from ``profiles_dir`` once per
        (capacity, pinned-slots) config; None when the decode table is
        absent (callers fall back to the analytic path)."""
        import os

        if self.profiles_dir is None:
            return None
        cache_key = (max_len, num_slots)
        if cache_key in self._table_plans:
            return self._table_plans[cache_key]
        from ray_dynamic_batching_tpu.profiles.table import BatchProfile

        decode_csv = os.path.join(
            self.profiles_dir, f"{self.model_name}_decode_summary.csv"
        )
        if not os.path.exists(decode_csv):
            logger.warning(
                "%s: profiles_dir=%s has no decode table — falling back "
                "to the analytic HBM model", self.model_name,
                self.profiles_dir,
            )
            return None
        decode_profile = BatchProfile.from_csv(
            f"{self.model_name}_decode", decode_csv
        )
        prefill_csv = os.path.join(
            self.profiles_dir, f"{self.model_name}_prefill_summary.csv"
        )
        prefill_profile = None
        if os.path.exists(prefill_csv):
            prefill_profile = BatchProfile.from_csv(
                f"{self.model_name}_prefill", prefill_csv
            )
        try:
            plan = self.plan_from_tables(
                decode_profile, prefill_profile, max_len=max_len,
                num_slots=num_slots,
            )
        except ValueError as e:
            # A table that exists but has no row at this capacity (swept at
            # different max_lens) must degrade exactly like a missing
            # table — raising here would crash-loop every replica start
            # until the controller marks the deployment unhealthy.
            logger.warning(
                "%s: committed tables unusable at capacity %d (%s) — "
                "falling back to the analytic HBM model",
                self.model_name, max_len, e,
            )
            plan = None
        self._table_plans[cache_key] = plan
        return plan

    def build_engine(
        self, queue: RequestQueue, device: Any = None, mesh: Any = None,
        max_len: Optional[int] = None, num_slots: Optional[int] = None,
    ) -> DecodeEngine:
        # ``num_slots`` override: the colocation control loop passes the
        # planner's placement shape (scheduler/llm_control.py) — an
        # explicit measured config outranks both the table plan and the
        # analytic HBM model below.
        self._ensure_model()
        # Same snapshot discipline as auto_num_slots: the model/param
        # pairs are published under _init_lock by _ensure_model.
        with self._init_lock:
            model, params = self._model, self._params
            draft_model = self._draft_model
            draft_params = self._draft_params
        max_len = max_len or self.max_len
        num_slots = num_slots if num_slots is not None else self.num_slots
        decode_horizon = self.decode_horizon
        ttft_horizon = self.ttft_horizon
        # Measured tables govern single-chip engines (they are per-chip
        # measurements; a TP mesh shards the program they describe). ANY
        # pinned slot count — the caller's colocation placement or the
        # deployment config's own num_slots — pins the plan to ITS
        # measured row, so the horizons below always describe the config
        # that actually runs, never the table's (different) best row.
        plan = (
            self._table_plan(
                max_len, num_slots=num_slots if num_slots > 0 else None
            )
            if mesh is None else None
        )
        if plan is not None:
            if num_slots <= 0:
                num_slots = plan["num_slots"]
            decode_horizon = plan.get("decode_horizon", decode_horizon)
            ttft_horizon = plan.get("ttft_horizon", ttft_horizon)
        elif num_slots <= 0:
            n_chips = mesh.devices.size if mesh is not None else 1
            num_slots = self.auto_num_slots(
                n_chips, max_len=max_len,
                budget_fraction=1.0 / len(self.length_buckets),
            )
        prompt_buckets = self._prompt_buckets_for(max_len)
        return DecodeEngine(
            model,
            params,
            queue,
            num_slots=num_slots,
            max_len=max_len,
            prompt_buckets=prompt_buckets,
            eos_token_id=self.eos_token_id,
            default_max_new_tokens=self.default_max_new_tokens,
            decode_horizon=decode_horizon,
            ttft_horizon=ttft_horizon,
            max_admissions_per_step=self.max_admissions_per_step,
            prefix_cache_size=self.prefix_cache_size,
            session_cache_size=self.session_cache_size,
            draft_model=draft_model,
            draft_params=draft_params,
            spec_tokens=self.spec_tokens,
            quantize_weights=self.quantize_weights,
            device=device,
            mesh=mesh,
            page_size=self.page_size,
            kv_pool_pages=self.kv_pool_pages,
            host_spill_pages=self.host_spill_pages,
            prefill_token_budget=self.prefill_token_budget,
        )

    # Controller protocol: factories exposing make_replica own replica
    # construction (the reference's deployment holds its replica class the
    # same way — deployment_state builds ReplicaActor from the deployment's
    # target state). ``devices`` arrives from the replica's placement-group
    # bundle when the deployment reserves chips.
    def make_replica(
        self, replica_id: str, config: Any, devices: Optional[Sequence] = None,
    ) -> LLMReplica:
        device = None
        mesh = None
        if devices and len(devices) > 1 and self.quantize_weights:
            # Fail BEFORE the mesh/engine build (and before the placement
            # group's chips are consumed by a doomed start).
            raise ValueError(
                f"{config.name}: quantize_weights is not supported for "
                "multi-chip (TP) replicas yet — drop chips_per_replica or "
                "the quantization flag"
            )
        if devices and len(devices) > 1:
            # Multi-chip bundle -> TP-sharded replica over its own mesh
            # slice (replica = mesh slice, SURVEY.md §7 stage 6).
            from ray_dynamic_batching_tpu.parallel.mesh import (
                MeshConfig,
                build_mesh,
            )

            mesh = build_mesh(MeshConfig(tp=len(devices)), list(devices))
        elif devices:
            device = devices[0]
        builders = {
            bucket: (
                lambda q, b=bucket: self.build_engine(
                    q, device=device, mesh=mesh, max_len=b
                )
            )
            for bucket in self.length_buckets
        }
        replica = LLMReplica(
            replica_id=replica_id,
            deployment=config.name,
            engine_builders=builders,
            max_ongoing_requests=config.max_ongoing_requests,
            warmup=self.warmup,
            default_max_new_tokens=self.default_max_new_tokens,
        )
        replica.devices = list(devices) if devices else None
        if self.session_cache_size > 0:
            # Session-affinity ids ride the replica's advertised multiplex
            # LRU; with the default bound of 8, more concurrent sessions
            # than that would age each other (and genuine model ids) out
            # of the routing view while their KV rows are still cached.
            replica.max_multiplexed_models = max(
                replica.max_multiplexed_models,
                len(self.length_buckets) * self.session_cache_size + 8,
            )
        return replica

    # Legacy callable protocol (factory() -> fn) is not meaningful here.
    def __call__(self) -> Callable[[List[Any]], Sequence[Any]]:
        raise TypeError(
            "LLMDeployment builds replicas via make_replica; register it "
            "with the controller directly"
        )
