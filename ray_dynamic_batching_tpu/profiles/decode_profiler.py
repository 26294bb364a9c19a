"""Decode-phase profiler: measured tables for the LLM serving control loop.

The reference's core control theory is profiled-latency-driven planning —
its committed profiler CSVs ARE the scheduler's input
(``293-project/profiling/*_summary.csv`` consumed at
``293-project/src/scheduler.py:1019-1041``; packing logic
``293-project/src/nexus.py:129-296``). The forward-pass profiler covers
the vision/encoder path; this module extends the same committed-table
contract to the continuous-batching DECODE engine, whose cost axes are
different:

- **Decode step**: per-substep latency + program HBM vs
  ``num_slots`` (batch occupancy) x ``max_len`` (KV capacity). The paged
  kernel stops at a slot's length, so the step is timed with every slot
  one position short of ``max_len``: one row per (slots, capacity) config
  bounds the whole sequence from above.
- **Prefill**: the chunk program's latency vs (prompt bucket x group
  width) — the TTFT-side cost.

Rows reuse :class:`~ray_dynamic_batching_tpu.profiles.table.ProfileRow`
(decode: ``batch_size``=num_slots, ``seq_len``=KV capacity, throughput =
tokens/s at full occupancy; prefill: ``batch_size``=group width,
``seq_len``=prompt bucket), so the CSV/report/store machinery and the
committed-table contract are identical across profile families. Tables
land as ``<model>_decode_summary.csv`` / ``<model>_prefill_summary.csv``
and feed :meth:`LLMDeployment.plan_from_tables`, which derives num_slots /
decode_horizon / ttft_horizon from measurement + SLOs instead of the
analytic HBM model.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.models.base import ServableModel
from ray_dynamic_batching_tpu.profiles.profiler import _is_oom
from ray_dynamic_batching_tpu.profiles.table import BatchProfile, ProfileRow
from ray_dynamic_batching_tpu.utils.logging import get_logger

logger = get_logger("decode_profiler")


def _program_hbm(compiled) -> int:
    mem = compiled.memory_analysis()
    if mem is None:
        return 0
    return int(
        getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        + getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "generated_code_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
    )


class DecodeProfiler:
    """Sweeps a model's decode engine across (num_slots, capacity) and
    (prompt bucket, group) configs on the live backend."""

    def __init__(
        self,
        model: ServableModel,
        params=None,
        timing_iters: int = 8,
        warmup_iters: int = 2,
        max_consecutive_errors: int = 2,
    ):
        from ray_dynamic_batching_tpu.utils import compile_cache

        compile_cache.enable()
        self.model = model
        self.params = params
        self.timing_iters = max(2, timing_iters)
        self.warmup_iters = max(1, warmup_iters)
        self.max_consecutive_errors = max_consecutive_errors

    def _ensure_params(self):
        if self.params is None:
            self.params = self.model.init(jax.random.PRNGKey(0))
        return self.params

    def _engine(self, num_slots: int, max_len: int,
                prompt_bucket: int, group: int) -> DecodeEngine:
        queue = RequestQueue(self.model.name, max_len=max(64, num_slots))
        return DecodeEngine(
            self.model, self._ensure_params(), queue,
            num_slots=num_slots, max_len=max_len,
            prompt_buckets=[prompt_bucket], decode_horizon=1,
            max_admissions_per_step=group,
        )

    # --- decode step -------------------------------------------------------
    def profile_decode_config(
        self, num_slots: int, max_len: int
    ) -> Optional[ProfileRow]:
        """One (slots, capacity) config: AOT-compile the engine's own
        decode program (donation included — the serving path's exact
        memory behavior), read its HBM footprint from XLA's memory
        analysis, then time chained single-substep dispatches with one
        scalar fetch per timing block (in-order execution makes the last
        step's value cover every dispatched step).
        None if the program is infeasible (OOM)."""
        engine = self._engine(num_slots, max_len, prompt_bucket=8, group=1)
        try:
            B = num_slots
            (samp_f, samp_i, bias_ids, bias_vals) = \
                engine._sampling_arrays()
            # Rows: pending tokens / active mask / sample index / use the
            # carry — the engine's single per-dispatch upload, all slots
            # active, each step fetched before the next (no carry).
            step_state = jnp.stack([
                jnp.ones((B,), jnp.int32),
                jnp.ones((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
            ])
            fn = jax.jit(
                engine._decode_impl, donate_argnums=(1, 8),
                static_argnums=(3,),
            )
            # Full rows (pages laid out slot by slot): the scan walks
            # every page a slot can hold.
            NP = engine._n_table_entries
            cache = engine._cache.replace(
                page_table=engine._put(np.arange(
                    B * NP, dtype=np.int32).reshape(B, NP)),
                lengths=engine._put(
                    np.full((B,), max_len - 1, np.int32)),
            )
            args = (engine.params, cache, step_state, 1,
                    samp_f, samp_i, bias_ids, bias_vals, engine._counts,
                    engine._carry)
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            compile_ms = (time.perf_counter() - t0) * 1000.0
            hbm_bytes = _program_hbm(compiled)

            counts, carry = engine._counts, engine._carry
            run_args = lambda: (engine.params, cache, step_state,  # noqa: E731
                                samp_f, samp_i, bias_ids,
                                bias_vals, counts, carry)
            for _ in range(self.warmup_iters):
                packed, cache, counts, carry = compiled(*run_args())
            float(np.asarray(packed)[0, 0])
            samples = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(self.timing_iters):
                    packed, cache, counts, carry = compiled(*run_args())
                float(np.asarray(packed)[0, 0])  # host fetch = completion
                samples.append(
                    (time.perf_counter() - t0) * 1000.0 / self.timing_iters
                )
        except Exception as e:  # noqa: BLE001 — XLA raises backend types
            if _is_oom(e):
                logger.warning(
                    "%s decode slots=%d cap=%d infeasible (OOM)",
                    self.model.name, num_slots, max_len,
                )
                return None
            raise
        finally:
            engine.release_buffers()
        return ProfileRow(
            batch_size=num_slots,
            seq_len=max_len,
            latency_ms=float(np.mean(samples)),
            latency_std_ms=float(np.std(samples)),
            hbm_bytes=hbm_bytes,
            compile_ms=compile_ms,
        )

    # --- prefill -----------------------------------------------------------
    def profile_prefill_config(
        self, prompt_bucket: int, group: int, max_len: int
    ) -> Optional[ProfileRow]:
        """One (prompt bucket, group width) admission program: the
        engine's chunk program, every row a whole prompt's final chunk
        written into its own pages."""
        num_slots = max(2, group)
        engine = self._engine(num_slots, max_len, prompt_bucket, group)
        try:
            packed, f = engine._new_chunk_group(group, prompt_bucket)
            f.tokens[:] = 1
            f.mask[:] = 1
            NP = engine._n_table_entries
            f.table[:] = np.arange(group * NP, dtype=np.int32).reshape(
                group, NP)
            # slot / start / take_idx / top_k / seed / new_len
            f.meta_i[:, 0] = np.arange(group) % num_slots
            f.meta_i[:, 2] = prompt_bucket - 1
            f.meta_i[:, 5] = prompt_bucket
            packed = jnp.asarray(packed)
            fn = jax.jit(engine._chunk_group_paged_impl,
                         donate_argnums=(2,))
            args = (engine.params, packed, engine._cache)
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            compile_ms = (time.perf_counter() - t0) * 1000.0
            hbm_bytes = _program_hbm(compiled)

            cache = engine._cache
            for _ in range(self.warmup_iters):
                first, cache = compiled(engine.params, packed, cache)
            float(np.asarray(first)[0])
            samples = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(self.timing_iters):
                    first, cache = compiled(engine.params, packed, cache)
                float(np.asarray(first)[0])
                samples.append(
                    (time.perf_counter() - t0) * 1000.0 / self.timing_iters
                )
        except Exception as e:  # noqa: BLE001
            if _is_oom(e):
                logger.warning(
                    "%s prefill bucket=%d group=%d infeasible (OOM)",
                    self.model.name, prompt_bucket, group,
                )
                return None
            raise
        finally:
            engine.release_buffers()
        return ProfileRow(
            batch_size=group,
            seq_len=prompt_bucket,
            latency_ms=float(np.mean(samples)),
            latency_std_ms=float(np.std(samples)),
            hbm_bytes=hbm_bytes,
            compile_ms=compile_ms,
        )

    # --- sweeps ------------------------------------------------------------
    def sweep(
        self,
        slot_buckets: Sequence[int] = (4, 8, 16, 32, 64, 128),
        capacities: Sequence[int] = (256,),
        prompt_buckets: Sequence[int] = (16, 64),
        group_sizes: Sequence[int] = (1, 2, 4),
    ) -> Tuple[BatchProfile, BatchProfile]:
        """Returns (decode profile, prefill profile). Slot sweeps stop at
        the HBM edge (profiler-stopped, not config-stopped) after
        ``max_consecutive_errors`` infeasible configs."""
        decode = BatchProfile(f"{self.model.name}_decode")
        for cap in capacities:
            errors = 0
            for slots in slot_buckets:
                row = self.profile_decode_config(slots, cap)
                if row is None:
                    errors += 1
                    if errors >= self.max_consecutive_errors:
                        break
                    continue
                errors = 0
                decode.add(row)
                logger.info(
                    "%s decode slots=%d cap=%d: %.2f ms/substep "
                    "(%.0f tok/s full), %.0f MB",
                    self.model.name, slots, cap, row.latency_ms,
                    slots * 1000.0 / row.latency_ms, row.hbm_bytes / 1e6,
                )
        prefill = BatchProfile(f"{self.model.name}_prefill")
        cap = max(capacities)
        for bucket in prompt_buckets:
            if bucket >= cap:
                continue
            for group in group_sizes:
                row = self.profile_prefill_config(bucket, group, cap)
                if row is None:
                    continue
                prefill.add(row)
                logger.info(
                    "%s prefill bucket=%d group=%d: %.2f ms",
                    self.model.name, bucket, group, row.latency_ms,
                )
        return decode, prefill
