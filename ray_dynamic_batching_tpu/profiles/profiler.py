"""Offline model profiler — sweeps (batch, seq) buckets on the live backend.

TPU-native re-design of the reference's ``ModelProfiler``
(``293-project/profiling/ModelProfiler.py:92-109`` CUDA-event timing,
``:85-90`` peak memory via ``max_memory_allocated``, ``:163-211`` OOM
tolerance + early stop; driven by ``run_profiler.py:191-196`` batch sweep
1→512). Differences forced by the XLA compilation model:

- Buckets, not arbitrary sizes: every (batch, seq) is a separate compiled
  program, so the sweep walks power-of-two buckets and records ``compile_ms``
  (the reference assumes any batch is instantly runnable — SURVEY.md §7(a)).
- Memory is read from XLA's compiled-program ``memory_analysis()`` (argument +
  output + temp + generated code size), not an allocator high-water mark —
  exact, available without running, and includes the weights the program holds
  resident in HBM.
- Timing dispatches the **already-compiled** executable (the same one the
  compile_ms/memory numbers describe — one compile per bucket) many times
  and fetches one scalar at the end: the device executes programs in
  order, so the final step's value reaching the host covers every
  dispatched step (``block_until_ready`` on it would serve as well; the
  fetch keeps one completion signal for every backend).
- OOM tolerance: RESOURCE_EXHAUSTED from compile or run marks the bucket
  infeasible; after ``max_consecutive_errors`` the sweep stops early.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_dynamic_batching_tpu.models.base import ServableModel
from ray_dynamic_batching_tpu.profiles.table import (
    BatchProfile,
    ProfileRow,
    default_batch_buckets,
)
from ray_dynamic_batching_tpu.utils.logging import get_logger

logger = get_logger("profiler")


def _is_oom(err: Exception) -> bool:
    msg = str(err)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg or "OOM" in msg


def _fetch_scalar(out) -> float:
    """Host fetch of one scalar: the timed region ends when a value the
    last step computed has reached the host."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(np.ravel(np.asarray(leaf))[0])


def timed_steps_ms(compiled, params, inputs, iters: int, warmup: int = 1):
    """Per-step latency samples for an already-compiled executable.

    Dispatches ``iters`` async calls and fetches one scalar from the last
    output: the device executes programs in order, so the final fetch
    observes every step, and per-call dispatch overhead is included — which
    is exactly the serving hot path (the engine dispatches each batch from
    the host too). Reuses the executable the scheduler's compile_ms/memory
    numbers describe, so each bucket pays XLA compilation exactly once.
    """
    out = None
    for _ in range(max(warmup, 1)):
        out = compiled(params, *inputs)
    _fetch_scalar(out)
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = compiled(params, *inputs)
        _fetch_scalar(out)
        samples.append((time.perf_counter() - t0) * 1000.0 / iters)
    return samples


class ModelProfiler:
    """Profiles one model's apply fn across shape buckets."""

    def __init__(
        self,
        model: ServableModel,
        params=None,
        warmup_iters: int = 2,
        timing_iters: int = 5,
        max_consecutive_errors: int = 3,
        donate: bool = False,
    ):
        from ray_dynamic_batching_tpu.utils import compile_cache

        compile_cache.enable()  # sweep re-runs reuse compiled buckets
        self.model = model
        self.params = params
        self.warmup_iters = warmup_iters
        self.timing_iters = timing_iters
        self.max_consecutive_errors = max_consecutive_errors

    def _ensure_params(self):
        if self.params is None:
            self.params = self.model.init(jax.random.PRNGKey(0))
        return self.params

    def profile_bucket(
        self, batch_size: int, seq_len: int = 0
    ) -> Optional[ProfileRow]:
        """Compile + time one bucket; None if infeasible (OOM)."""
        params = self._ensure_params()
        inputs = self.model.example_inputs(batch_size, seq_len or None)
        fn = jax.jit(self.model.apply)
        try:
            t0 = time.perf_counter()
            lowered = fn.lower(params, *inputs)
            compiled = lowered.compile()
            compile_ms = (time.perf_counter() - t0) * 1000.0

            mem = compiled.memory_analysis()
            hbm_bytes = 0
            if mem is not None:
                hbm_bytes = int(
                    getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)
                    + getattr(mem, "generated_code_size_in_bytes", 0)
                    - getattr(mem, "alias_size_in_bytes", 0)
                )

            samples = timed_steps_ms(
                compiled, params, inputs,
                iters=max(self.timing_iters, 2), warmup=self.warmup_iters,
            )
        except Exception as e:  # noqa: BLE001 — XLA raises backend-specific types
            if _is_oom(e):
                logger.warning(
                    "%s batch=%d seq=%d infeasible (OOM)",
                    self.model.name, batch_size, seq_len,
                )
                return None
            raise
        return ProfileRow(
            batch_size=batch_size,
            seq_len=seq_len,
            latency_ms=float(np.mean(samples)),
            latency_std_ms=float(np.std(samples)),
            hbm_bytes=hbm_bytes,
            compile_ms=compile_ms,
        )

    def sweep(
        self,
        batch_buckets: Optional[Sequence[int]] = None,
        seq_buckets: Sequence[int] = (0,),
        max_batch: int = 512,
    ) -> BatchProfile:
        """Full sweep (ref: ProfilerRunner loop, run_profiler.py:191-211)."""
        buckets = list(batch_buckets or default_batch_buckets(max_batch))
        profile = BatchProfile(self.model.name)
        for seq in seq_buckets:
            consecutive_errors = 0
            for b in buckets:
                row = self.profile_bucket(b, seq)
                if row is None:
                    consecutive_errors += 1
                    if consecutive_errors >= self.max_consecutive_errors:
                        logger.warning(
                            "%s: stopping sweep at seq=%d after %d errors",
                            self.model.name, seq, consecutive_errors,
                        )
                        break
                    continue
                consecutive_errors = 0
                profile.add(row)
                logger.info(
                    "%s b=%d s=%d: %.2f ms, %.1f sps, %.0f MB, compile %.0f ms",
                    self.model.name, b, seq, row.latency_ms,
                    row.with_throughput().throughput_sps,
                    row.hbm_bytes / 1e6, row.compile_ms,
                )
        return profile

    def write_outputs(self, profile: BatchProfile, out_dir: str) -> Tuple[str, str, str]:
        """Persist summary.csv / detailed.json / report.txt (reference contract,
        ``ModelProfiler.py:224-371``)."""
        return write_profile_outputs(profile, out_dir)


def write_profile_outputs(
    profile: BatchProfile, out_dir: str
) -> Tuple[str, str, str]:
    """Shared writer for every profile family (forward-pass, decode,
    prefill): summary.csv / detailed.json / report.txt keyed by the
    profile's model_name."""
    import os

    os.makedirs(out_dir, exist_ok=True)
    base = os.path.join(out_dir, profile.model_name)
    csv_path, json_path, report_path = (
        base + "_summary.csv", base + "_detailed.json", base + "_report.txt",
    )
    profile.to_csv(csv_path)
    with open(json_path, "w") as f:
        f.write(profile.to_json())
    with open(report_path, "w") as f:
        f.write(profile.report())
    return csv_path, json_path, report_path
