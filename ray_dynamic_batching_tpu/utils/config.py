"""Typed, env-overridable framework configuration.

TPU-native analogue of the reference's ``RayConfig`` flag system
(``src/ray/common/ray_config_def.h:23`` — 218 ``RAY_CONFIG(type, name, default)``
entries overridable via ``RAY_<name>`` env vars). Here every field of
:class:`RDBConfig` is overridable via ``RDB_<NAME>`` environment variables, with
type coercion derived from the dataclass annotation.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Optional


def _coerce(value: str, typ: Any) -> Any:
    if typ is bool:
        return value.strip().lower() in ("1", "true", "yes", "on")
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    if typ is str:
        return value
    # Optional[X] / unions: try int, float, fall back to str.
    for t in (int, float):
        try:
            return t(value)
        except ValueError:
            continue
    return value


@dataclasses.dataclass
class RDBConfig:
    """All framework knobs in one place. Override any field with ``RDB_<NAME>``.

    Grouped the way the reference groups ``ray_config_def.h``: scheduling,
    batching, memory, control-plane timing, transport, observability, testing.
    """

    # --- scheduling (ref: 293-project/src/scheduler.py:28, nexus.py:154) ---
    # SLO safety divisor applied at schedule time (ref SLO_hack=2.2, scheduler.py:28).
    slo_safety_factor: float = 2.2
    # Fraction of the (safety-adjusted) SLO a saturated batch may spend computing
    # (Nexus "SLO/2" rule, nexus.py:154).
    slo_compute_fraction: float = 0.5
    # Rate-change fraction that triggers a reschedule (ref scheduler.py:794).
    rate_change_threshold: float = 0.05
    # Multiplier on the threshold for rate *decreases* (ref scheduler.py:798-801).
    rate_decrease_multiplier: float = 2.0
    # Seconds between control-loop monitoring passes (ref monitoring_interval=5).
    monitoring_interval_s: float = 5.0
    # Sliding window for request-rate estimation (ref RequestTracker window).
    rate_window_s: float = 10.0
    # Cold-window replan guard: suppress rate-change replans for models whose
    # sliding window covers fewer than this many seconds (a half-filled window
    # under-reads by up to 1/span and the monitor scales DOWN during rampup).
    # 0.0 = react immediately (the reference's behavior).
    rate_min_span_s: float = 0.0

    # --- batching / bucketing (TPU-first: XLA compiles per shape bucket) ---
    # Batch buckets are rounded up to the nearest of these (powers of two by
    # default keep the jit cache small; profile rows exist per bucket).
    max_batch_size: int = 1024
    # Opportunistic batching defaults (ref serve/batching.py:530).
    default_batch_wait_timeout_s: float = 0.01
    default_max_batch_size: int = 32
    # Sequence buckets for LLM prefill (powers of two from min upward).
    min_seq_bucket: int = 32
    max_seq_len: int = 8192

    # --- memory (HBM replaces the reference's gpu_mem budget, nexus.py:156) ---
    # Per-chip HBM budget in bytes (v5e = 16 GiB; leave headroom for XLA scratch).
    hbm_budget_bytes: int = 14 * 1024**3
    # Fraction of HBM the scheduler may plan against (scratch/fragmentation slack).
    hbm_plan_fraction: float = 0.9

    # --- compile management (no GPU analogue; XLA-specific) ---
    # Estimated cost charged to a migration that requires a fresh XLA compile.
    compile_cost_default_ms: float = 5000.0
    # Number of schedule intervals over which compile cost is amortized when
    # judging merge feasibility.
    compile_amortization_intervals: int = 60

    # --- queues (ref 293-project/src/scheduler.py:190) ---
    max_queue_len: int = 4096
    # Drop requests whose deadline cannot be met given profiled batch latency
    # (staleness discard, ref scheduler.py:281-283).
    discard_stale_requests: bool = True

    # --- control plane / runtime (ref: gcs health checks, ray_config_def.h:846) ---
    health_check_period_ms: int = 1000
    health_check_timeout_ms: int = 5000
    health_check_failure_threshold: int = 5
    actor_max_restarts: int = 3
    controller_checkpoint_period_s: float = 5.0

    # --- transport ---
    ingress_host: str = "0.0.0.0"
    ingress_port: int = 8265
    metrics_port: int = 9464

    # --- observability ---
    metrics_report_interval_s: float = 5.0
    slo_good_threshold: float = 0.98   # ref metrics_display.py:65
    slo_warn_threshold: float = 0.95

    # --- testing / chaos (ref: src/ray/rpc/rpc_chaos.cc:32) ---
    # Format: "method=N[,method=N...]" — fail the first N calls of `method`.
    testing_rpc_failure: str = ""
    # Deterministic seed for chaos injection.
    chaos_seed: int = 0

    @classmethod
    def from_env(cls, **overrides: Any) -> "RDBConfig":
        import typing

        hints = typing.get_type_hints(cls)  # resolves PEP 563 string annotations
        kwargs: dict[str, Any] = {}
        for f in dataclasses.fields(cls):
            env_key = "RDB_" + f.name.upper()
            if env_key in os.environ:
                try:
                    kwargs[f.name] = _coerce(os.environ[env_key], hints[f.name])
                except ValueError as e:
                    raise ValueError(f"bad value for {env_key}: {e}") from e
        kwargs.update(overrides)
        return cls(**kwargs)


_global_config: Optional[RDBConfig] = None
_lock = threading.Lock()


def get_config() -> RDBConfig:
    """Process-wide config singleton (env-initialized on first use)."""
    global _global_config
    if _global_config is None:
        with _lock:
            if _global_config is None:
                _global_config = RDBConfig.from_env()
    return _global_config


def set_config(cfg: RDBConfig) -> None:
    global _global_config
    with _lock:
        _global_config = cfg


def reset_config() -> None:
    global _global_config
    with _lock:
        _global_config = None
