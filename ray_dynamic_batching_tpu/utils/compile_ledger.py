"""Runtime compile flight recorder — the jit layer's hop ledger.

One silent mid-serving XLA recompile costs more than a thousand decode
turns, and nothing in the stack proved it never happens. This module
hooks ``jax.monitoring``'s compilation callbacks and attributes every
compile to the jit program that triggered it:

- ``instrument(name, fn)`` wraps a compiled callable; while a wrapped
  call is on the stack, any compile event that fires is charged to
  ``name``. A cached dispatch fires ZERO events, so the wrapper's
  steady-state cost is one thread-local push/pop. One wrapped call in
  which any event fired counts as ONE **compile episode** — jax emits
  several ``backend_compile`` bursts per trace (three on a first call,
  two on a retrace, measured), so raw events are the wrong unit.
- a phase machine (``startup`` → ``warmup`` → ``steady``) driven by
  ``begin_warmup()``/``end_warmup()`` around ``DecodeEngine.warmup()``
  (depth-counted: nested warmups — multi-engine processes — re-enter
  the warmup phase). The first ``end_warmup`` that unwinds to depth 0
  arms the **steady-state mark**: every later episode is a recorded
  violation carrying the function, argument shapes, and triggering
  callsite — a named guilty hop, never a mystery stall.
- every episode increments ``rdb_jit_compiles_total{fn,phase}`` (fn
  label bounded — an unbounded cardinality bug cannot mint series) and
  emits a ``jit.compile`` tracer span so recompiles join the PR-1
  flight record and the PR-8 hop ledger.

Compiles with no wrapped call on the stack land under
``__unattributed__`` with a callsite walked from the Python stack; for
those the episode unit degrades to one-per-``backend_compile``-burst
(there is no call boundary to coalesce on — documented, not hidden).

``tools/check_compiles.py`` is the CI gate over this ledger: warmup
plus a canonical serving segment must stay inside the ratcheted budget
(``tools/compile_budget.json``) with ZERO steady-phase episodes.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_dynamic_batching_tpu.utils import metrics as m
from ray_dynamic_batching_tpu.utils.concurrency import (
    OrderedLock,
    assert_owner,
)
from ray_dynamic_batching_tpu.utils.logging import get_logger
from ray_dynamic_batching_tpu.utils.tracing import tracer

logger = get_logger("compile_ledger")

UNATTRIBUTED = "__unattributed__"

PHASE_STARTUP = "startup"
PHASE_WARMUP = "warmup"
PHASE_STEADY = "steady"

# Event names jax.monitoring emits per compilation stage (duration
# listeners). Any of them firing means real (re)compilation work — a
# cached dispatch emits none.
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"

# Hot-path fn labels are a small closed set (ops/jit_model.py registry
# + __unattributed__); 16 leaves headroom without unbounding the series.
COMPILES = m.Counter(
    "rdb_jit_compiles_total",
    "XLA compile episodes by jit program and ledger phase "
    "(startup | warmup | steady — steady MUST stay 0 in serving)",
    tag_keys=("fn", "phase"),
    bounded_tags={"fn": 16},
)


class SteadyStateViolation(RuntimeError):
    """A compile landed after the steady-state mark (post-warmup)."""


_tls = threading.local()


class _Frame:
    __slots__ = ("name", "fired", "trace_ms", "lower_ms", "compile_ms")

    def __init__(self, name: str) -> None:
        self.name = name
        self.fired = False
        self.trace_ms = 0.0
        self.lower_ms = 0.0
        self.compile_ms = 0.0


def _frames() -> List[_Frame]:
    stack = getattr(_tls, "frames", None)
    if stack is None:
        stack = _tls.frames = []
    return stack


def current_program() -> str:
    """Name of the instrumented program executing (tracing, on a first
    call) on this thread; "" outside one. Trace-time records
    (``ops/attention.py``) use it to say WHICH program took a path."""
    stack = _frames()
    return stack[-1].name if stack else ""


def _shape_sig(args: Tuple[Any, ...], limit: int = 12) -> str:
    """Compact shape/dtype signature of a call's positional args —
    attribution detail for episodes, computed ONLY when one fired."""
    parts: List[str] = []
    for a in args[:limit]:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        elif isinstance(a, (int, float, bool)):
            parts.append(repr(a))
        elif isinstance(a, (tuple, list)):
            parts.append(f"{type(a).__name__}({len(a)})")
        else:
            parts.append(type(a).__name__)
    if len(args) > limit:
        parts.append("...")
    return f"({', '.join(parts)})"


def _callsite() -> str:
    """First stack frame outside jax and this module — the code that
    triggered the compile, repo-relative when possible."""
    for fr in reversed(traceback.extract_stack()):
        fn = fr.filename.replace("\\", "/")
        if "/jax/" in fn or "/jaxlib/" in fn or fn.endswith(
            "/utils/compile_ledger.py"
        ):
            continue
        for marker in ("ray_dynamic_batching_tpu/", "tools/", "tests/"):
            i = fn.find(marker)
            if i >= 0:
                fn = fn[i:]
                break
        return f"{fn}:{fr.lineno} ({fr.name})"
    return "<unknown>"


class CompileLedger:
    """Process-wide compile episode recorder (see module docstring)."""

    def __init__(self) -> None:
        self._lock = OrderedLock("compile_ledger")
        self._phase = PHASE_STARTUP
        self._warmup_depth = 0
        self._armed = False  # a warmup has completed; next phase steady
        # fn -> {"episodes": int, "by_phase": {phase: int},
        #        "trace_ms"/"lower_ms"/"compile_ms": float}
        self._fns: Dict[str, Dict[str, Any]] = {}
        self._violations: List[Dict[str, Any]] = []

    # --- phase machine --------------------------------------------------
    @property
    def phase(self) -> str:
        with self._lock:
            return self._phase

    def begin_warmup(self) -> None:
        with self._lock:
            self._warmup_depth += 1
            self._phase = PHASE_WARMUP

    def end_warmup(self) -> None:
        with self._lock:
            self._warmup_depth = max(0, self._warmup_depth - 1)
            if self._warmup_depth == 0:
                self._armed = True
                self._phase = PHASE_STEADY

    @contextlib.contextmanager
    def warming(self):
        """``begin_warmup`` ... ``end_warmup`` around a block, the end
        guaranteed (nests, like the pair it wraps)."""
        self.begin_warmup()
        try:
            yield
        finally:
            self.end_warmup()

    def steady_state(self) -> None:
        """Force-arm the steady-state mark (gates/tests; engine warmup
        arms it through ``end_warmup``)."""
        with self._lock:
            self._warmup_depth = 0
            self._armed = True
            self._phase = PHASE_STEADY

    # --- recording ------------------------------------------------------
    def _on_event(self, event: str, duration_ms: float) -> None:
        stack = _frames()
        if stack:
            fr = stack[-1]
            fr.fired = True
            if event == _EV_TRACE:
                fr.trace_ms += duration_ms
            elif event == _EV_LOWER:
                fr.lower_ms += duration_ms
            else:
                fr.compile_ms += duration_ms
            return
        # No wrapped call on this thread's stack: un-coalesced. Count
        # one episode per backend burst; fold trace/lower time into the
        # same bucket so the ms totals stay honest.
        if event == _EV_BACKEND:
            self._record(
                UNATTRIBUTED, shapes="", callsite=_callsite(),
                trace_ms=0.0, lower_ms=0.0, compile_ms=duration_ms,
            )
        else:
            with self._lock:
                rec = self._fn_rec(UNATTRIBUTED)
                key = "trace_ms" if event == _EV_TRACE else "lower_ms"
                rec[key] += duration_ms

    def _fn_rec(self, name: str) -> Dict[str, Any]:
        assert_owner(self._lock)
        rec = self._fns.get(name)
        if rec is None:
            rec = self._fns[name] = {
                "episodes": 0, "by_phase": {},
                "trace_ms": 0.0, "lower_ms": 0.0, "compile_ms": 0.0,
            }
        return rec

    def _record(self, name: str, shapes: str, callsite: str,
                trace_ms: float, lower_ms: float,
                compile_ms: float) -> None:
        end = time.monotonic() * 1000.0
        with self._lock:
            phase = self._phase
            rec = self._fn_rec(name)
            rec["episodes"] += 1
            rec["by_phase"][phase] = rec["by_phase"].get(phase, 0) + 1
            rec["trace_ms"] += trace_ms
            rec["lower_ms"] += lower_ms
            rec["compile_ms"] += compile_ms
            if phase == PHASE_STEADY:
                self._violations.append({
                    "fn": name, "phase": phase, "shapes": shapes,
                    "callsite": callsite,
                    "trace_ms": round(trace_ms, 3),
                    "lower_ms": round(lower_ms, 3),
                    "compile_ms": round(compile_ms, 3),
                })
        # Outside the ledger lock on purpose: the metric and tracer have
        # their own (metrics-rank / plain) locks and neither needs ours.
        COMPILES.inc(tags={"fn": name, "phase": phase})
        total = trace_ms + lower_ms + compile_ms
        tracer().record_span(
            "jit.compile",
            start_ms=end - total, end_ms=end,
            fn=name, phase=phase, shapes=shapes, callsite=callsite,
            trace_ms=round(trace_ms, 3), lower_ms=round(lower_ms, 3),
            compile_ms=round(compile_ms, 3),
        )
        if phase == PHASE_STEADY:
            logger.warning(
                "steady-state compile: fn=%s shapes=%s at %s "
                "(%.1f ms trace, %.1f ms lower, %.1f ms backend)",
                name, shapes, callsite, trace_ms, lower_ms, compile_ms,
            )

    # --- instrumentation ------------------------------------------------
    def instrument(self, name: str,
                   fn: Callable[..., Any]) -> Callable[..., Any]:
        """Wrap a compiled callable so its compiles are charged to
        ``name``. Cached dispatches cost one list push/pop."""
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            frame = _Frame(name)
            stack = _frames()
            stack.append(frame)
            try:
                return fn(*args, **kwargs)
            finally:
                stack.pop()
                if frame.fired:
                    self._record(
                        name,
                        shapes=_shape_sig(args),
                        callsite=_callsite(),
                        trace_ms=frame.trace_ms,
                        lower_ms=frame.lower_ms,
                        compile_ms=frame.compile_ms,
                    )
        wrapper.__name__ = f"ledger[{name}]"
        wrapper.__wrapped__ = fn
        return wrapper

    # --- inspection -----------------------------------------------------
    def counts(self, phase: Optional[str] = None) -> Dict[str, int]:
        with self._lock:
            if phase is None:
                return {n: r["episodes"] for n, r in self._fns.items()}
            return {
                n: r["by_phase"].get(phase, 0)
                for n, r in self._fns.items()
                if r["by_phase"].get(phase, 0)
            }

    def violations(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._violations)

    def check_steady(self) -> None:
        """Raise :class:`SteadyStateViolation` if any compile landed
        after the steady-state mark — the gate's hard failure."""
        v = self.violations()
        if v:
            lines = [
                f"  {x['fn']} {x['shapes']} at {x['callsite']}"
                for x in v
            ]
            raise SteadyStateViolation(
                f"{len(v)} compile(s) after the steady-state mark:\n"
                + "\n".join(lines)
            )

    def report(self) -> Dict[str, Any]:
        """Deterministically ordered snapshot (ms rounded to whole
        milliseconds so serializing the same state is byte-stable)."""
        with self._lock:
            fns = {
                name: {
                    "episodes": rec["episodes"],
                    "by_phase": dict(sorted(rec["by_phase"].items())),
                    "trace_ms": int(round(rec["trace_ms"])),
                    "lower_ms": int(round(rec["lower_ms"])),
                    "compile_ms": int(round(rec["compile_ms"])),
                }
                for name, rec in sorted(self._fns.items())
            }
            violations = list(self._violations)
            phase = self._phase
        totals = {p: 0 for p in (PHASE_STARTUP, PHASE_WARMUP,
                                 PHASE_STEADY)}
        for rec in fns.values():
            for p, n in rec["by_phase"].items():
                totals[p] = totals.get(p, 0) + n
        return {
            "phase": phase,
            "functions": fns,
            "total_compiles": sum(r["episodes"] for r in fns.values()),
            "by_phase": totals,
            "violations": violations,
        }

    def to_json(self) -> str:
        return json.dumps(self.report(), indent=2, sort_keys=True) + "\n"

    def reset(self) -> None:
        """Clear all state in place (the module-level jax.monitoring
        listener cannot be unregistered individually; the singleton it
        dispatches to resets instead)."""
        with self._lock:
            self._phase = PHASE_STARTUP
            self._warmup_depth = 0
            self._armed = False
            self._fns = {}
            self._violations = []


_ledger = CompileLedger()
_listener_lock = threading.Lock()
_listener_installed = False


def get_ledger() -> CompileLedger:
    """The process ledger, with the jax.monitoring listener installed on
    first use (import stays jax-free for stdlib-only consumers)."""
    global _listener_installed
    if not _listener_installed:
        with _listener_lock:
            if not _listener_installed:
                from jax import monitoring

                monitoring.register_event_duration_secs_listener(
                    _dispatch_event
                )
                _listener_installed = True
    return _ledger


def _dispatch_event(event: str, duration_secs: float, **_kw: Any) -> None:
    if event in (_EV_TRACE, _EV_LOWER, _EV_BACKEND):
        _ledger._on_event(event, duration_secs * 1000.0)


def instrument(name: str, fn: Callable[..., Any]) -> Callable[..., Any]:
    """Module-level convenience: wrap ``fn`` against the process
    ledger (see :meth:`CompileLedger.instrument`)."""
    return get_ledger().instrument(name, fn)
