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
  label bounded — an unbounded cardinality bug cannot mint series).
- an episode's time is split by what JAX reports: ``trace_ms``,
  ``lower_ms`` and ``compile_ms`` (the backend: XLA and Mosaic on a
  persistent-cache miss, the cache READ on a hit), each an event's SELF
  time (a jit traced inside a trace reports inside its caller's
  interval), so the three never exceed the wall time they were taken
  over; ``cache_read_ms`` (a part OF ``compile_ms``), ``saved_ms`` and
  the cache's hits and misses say which kind of start it was.
- an episode is charged to ``(name, key)``: the key is the open
  ``rdb.startup.warmup.program`` span's (``b=256,g=2`` / ``h=8``; ""
  elsewhere), and that span — any open start-up span of
  ``utils/tracing.py`` — is given the episode's parts as attributes.

Compiles with no wrapped call on the stack are charged to the thread's
innermost open start-up span's name (``rdb.startup.engine_build``,
``rdb.startup.warmup.program``, ...: a closed set) and only with none
open to ``__unattributed__``; for those the episode unit degrades to
one-per-``backend_compile``-burst (there is no call boundary to
coalesce on — documented, not hidden).

``tools/check_compiles.py`` is the CI gate over this ledger: warmup
plus a canonical serving segment must stay inside the ratcheted budget
(``tools/compile_budget.json``) with ZERO steady-phase episodes.
"""

from __future__ import annotations

import collections
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

# Event names jax.monitoring emits per compilation stage. Any of them
# firing means real (re)compilation work — a cached dispatch emits none.
# The first five are duration events, the last two plain ones; the cache's
# all fire INSIDE the backend event's interval.
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"
_EV_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_EV_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_MISS = "/jax/compilation_cache/cache_misses"

# Event -> the part of an episode it adds to (milliseconds, or a count).
_PART = {
    _EV_TRACE: "trace_ms", _EV_LOWER: "lower_ms", _EV_BACKEND: "compile_ms",
    _EV_CACHE_READ: "cache_read_ms", _EV_SAVED: "saved_ms",
    _EV_HIT: "cache_hits", _EV_MISS: "cache_misses",
}
PARTS = tuple(_PART.values())
# The three stages whose intervals nest (a jit called while another is
# traced; an eager op run while one is lowered): each is booked its self
# time.
_STAGES = (_EV_TRACE, _EV_LOWER, _EV_BACKEND)

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
    __slots__ = ("name", "parts")

    def __init__(self, name: str) -> None:
        self.name = name
        # What fired under the call, by part; None until something does
        # (a cached dispatch leaves it so).
        self.parts: Optional[Dict[str, float]] = None


def _frames() -> List[_Frame]:
    stack = getattr(_tls, "frames", None)
    if stack is None:
        stack = _tls.frames = []
    return stack


def _self_ms(duration_ms: float) -> float:
    """A stage event's SELF time: its duration less the stage events of
    this thread that fired inside its interval. An event fires at its
    interval's end, on the thread that ran it, so an earlier event lies
    inside this one exactly when it ENDED after this one started (two
    intervals of one thread nest or follow each other). The thread keeps
    the (end, duration) no enclosing interval has claimed yet: a first
    trace holds some hundreds of inner ones; what is never claimed ages
    out."""
    seen = getattr(_tls, "stages", None)
    if seen is None:
        seen = _tls.stages = collections.deque(maxlen=4096)
    end = time.monotonic() * 1000.0
    start = end - duration_ms
    inner = 0.0
    while seen and seen[-1][0] > start:
        inner += seen.pop()[1]
    seen.append((end, duration_ms))
    return max(duration_ms - inner, 0.0)


def _new_parts() -> Dict[str, float]:
    return dict.fromkeys(PARTS, 0.0)


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
        # fn -> {"episodes": int, "by_phase": {phase: int}, every one
        #        of PARTS: float, "by_key": {key: the same less by_phase}}
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
    def _on_event(self, event: str, value: float) -> None:
        """One monitoring event on the thread that compiled: ``value`` is
        milliseconds (a duration event) or 1 (a plain one)."""
        part = _PART[event]
        if event in _STAGES:
            value = _self_ms(value)
        stack = _frames()
        if stack:
            fr = stack[-1]
            if fr.parts is None:
                fr.parts = _new_parts()
            fr.parts[part] += value
            return
        # No wrapped call on this thread's stack: un-coalesced. The open
        # start-up span owns it, else nobody. Count one episode per
        # backend burst; book every other part as it comes so the totals
        # stay honest.
        span = tracer().open_startup()
        name = span.name if span is not None else UNATTRIBUTED
        if event == _EV_BACKEND:
            self._record(name, span, {part: value}, args=())
        else:
            self._book(name, span, {part: value}, episodes=0)

    def _fn_rec(self, name: str) -> Dict[str, Any]:
        assert_owner(self._lock)
        rec = self._fns.get(name)
        if rec is None:
            rec = self._fns[name] = dict(
                _new_parts(), episodes=0, by_phase={}, by_key={})
        return rec

    def _book(self, name: str, span: Any, parts: Dict[str, float],
              episodes: int) -> str:
        """Add ``parts`` to ``name``'s totals, to its row for the open
        start-up span's ``key`` and to that span's attributes; returns
        the phase it was booked in."""
        key = "" if span is None else str(span.attributes.get("key", ""))
        with self._lock:
            phase = self._phase
            rec = self._fn_rec(name)
            row = rec["by_key"].get(key)
            if row is None:
                row = rec["by_key"][key] = dict(_new_parts(), episodes=0)
            for tot in (rec, row):
                tot["episodes"] += episodes
                for k, v in parts.items():
                    tot[k] += v
            if episodes:
                rec["by_phase"][phase] = (
                    rec["by_phase"].get(phase, 0) + episodes)
        if span is not None:
            # The span is this thread's own until it closes. Its name for
            # the backend's time is ``backend_ms``.
            a = span.attributes
            for k, v in parts.items():
                k = "backend_ms" if k == "compile_ms" else k
                a[k] = a.get(k, 0.0) + v
            a["cache"] = ("miss" if a.get("cache_misses") else
                          "hit" if a.get("cache_hits") else "off")
        return phase

    def _record(self, name: str, span: Any, parts: Dict[str, float],
                args: Tuple[Any, ...]) -> None:
        """One episode of ``name``. Who called and with what shapes is
        worked out only for a steady-phase violation: nothing else
        reads it."""
        phase = self._book(name, span, parts, episodes=1)
        # Outside the ledger lock on purpose: the metric has its own
        # (metrics-rank) lock and does not need ours.
        COMPILES.inc(tags={"fn": name, "phase": phase})
        if phase != PHASE_STEADY:
            return
        violation = {
            "fn": name, "phase": phase,
            "shapes": _shape_sig(args) if args else "",
            "callsite": _callsite(),
            **{k: round(parts.get(k, 0.0), 3)
               for k in ("trace_ms", "lower_ms", "compile_ms")},
        }
        with self._lock:
            self._violations.append(violation)
        logger.warning(
            "steady-state compile: fn=%s shapes=%s at %s "
            "(%.1f ms trace, %.1f ms lower, %.1f ms backend)",
            name, violation["shapes"], violation["callsite"],
            violation["trace_ms"], violation["lower_ms"],
            violation["compile_ms"],
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
                if frame.parts is not None:
                    self._record(name, tracer().open_startup(),
                                 frame.parts, args)
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
        def totals(rec: Dict[str, Any]) -> Dict[str, int]:
            return dict({k: int(round(rec[k])) for k in PARTS},
                        episodes=rec["episodes"])

        with self._lock:
            fns = {
                name: dict(
                    totals(rec),
                    by_phase=dict(sorted(rec["by_phase"].items())),
                    by_key={key: totals(row) for key, row
                            in sorted(rec["by_key"].items())},
                )
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
                monitoring.register_event_listener(_dispatch_count)
                _listener_installed = True
    return _ledger


def _dispatch_event(event: str, duration_secs: float, **_kw: Any) -> None:
    if event in _PART:
        _ledger._on_event(event, duration_secs * 1000.0)


def _dispatch_count(event: str, **_kw: Any) -> None:
    if event in _PART:
        _ledger._on_event(event, 1)


def instrument(name: str, fn: Callable[..., Any]) -> Callable[..., Any]:
    """Module-level convenience: wrap ``fn`` against the process
    ledger (see :meth:`CompileLedger.instrument`)."""
    return get_ledger().instrument(name, fn)
