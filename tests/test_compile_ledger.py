"""Compile flight recorder (ISSUE 20): episode counting over
jax.monitoring events, phase attribution, the steady-state mark, and
byte-stable serialization.

The counting unit under test is the EPISODE — one wrapped call in which
any compile event fired — because jax emits several backend_compile
bursts per trace (three on a first call, two on a retrace, measured);
raw events would overcount every compile.
"""

import json
import threading

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cache

from ray_dynamic_batching_tpu.utils.compile_ledger import (
    PHASE_STARTUP,
    PHASE_STEADY,
    PHASE_WARMUP,
    SteadyStateViolation,
    get_ledger,
)
from ray_dynamic_batching_tpu.utils.tracing import tracer


@pytest.fixture()
def ledger():
    led = get_ledger()
    led.reset()
    yield led
    # Leave the process ledger disarmed so later tests' compiles are
    # plain startup episodes, never false violations.
    led.reset()


def _toy(scale):
    # A fresh jit per test: its cache is empty, so first-call compiles
    # are deterministic regardless of what ran before in the process.
    return jax.jit(lambda x: x * scale)


class TestEpisodeCounting:
    def test_first_call_is_exactly_one_episode(self, ledger):
        fn = ledger.instrument("toy", _toy(2))
        fn(jnp.ones((4,)))
        assert ledger.counts()["toy"] == 1

    def test_cached_dispatch_records_nothing(self, ledger):
        fn = ledger.instrument("toy", _toy(3))
        fn(jnp.ones((4,)))
        before = ledger.counts()["toy"]
        fn(jnp.ones((4,)))
        fn(jnp.ones((4,)))
        assert ledger.counts()["toy"] == before

    def test_forced_retrace_counts_exactly_once_per_shape(self, ledger):
        fn = ledger.instrument("toy", _toy(5))
        fn(jnp.ones((4,)))          # startup compile
        ledger.begin_warmup()
        fn(jnp.ones((8,)))          # new shape: ONE warmup episode
        fn(jnp.ones((8,)))          # cached
        ledger.end_warmup()
        assert ledger.counts()["toy"] == 2
        assert ledger.counts(phase=PHASE_STARTUP)["toy"] == 1
        assert ledger.counts(phase=PHASE_WARMUP)["toy"] == 1
        assert ledger.counts(phase=PHASE_STEADY) == {}

    def test_result_passes_through_wrapper(self, ledger):
        fn = ledger.instrument("toy", _toy(7))
        out = fn(jnp.ones((2,)))
        assert out.tolist() == [7.0, 7.0]


class TestSteadyStateMark:
    def test_violation_recorded_and_gate_raises(self, ledger):
        fn = ledger.instrument("toy", _toy(11))
        ledger.begin_warmup()
        fn(jnp.ones((4,)))
        # Built during warmup: jnp.ones itself compiles on first use of
        # a shape, and a steady-phase constant build would be a real
        # (unattributed) violation of its own.
        x16 = jnp.ones((16,))
        ledger.end_warmup()
        ledger.check_steady()  # clean so far
        fn(x16)                # post-warmup retrace: the violation
        v = ledger.violations()
        assert len(v) == 1
        assert v[0]["fn"] == "toy"
        assert "16" in v[0]["shapes"]
        assert "test_compile_ledger" in v[0]["callsite"]
        with pytest.raises(SteadyStateViolation) as exc:
            ledger.check_steady()
        assert "toy" in str(exc.value)

    def test_nested_warmups_arm_only_at_depth_zero(self, ledger):
        fn = ledger.instrument("toy", _toy(13))
        ledger.begin_warmup()
        ledger.begin_warmup()
        ledger.end_warmup()
        # Still inside the outer warmup: compiles are warmup, not steady.
        fn(jnp.ones((4,)))
        ledger.end_warmup()
        assert ledger.counts(phase=PHASE_WARMUP)["toy"] == 1
        assert ledger.violations() == []
        assert ledger.phase == PHASE_STEADY

    def test_force_arm_via_steady_state(self, ledger):
        fn = ledger.instrument("toy", _toy(17))
        ledger.steady_state()
        fn(jnp.ones((4,)))
        with pytest.raises(SteadyStateViolation):
            ledger.check_steady()


class TestReport:
    def test_report_is_byte_stable(self, ledger):
        fn = ledger.instrument("toy", _toy(19))
        ledger.begin_warmup()
        fn(jnp.ones((4,)))
        ledger.end_warmup()
        first = ledger.to_json()
        second = ledger.to_json()
        assert first == second
        payload = json.loads(first)
        assert payload["functions"]["toy"]["episodes"] == 1
        assert payload["by_phase"][PHASE_WARMUP] >= 1
        assert payload["violations"] == []
        assert first.endswith("\n")

    def test_reset_clears_everything(self, ledger):
        fn = ledger.instrument("toy", _toy(23))
        ledger.steady_state()
        fn(jnp.ones((4,)))
        ledger.reset()
        assert ledger.counts() == {}
        assert ledger.violations() == []
        assert ledger.phase == PHASE_STARTUP

    def test_wrapper_is_thread_attributed(self, ledger):
        # Frames are thread-local: a compile on a worker thread charges
        # the program the WORKER wrapped, not whatever the main thread
        # happens to be running.
        fn = ledger.instrument("worker_toy", _toy(29))
        done = threading.Event()

        def work():
            fn(jnp.ones((6,)))
            done.set()

        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=60)
        assert done.is_set()
        assert ledger.counts()["worker_toy"] == 1


# --- ISSUE 38: a cache read is not a compile, one program is not another ----
@pytest.fixture()
def fresh_cache(tmp_path):
    """The persistent cache pointed at an empty directory, with
    ``utils/compile_cache.py``'s thresholds (every program is written);
    the suite's own directory is put back after."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), -1, 0)):
        jax.config.update(k, v)
    jax_cache.reset_cache()
    yield tmp_path
    for k, v in before.items():
        jax.config.update(k, v)
    jax_cache.reset_cache()


def _program_span(key="", **attrs):
    return tracer().startup("rdb.startup.warmup.program", key=key, **attrs)


class TestCacheReadOrCompile:
    def test_a_fresh_cache_misses_then_hits_and_only_the_hit_reads(
            self, ledger, fresh_cache):
        x = jnp.ones((3, 5))
        spans = []
        for _ in range(2):
            # A fresh function object of the same code: a second jit of
            # ONE object is served from memory and reaches no cache.
            fn = ledger.instrument("toy", jax.jit(lambda a: a * 31 + 7))
            with _program_span() as sp:
                fn(x).block_until_ready()
            spans.append(sp.attributes)
        first, second = spans
        assert (first["cache"], second["cache"]) == ("miss", "hit")
        assert first["cache_read_ms"] == 0 and second["cache_read_ms"] > 0
        # the read is a part OF the backend's time, not beside it
        assert second["cache_read_ms"] <= second["backend_ms"]
        rec = ledger.report()["functions"]["toy"]
        assert (rec["cache_misses"], rec["cache_hits"]) == (1, 1)
        assert rec["episodes"] == 2

    def test_with_no_cache_the_span_says_off(self, ledger):
        # JAX asks once a process whether the cache is used and keeps the
        # answer until ``reset_cache``: forget it on both sides, or this
        # test reads the last answer and leaves its own to the tests after.
        jax.config.update("jax_enable_compilation_cache", False)
        jax_cache.reset_cache()
        try:
            fn = ledger.instrument("toy", _toy(37))
            with _program_span() as sp:
                fn(jnp.ones((4,)))
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            jax_cache.reset_cache()
        assert sp.attributes["cache"] == "off"
        assert sp.attributes["backend_ms"] > 0

    def test_nested_traces_are_booked_their_self_time(self, ledger):
        inner = jax.jit(lambda a: a + 1)

        def outer(a):
            for _ in range(200):        # 200 inner calls, traced once
                a = inner(a)
            return a

        fn = ledger.instrument("toy", jax.jit(outer))
        with _program_span() as sp:
            fn(jnp.ones((4,))).block_until_ready()
        a = sp.attributes
        spent = a["trace_ms"] + a["lower_ms"] + a["backend_ms"]
        assert 0 < spent <= sp.duration_ms()


class TestKeysAndOwners:
    def test_two_keys_of_one_name_sum_to_the_names_totals(self, ledger):
        fn = ledger.instrument("toy", _toy(41))
        for key, n in (("b=4", 4), ("b=8", 8)):
            with _program_span(key=key):
                fn(jnp.ones((n,)))
        rec = ledger.report()["functions"]["toy"]
        assert set(rec["by_key"]) == {"b=4", "b=8"}
        assert all(r["episodes"] == 1 for r in rec["by_key"].values())
        assert rec["episodes"] == 2
        raw = ledger._fns["toy"]
        for part in ("trace_ms", "lower_ms", "compile_ms"):
            assert raw[part] == pytest.approx(
                sum(r[part] for r in raw["by_key"].values()))
            assert raw[part] > 0

    def test_outside_a_program_span_the_key_is_empty(self, ledger):
        ledger.instrument("toy", _toy(43))(jnp.ones((4,)))
        assert set(ledger.report()["functions"]["toy"]["by_key"]) == {""}

    def test_a_compile_under_a_startup_span_is_charged_to_its_name(
            self, ledger):
        with tracer().startup("rdb.startup.engine_build") as sp:
            jax.jit(lambda a: a * 47)(jnp.ones((4,)))   # not instrumented
        counts = ledger.counts()
        assert counts.get("rdb.startup.engine_build", 0) >= 1
        assert "__unattributed__" not in counts
        assert sp.attributes["backend_ms"] > 0
        # ... and with none open, to nobody
        jax.jit(lambda a: a * 53)(jnp.ones((4,)))
        assert ledger.counts()["__unattributed__"] >= 1

    def test_the_innermost_open_span_owns_it(self, ledger):
        with tracer().startup("rdb.startup.warmup"):
            with _program_span(key="h=1"):
                jax.jit(lambda a: a * 59)(jnp.ones((4,)))
        fns = ledger.report()["functions"]
        assert "rdb.startup.warmup" not in fns
        assert set(fns["rdb.startup.warmup.program"]["by_key"]) == {"h=1"}

    def test_a_cached_dispatch_appends_nothing_anywhere(self, ledger):
        fn = ledger.instrument("toy", _toy(61))
        x = jnp.ones((4,))
        fn(x)
        before = (ledger.to_json(), len(tracer().startup_spans()),
                  len(tracer().finished_spans()))
        for _ in range(5):
            fn(x)
        assert before == (ledger.to_json(), len(tracer().startup_spans()),
                          len(tracer().finished_spans()))

    def test_a_violation_keeps_its_fields_and_gains_none(self, ledger):
        fn = ledger.instrument("toy", _toy(67))
        x = jnp.ones((4,))
        ledger.steady_state()
        fn(x)
        (v,) = ledger.violations()
        assert set(v) == {"fn", "phase", "shapes", "callsite", "trace_ms",
                          "lower_ms", "compile_ms"}
