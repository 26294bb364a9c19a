"""A replica's start traced from inside (ISSUE 38; tier-1): the
``rdb.startup.*`` spans of ``Tracer.startup``, what the compile ledger
charges them, and ``DecodeEngine.snapshot()["startup"]``. Counts, nesting
and sums only — never a time."""

import jax.numpy as jnp
import pytest

from ray_dynamic_batching_tpu.engine.decode import (
    STARTUP_PROGRAM,
    startup_rows,
    startup_sums,
)
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.parallel.placement import PlacementManager
from ray_dynamic_batching_tpu.serve.controller import (
    DeploymentConfig,
    ServeController,
)
from ray_dynamic_batching_tpu.serve.llm import LLMDeployment
from ray_dynamic_batching_tpu.utils.tracing import (
    _STARTUP_SPAN_CAP,
    Span,
    tracer,
)

BUCKETS, GROUPS, HORIZONS = (8, 16), (1, 2), (1, 2, 8)
PROGRAMS = len(BUCKETS) * len(GROUPS) + len(HORIZONS)
SECONDS = ("trace_lower_s", "backend_s", "first_run_s", "engine_build_s",
           "unaccounted_s")


@pytest.fixture(scope="module")
def deployed():
    """Two one-chip replicas of a tiny model through the controller, and
    the start-up log as their start left it."""
    import jax

    tracer().reset()
    controller = ServeController(
        placement=PlacementManager(jax.devices()[:2]))
    controller.deploy(
        DeploymentConfig(name="llm", num_replicas=2, chips_per_replica=1),
        factory=LLMDeployment(
            "llama_tiny", num_slots=2, max_len=32,
            prompt_buckets=list(BUCKETS), default_max_new_tokens=4,
            dtype=jnp.float32))
    try:
        engines = [r.engine for r in controller._deployments["llm"].replicas]
        yield engines, tracer().startup_spans()
    finally:
        controller.shutdown()


def _by_name(spans, name):
    return [sp for sp in spans if sp.name == name]


def test_one_program_span_a_bucket_and_group_and_a_horizon(deployed):
    engines, spans = deployed
    for engine in engines:
        mine = [sp.attributes for sp in _by_name(spans, STARTUP_PROGRAM)
                if sp.attributes["replica"] == engine._phase_tag]
        keys = {(a["program"], a["key"]) for a in mine}
        assert len(mine) == len(keys) == PROGRAMS
        assert keys == (
            {("chunk_prefill", f"b={b},g={g}")
             for b in BUCKETS for g in GROUPS}
            | {("decode_step", f"h={h}") for h in HORIZONS})


def test_programs_nest_in_warmup_in_replica_in_deploy(deployed):
    engines, spans = deployed
    by_id = {sp.span_id: sp for sp in spans}
    (deploy,) = _by_name(spans, "rdb.startup.deploy")
    assert deploy.parent_id is None
    assert deploy.attributes == {"deployment": "llm", "replicas": 2}
    for sp in _by_name(spans, STARTUP_PROGRAM):
        chain = []
        while sp.parent_id is not None:
            assert by_id[sp.parent_id].start_ms <= sp.start_ms
            assert sp.end_ms <= by_id[sp.parent_id].end_ms
            sp = by_id[sp.parent_id]
            chain.append(sp.name)
        assert chain == ["rdb.startup.warmup", "rdb.startup.replica",
                         "rdb.startup.deploy"]
    for name in ("rdb.startup.engine_build", "rdb.startup.warmup"):
        assert [by_id[sp.parent_id].name for sp in _by_name(spans, name)] \
            == ["rdb.startup.replica"] * 2
    (register,) = _by_name(spans, "rdb.startup.register")
    assert register.parent_id == deploy.span_id


def test_each_programs_parts_are_its_duration(deployed):
    _, spans = deployed
    for sp in _by_name(spans, STARTUP_PROGRAM):
        a = sp.attributes
        assert a["trace_ms"] > 0 and a["lower_ms"] > 0 and a["backend_ms"] > 0
        assert a["cache"] in ("hit", "miss")
        assert 0 <= a["cache_read_ms"] <= a["backend_ms"]
        assert a["run_ms"] > 0
        assert (a["trace_ms"] + a["lower_ms"] + a["backend_ms"]
                + a["run_ms"]) == pytest.approx(sp.duration_ms(), abs=1e-6)


def test_two_engines_give_two_replica_subtrees_each_with_its_episodes(
        deployed):
    engines, spans = deployed
    replicas = _by_name(spans, "rdb.startup.replica")
    assert [sp.attributes["replica"] for sp in replicas] == ["llm#0", "llm#1"]
    assert len({sp.attributes["chips"] for sp in replicas}) == 2
    tags = set()
    for rep, engine in zip(replicas, engines):
        under = [sp for sp in spans if sp.parent_id == rep.span_id]
        assert {sp.attributes["replica"] for sp in under} \
            == {engine._phase_tag}
        tags.add(engine._phase_tag)
        (build,) = [sp for sp in under
                    if sp.name == "rdb.startup.engine_build"]
        assert build.attributes["slots"] == 2
        assert build.attributes["pages"] == engine.num_pages
        assert build.attributes["pool_bytes"] > 0
        (warmup,) = [sp for sp in under if sp.name == "rdb.startup.warmup"]
        assert warmup.attributes["programs"] == PROGRAMS
    assert len(tags) == 2
    # The second engine's jit of its own bound methods traces again.
    second = [sp for sp in _by_name(spans, STARTUP_PROGRAM)
              if sp.attributes["replica"] == engines[1]._phase_tag]
    assert all(sp.attributes["trace_ms"] > 0 for sp in second)


def test_snapshot_sums_to_the_deploy_span(deployed):
    engines, spans = deployed
    (deploy,) = _by_name(spans, "rdb.startup.deploy")
    for engine in engines:
        start = engine.snapshot()["startup"]
        names = [r["name"] for r in start["rows"]]
        assert names[:2] == ["rdb.startup.deploy", "rdb.startup.replica"]
        assert names.count(STARTUP_PROGRAM) == PROGRAMS
        assert names.count("rdb.startup.replica") == 1  # its own only
        assert "rdb.startup.register" in names
        assert start["deploy_s"] == pytest.approx(
            deploy.duration_ms() / 1000.0)
        assert sum(start[k] for k in SECONDS) == pytest.approx(
            start["deploy_s"], abs=1e-9)
        assert all(start[k] > 0 for k in SECONDS)
        assert start["cache_hits"] + start["cache_misses"] >= PROGRAMS
    # Over both engines' rows (what the benchmark's reader sums) the
    # other replica's time leaves ``unaccounted_s``.
    rows = {r["id"]: r for e in engines
            for r in e.snapshot()["startup"]["rows"]}
    both = startup_sums(list(rows.values()))
    assert sum(both[k] for k in SECONDS) == pytest.approx(both["deploy_s"])
    assert both["unaccounted_s"] < min(
        e.snapshot()["startup"]["unaccounted_s"] for e in engines)


def test_rows_give_each_span_the_time_no_child_covers():
    def sp(i, parent, start, end, name="rdb.startup.x", **attrs):
        return Span(name=name, trace_id="t", span_id=i, parent_id=parent,
                    start_ms=start, end_ms=end, attributes=attrs)

    rows = startup_rows([
        sp(2, 1, 1010.0, 1030.0, name=STARTUP_PROGRAM, trace_ms=5.0,
           lower_ms=4.0, backend_ms=3.0, run_ms=8.0),
        sp(1, None, 1000.0, 1100.0, name="rdb.startup.deploy"),
        sp(3, 1, 1040.0, 1050.0, name="rdb.startup.engine_build"),
    ])
    assert [(r["id"], r["start_ms"], r["dur_ms"], r["self_ms"])
            for r in rows] == [(1, 0.0, 100.0, 70.0), (2, 10.0, 20.0, 20.0),
                               (3, 40.0, 10.0, 10.0)]
    assert startup_sums(rows) == {
        "trace_lower_s": 0.009, "backend_s": 0.003, "first_run_s": 0.008,
        "engine_build_s": 0.01, "cache_hits": 0, "cache_misses": 0,
        "deploy_s": 0.1, "unaccounted_s": pytest.approx(0.07)}
    # no deploy span among them: the four parts, and no remainder
    alone = startup_sums(rows[1:])
    assert alone["deploy_s"] is None and alone["unaccounted_s"] is None
    assert alone["engine_build_s"] == 0.01


def test_the_startup_log_is_bounded_and_serving_spans_do_not_evict_it():
    t = tracer()
    t.reset()
    try:
        with t.startup("rdb.startup.deploy", deployment="d") as keep:
            pass
        t.set_exporter(lambda span: None)
        for i in range(10_001):
            t.record_span("decode.turn", start_ms=0.0, end_ms=1.0)
        assert len(t.finished_spans()) == 10_000
        assert [sp.span_id for sp in t.startup_spans()] == [keep.span_id]
        for i in range(_STARTUP_SPAN_CAP + 5):
            with t.startup("rdb.startup.replica"):
                pass
        assert len(t.startup_spans()) == _STARTUP_SPAN_CAP
        assert keep.span_id not in {sp.span_id for sp in t.startup_spans()}
    finally:
        t.reset()


def test_startup_records_without_an_exporter_and_exports_with_one():
    t = tracer()
    t.reset()
    try:
        assert not t.enabled
        with t.startup("rdb.startup.deploy", deployment="d") as outer:
            assert t.open_startup() is outer
            with t.startup("rdb.startup.replica") as inner:
                assert t.open_startup() is inner
                inner.attributes["replica"] = "d#0"
        assert t.open_startup() is None
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == outer.trace_id
        assert [sp.name for sp in t.startup_spans()] == [
            "rdb.startup.replica", "rdb.startup.deploy"]
        assert t.finished_spans() == []   # not in the serving spans' ring
        got = []
        t.set_exporter(got.append)
        with t.startup("rdb.startup.register"):
            pass
        assert [sp.name for sp in got] == ["rdb.startup.register"]
    finally:
        t.reset()
