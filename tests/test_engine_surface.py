"""One engine arm: what ``DecodeEngine`` and ``LLMDeployment`` take, refuse
and give a caller who sets nothing.

The paged pool with chunked admission is the engine, not an option of it:
``paged`` is a key with one legal value (the benchmark's configurations
still pass ``"paged": true``), ``chunked_prefill`` is no keyword at all,
the registry of hot programs lists what can run, and the planner prices a
slot by what the pool allocates for it.
"""

import inspect
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
from ray_dynamic_batching_tpu.ops import jit_model
from ray_dynamic_batching_tpu.serve.llm import LLMDeployment
from ray_dynamic_batching_tpu.serve.schema import (
    ServeConfigSchema,
    apply_config,
)

CONFIGS = sorted(
    (Path(__file__).resolve().parent.parent / "benchmark" / "configs")
    .glob("*.json"))


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


def _build(surface, lm, **option):
    """Hand ``option`` to one of the three surfaces that take the
    engine's options; none of them builds a device program on refusal."""
    model, params = lm
    if surface == "engine":
        return DecodeEngine(model, params,
                            RequestQueue(model.name, max_len=16), **option)
    if surface == "deployment":
        return LLMDeployment("llama_tiny", **option)
    return apply_config(ServeConfigSchema.from_dict({"applications": [{
        "name": "refused", "deployments": [{
            "name": "refused", "llm": dict(model="llama_tiny", **option),
        }]}]}))


SURFACES = ["engine", "deployment", "apply_config"]


@pytest.mark.parametrize("surface", SURFACES)
def test_paged_false_is_refused_and_says_the_slab_is_gone(surface, lm):
    with pytest.raises(ValueError, match="slab KV cache was removed"):
        _build(surface, lm, paged=False)


@pytest.mark.parametrize("surface", SURFACES)
def test_chunked_prefill_is_an_unknown_keyword(surface, lm):
    with pytest.raises(TypeError, match="chunked_prefill"):
        _build(surface, lm, chunked_prefill=True)


def _serve_one(engine, queue, model_name):
    req = Request(model=model_name, payload={
        "tokens": np.asarray([3, 1, 4, 1, 5], np.int32),
        "max_new_tokens": 4}, slo_ms=60_000.0)
    queue.add_request(req)
    engine.run_until_idle(timeout_s=120)
    return req.future.result(timeout=5).tokens


def test_an_engine_given_no_option_serves_from_a_paged_pool(lm):
    model, params = lm
    queue = RequestQueue(model.name, max_len=16)
    engine = DecodeEngine(model, params, queue)
    assert len(_serve_one(engine, queue, model.name)) == 4
    snap = engine.snapshot()
    assert snap["paged"] is True and snap["prefill"]["mode"] == "chunked"
    assert snap["num_pages"] == engine.num_slots * engine._n_table_entries
    assert {t.kind for t in engine.turns} == {"chunk", "turn"}
    assert snap["free_pages"] == snap["num_pages"]      # drained


def test_a_deployment_given_no_option_serves_from_a_paged_pool(lm):
    from ray_dynamic_batching_tpu.serve.controller import DeploymentConfig

    model, params = lm
    dep = LLMDeployment("llama_tiny", model=model, params=params,
                        warmup=False)
    replica = dep.make_replica("llama_tiny#0",
                               DeploymentConfig(name="llama_tiny"))
    engine = replica.engine
    assert len(_serve_one(engine, replica._queues[dep.max_len],
                          model.name)) == 4
    snap = engine.snapshot()
    assert snap["paged"] is True and snap["prefill"]["mode"] == "chunked"
    assert snap["page_size"] == 128 and snap["kv_pool"]["resident_bytes"] > 0


def test_the_registry_lists_the_seven_programs_that_can_run():
    assert [p.name for p in jit_model.HOT_PROGRAMS] == [
        "decode_step", "chunk_prefill", "spec_verify", "draft_catchup",
        "zero_counts", "draft_long_chunk", "draft_long_commit"]
    assert {p.arm for p in jit_model.HOT_PROGRAMS} == {
        jit_model.ARM_ALWAYS, jit_model.ARM_SPEC}


@pytest.mark.parametrize("has_draft, names", [
    (False, {"decode_step", "chunk_prefill", "zero_counts"}),
    (True, {"decode_step", "chunk_prefill", "zero_counts", "spec_verify",
            "draft_catchup"}),
])
def test_required_for_projects_on_the_draft_alone(has_draft, names):
    assert list(inspect.signature(jit_model.required_for).parameters) == [
        "has_draft"]
    assert {p.name for p in jit_model.required_for(has_draft)} == names


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_the_benchmarks_configuration_keys_are_accepted(path):
    """Each configuration's ``llm`` mapping, read from the file, binds to
    ``LLMDeployment.__init__`` as ``apply_config`` passes it and to
    ``DecodeEngine.__init__`` as ``rehearse_compile.py`` does, and the
    legacy ``paged`` key it carries is the one legal value."""
    from ray_dynamic_batching_tpu.engine.decode import require_paged

    llm = json.loads(path.read_text())["deployment"]["llm"]
    inspect.signature(LLMDeployment.__init__).bind(
        None, "bench_model", params=None, dtype=None, **llm)
    inspect.signature(DecodeEngine.__init__).bind(
        None, "model", "params", "queue",
        **{k: v for k, v in llm.items() if k != "default_max_new_tokens"})
    require_paged(llm["paged"])


@pytest.mark.parametrize("head_dim, kv_heads, padding", [
    (64, 2, 1), (64, 1, 2), (128, 2, 1)])
def test_auto_slot_sizing_prices_a_slot_by_the_pool(head_dim, kv_heads,
                                                    padding, monkeypatch):
    """The planner's figure for one slot equals what a built engine's
    pool holds on the device, per slot — two 64-wide heads lie in one
    128-lane row and cost what ``kv_bytes_per_slot`` counts; ONE 64-wide
    head has nothing to pair with, is lane-padded to 128 and costs twice
    that; max_len is rounded up to whole pages."""
    cfg = DecoderConfig(vocab_size=64, d_model=2 * head_dim, num_layers=2,
                        num_heads=2, num_kv_heads=kv_heads, mlp_dim=64,
                        max_seq_len=256)
    model = CausalLM(cfg, name=f"sizing{head_dim}x{kv_heads}",
                     dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    max_len, slots = 200, 4               # 2 pages a slot, not 200 rows
    dep = LLMDeployment(model.name, model=model, params=params,
                        max_len=max_len, num_slots=0)
    priced = dep.pool_bytes_per_slot(model, max_len)
    engine = DecodeEngine(model, params, RequestQueue(model.name),
                          num_slots=slots, max_len=max_len)
    assert engine.snapshot()["kv_pool"]["resident_bytes"] == slots * priced
    unpadded = model.kv_bytes_per_slot(256)
    assert priced == unpadded * padding
    assert engine.snapshot()["kv_pool"]["heads_per_row"] == (
        2 if (head_dim, kv_heads) == (64, 2) else 1)
    # And auto sizing divides the budget by that figure.
    from ray_dynamic_batching_tpu.utils.config import RDBConfig, set_config

    weights = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    set_config(RDBConfig.from_env(
        hbm_budget_bytes=weights + 9 * priced, hbm_plan_fraction=1.0))
    try:
        assert dep.auto_num_slots(1) == 8          # 9 fit: power of two
    finally:
        set_config(RDBConfig.from_env())
