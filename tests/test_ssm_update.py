"""The state-space mixer's decode row as ONE kernel on the state plane in
place (``ops/ssm_update.py``), interpreted on the CPU, against the row in
plain XLA (``models/ssm.py::decode_update``): the same arithmetic in float32
(``y`` and the advanced states within reassociation), a slot that does not
advance and EVERY OTHER LAYER of the plane bit for bit (negative zeros and
all); the wrapper's declines by name; which form a program traced
(``SsmPath``); the tile from shapes; and a tiny Falcon-H1-shaped model served
with the kernel on against off.
"""

import dataclasses
import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.models import ssm
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import ssm_update, tile_math
from tests.test_falcon_h1 import TINY, _engine, _load, _seeded, _submit

ROOT = Path(__file__).resolve().parents[1]
L, B, P, N, GROUP = 3, 5, 16, 256, 4     # N: two lane tiles, folded first
LAYER = 1
ADVANCE = np.array([1, 0, 1, 1, 0], np.int32)


@pytest.fixture()
def pallas():
    attn_ops.set_attention_backend("pallas")
    attn_ops.clear_attention_paths()
    yield
    attn_ops.set_attention_backend("auto")


def _row(G: int, seed: int = 0, dtype=jnp.float32, P=P, N=N, H=None):
    """A decode row's operands and a plane with negative zeros in it."""
    H = GROUP * G if H is None else H
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    plane = jax.random.normal(k[0], (L, B, H, P, N), jnp.float32)
    plane = jnp.where(jnp.abs(plane) < 0.1, -0.0, plane).astype(dtype)
    x = jax.random.normal(k[1], (B, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[2], (B, H)) - 2.0)
    A = -jnp.exp(ssm.a_log_init(k[3], (H,)))
    Bm, Cm = (jax.random.normal(kk, (B, G, N), jnp.float32) for kk in k[4:])
    return (x, dt, A, Bm, Cm), plane


@functools.lru_cache(maxsize=None)
def _both(G: int, hb: int):
    """(the kernel's ``y`` and plane, XLA's ``y`` and layer, the plane they
    began from), once a geometry; the kernel at ``hb`` heads a tile."""
    row, plane = _row(G)
    picker = ssm_update._heads_block
    ssm_update._heads_block = lambda *a: hb
    attn_ops.set_attention_backend("pallas")
    try:
        why = []
        got = ssm_update.state_update(*row, plane, LAYER,
                                      jnp.asarray(ADVANCE), why)
    finally:
        attn_ops.set_attention_backend("auto")
        ssm_update._heads_block = picker
    assert got is not None, why
    want = ssm.decode_update(*row, plane[LAYER], jnp.asarray(ADVANCE))
    return tuple(np.asarray(a) for a in (*got, *want, plane))


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint32)


GEOMETRIES = pytest.mark.parametrize("G,hb", [
    (1, GROUP), (1, GROUP // 2), (2, GROUP), (2, GROUP // 2)],
    ids=["one_group_whole", "one_group_half", "two_groups_whole",
         "two_groups_half"])


@GEOMETRIES
def test_the_read_out_is_xlas_within_float32_reassociation(G, hb):
    y, _, want_y, _, _ = _both(G, hb)
    assert y.shape == (B, GROUP * G, P) and y.dtype == np.float32
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)


@GEOMETRIES
def test_the_advanced_states_are_xlas_within_float32_reassociation(G, hb):
    _, plane, _, want_S, was = _both(G, hb)
    moved = ADVANCE.astype(bool)
    np.testing.assert_allclose(plane[LAYER][moved], want_S[moved],
                               rtol=1e-5, atol=1e-6)
    assert not np.array_equal(plane[LAYER][moved], was[LAYER][moved])


@GEOMETRIES
def test_a_slot_that_does_not_advance_keeps_its_state_bit_for_bit(G, hb):
    y, plane, want_y, _, was = _both(G, hb)
    still = ~ADVANCE.astype(bool)
    assert np.signbit(was[LAYER][still][was[LAYER][still] == 0]).any()
    assert np.array_equal(_bits(plane[LAYER][still]),
                          _bits(was[LAYER][still]))
    # ... and its y is read from that unchanged state
    np.testing.assert_allclose(y[still], want_y[still], rtol=1e-5, atol=1e-5)


@GEOMETRIES
def test_every_other_layer_of_the_plane_comes_back_bit_for_bit(G, hb):
    _, plane, _, _, was = _both(G, hb)
    assert plane.shape == was.shape
    for layer in range(L):
        if layer != LAYER:
            assert np.array_equal(_bits(plane[layer]), _bits(was[layer]))


def test_a_jitted_caller_that_donates_the_plane_gets_it_back(pallas):
    """As the decode programs do: the plane donated, the kernel's first
    result the new plane of the same shape and dtype."""
    row, plane = _row(2)
    want = ssm.decode_update(*row, plane[LAYER], jnp.asarray(ADVANCE))

    def step(plane, advance):
        return ssm_update.state_update(*row, plane, LAYER, advance)

    y, new = jax.jit(step, donate_argnums=(0,))(plane, jnp.asarray(ADVANCE))
    assert new.shape == (L, B, GROUP * 2, P, N) and new.dtype == jnp.float32
    np.testing.assert_allclose(new[LAYER], want[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y, want[0], rtol=1e-5, atol=1e-5)


# --- the declines, by name --------------------------------------------------------
@pytest.mark.parametrize("case,kw,said", [
    ("a_bfloat16_state", dict(dtype=jnp.bfloat16), "bfloat16 state"),
    ("rows_off_the_sublane_tile", dict(P=12), "[12, 256] is no whole"),
    ("a_state_off_the_lane_tile", dict(N=64), "[16, 64] is no whole"),
    ("heads_that_are_not_whole_groups", dict(H=6), "6 heads in 4 groups"),
])
def test_the_wrapper_declines_by_name(case, kw, said, pallas):
    row, plane = _row(4 if "H" in kw else 2, **kw)
    why = []
    assert ssm_update.state_update(*row, plane, LAYER, jnp.asarray(ADVANCE),
                                   why) is None
    assert len(why) == 1 and said in why[0], why


@pytest.mark.parametrize("backend", ["xla", "auto"])
def test_the_wrapper_declines_where_pallas_is_off(backend):
    """``auto`` on the CPU is the XLA form: the kernel is the chip's."""
    row, plane = _row(2)
    attn_ops.set_attention_backend(backend)
    try:
        why = []
        assert ssm_update.state_update(
            *row, plane, LAYER, jnp.asarray(ADVANCE), why) is None
    finally:
        attn_ops.set_attention_backend("auto")
    assert why == [f"pallas off: backend {backend!r} on cpu"]


# --- the tile, from shapes --------------------------------------------------------
def test_the_tile_is_a_whole_group_where_it_fits_the_budget():
    # Falcon-H1: 16 heads a group of [128, 256] float32: 2 MB a tile, in
    # and out and double-buffered 8 MB
    assert tile_math.ssm_update_heads(16, 128, 256) == 16
    assert tile_math.ssm_update_tile_bytes(16, 128, 256) == 4 * (
        16 * 128 * 256 * 4 + 16 * 128 * 4)
    assert (tile_math.ssm_update_tile_bytes(16, 128, 256)
            <= tile_math.VMEM_BLOCK_BUDGET_BYTES)
    # a group twice as heavy is halved; an odd group is never split
    assert tile_math.ssm_update_heads(16, 128, 512) == 8
    assert tile_math.ssm_update_heads(64, 128, 256) == 16
    assert tile_math.ssm_update_heads(3, 8, 128) == 3
    assert ssm_update._heads_block(32, 2, 128, 256) == 16


# --- which form a program traced -------------------------------------------------
H1 = dataclasses.replace(TINY, ssm_state=128)      # a state of one lane tile


@pytest.fixture(scope="module")
def h1():
    return CausalLM(H1, name="falcon_h1_lane", dtype=jnp.float32)


def _trace_decode(model):
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    c = jax.eval_shape(lambda: model.make_paged_cache(2, 4, 128, 256))
    attn_ops.clear_attention_paths()
    jax.eval_shape(model.decode_step_paged, p, jnp.zeros((2, 1), jnp.int32),
                   c, jnp.ones((2,), bool))
    return [r for r in attn_ops.attention_paths() if r.path == "ssm"]


def test_a_decode_program_says_one_kernel_a_layer_where_pallas_is_on(
        h1, pallas):
    said = _trace_decode(h1)
    assert len(said) == H1.num_layers           # one record a layer
    assert all(r.kernel and r.interpret and not r.declines for r in said)
    assert {r.describe() for r in said} == {
        "state-space mixer, one step of the recurrence on the float32 "
        "state a slot in place (no pages), one kernel, the plane in place"}


@pytest.mark.parametrize("model_state,backend,why", [
    (128, "xla", "pallas off: backend 'xla' on cpu"),
    (16, "pallas", "a head's state [8, 16] is no whole (8, 128) tiles"),
])
def test_a_decode_program_says_xla_and_why(model_state, backend, why, h1):
    model = h1 if model_state == 128 else CausalLM(
        TINY, name="falcon_h1_tiny", dtype=jnp.float32)
    attn_ops.set_attention_backend(backend)
    try:
        said = _trace_decode(model)
    finally:
        attn_ops.set_attention_backend("auto")
    assert len(said) == H1.num_layers
    assert all(not r.kernel and not r.interpret for r in said)
    assert {r.declines for r in said} == {(why,)}
    assert {r.describe() for r in said} == {
        "state-space mixer, one step of the recurrence on the float32 "
        "state a slot in place (no pages), in XLA"}


def test_the_kernels_equation_is_in_the_decode_program_and_not_the_chunks(
        h1, pallas):
    """One ``pallas_call`` a layer under ``ssm_state_update`` beside the
    paged kernel's; the chunk program keeps the blocked scan in XLA."""
    p = jax.eval_shape(h1.init, jax.random.PRNGKey(0))
    c = jax.eval_shape(lambda: h1.make_paged_cache(2, 4, 128, 256))
    decode = str(jax.make_jaxpr(h1.decode_step_paged)(
        p, jnp.zeros((2, 1), jnp.int32), c, jnp.ones((2,), bool)))
    assert decode.count("name=_ssm_state_update") == H1.num_layers
    z = jnp.zeros((1, 32), jnp.int32)
    chunk = str(jax.make_jaxpr(
        lambda *a: h1.prefill_chunk_paged(*a[:-1], state_slots=a[-1]))(
        p, z, z, c, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 32, jnp.int32), jnp.zeros((1,), jnp.int32)))
    assert "_ssm_state_update" not in chunk


def test_importing_the_mixerless_program_imports_no_kernel_of_the_mixers():
    code = ("import sys, ray_dynamic_batching_tpu.engine.decode, "
            "ray_dynamic_batching_tpu.serve.llm; "
            "sys.exit('ray_dynamic_batching_tpu.ops.ssm_update' "
            "in sys.modules)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=300).returncode == 0


# --- a tiny model served, the kernel on against off --------------------------------
def _served(model, params, prompt, backend):
    attn_ops.set_attention_backend(backend)
    attn_ops.clear_attention_paths()
    try:
        engine, queue, tap = _engine(model, params, prompt_buckets=[64],
                                     default_max_new_tokens=17)
        req = _submit(queue, model, prompt, 17)
        engine.run_until_idle(timeout_s=600)
        steps = [r for r in attn_ops.attention_paths()
                 if r.path == "ssm" and r.q_shape[1] == 1]
    finally:
        attn_ops.set_attention_backend("auto")
    carried = sum(t.state_carries for t in engine.turns if t.kind == "chunk")
    return (list(req.future.result(timeout=5).tokens),
            np.concatenate(tap.rows, axis=0), steps, carried)


def test_a_tiny_model_serves_the_same_logits_with_the_kernel_on_as_off(h1):
    """A prompt of two chunks (the second begun from the state the first
    left) and 16 decode steps after the first token, through the engine's
    own programs: every row of logits they sample from, kernel on (and the
    paged kernel, both interpreted) against both off."""
    params = _seeded(h1, _load("benchmark/views/falcon_h1.py"))
    prompt = np.random.default_rng(3).integers(1, H1.vocab_size, 100)
    off_tokens, off_rows, off_steps, carried = _served(
        h1, params, prompt, "xla")
    on_tokens, on_rows, on_steps, _ = _served(h1, params, prompt, "pallas")
    assert carried == 1 and len(on_tokens) == 17
    assert on_steps and all(r.kernel for r in on_steps)
    assert off_steps and not any(r.kernel for r in off_steps)
    assert on_tokens == off_tokens
    assert on_rows.shape == off_rows.shape and len(on_rows) >= 17
    assert float(np.abs(off_rows).max()) > 0.5       # logits of unit size
    np.testing.assert_allclose(on_rows, off_rows, atol=2e-4, rtol=0)


# --- the tool that times it on the chip, rehearsed ---------------------------------
def test_the_kernel_ab_tool_runs_its_rows_at_a_tiny_plane():
    from tools import run_kernel_ab as ab

    rows = ab._time_ssm(1, (0, 2), geometry=("tiny", 3, 4, 8, 16, 256, 2),
                        samples=1)
    assert [(r["form"], r["heads_a_tile"]) for r in rows] == [
        ("xla", None), ("kernel", 4), ("kernel", 2)]
    for r in rows[1:]:
        assert r["idle_slots_and_other_layers_bit_for_bit"]
        assert r["max_abs_diff_y"] < 1e-4 and r["max_abs_diff_state"] < 1e-5
    assert all(r["layer_us"] > 0 and r["model_gb_per_s"] > 0 for r in rows)
    assert ssm_update._heads_block(32, 2, 128, 256) == 16   # put back
