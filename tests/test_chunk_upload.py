"""A chunk group's per-dispatch state reaches its program as ONE upload
(``DecodeEngine._new_chunk_group`` / ``_cut_chunk_group`` /
``_issue_chunk_group``): the buffer's fields come back from the program's
cut bit for bit, float32 fields included; a served mix of greedy and seeded
sampled rows under a logit bias is the reference's
(``tests/decode_reference.py``: the model's full forward, nothing of
``engine/``); the launch makes one host-to-device transfer; the warm-up,
which builds its arguments through the same packer, leaves nothing to
compile at any (bucket, group) a dense model or a model with rings is
served at; filler rows repeat row 0 under a zero mask, so an expert
model's counters still count real rows only. CPU, float32, tiny widths.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
from tests.decode_reference import teacher_forced
from tests.test_mimo import TINY as RINGS_TINY, TOP_K

KINDS = ["dense", "rings"]


@pytest.fixture(scope="module")
def lms():
    """A dense tiny model, and a tiny model with state by layer kind (the
    window layers' rings beside the full layers' pages) that is also an
    expert model: ``tests/test_mimo.py``'s."""
    dense = get_model("llama_tiny", dtype=jnp.float32)
    rings = CausalLM(RINGS_TINY, name="rings_tiny", dtype=jnp.float32)
    return {"dense": (dense, dense.init(jax.random.PRNGKey(0))),
            "rings": (rings, rings.init(jax.random.PRNGKey(0)))}


def _engine(lm, **kw):
    model, params = lm
    queue = RequestQueue(model.name, max_len=256)
    opts = dict(num_slots=4, max_len=256, prompt_buckets=[8, 16],
                eos_token_id=None, default_max_new_tokens=4,
                decode_horizon=2, page_size=128, kv_pool_pages=8,
                max_admissions_per_step=2, prefill_token_budget=64)
    opts.update(kw)
    return DecodeEngine(model, params, queue, **opts), queue


def _request(queue, model_name, tokens, max_new=4, **payload):
    req = Request(model=model_name, slo_ms=60_000.0, payload=dict(
        tokens=[int(t) for t in tokens], max_new_tokens=max_new, **payload))
    queue.add_request(req)
    return req


def _tokens(req):
    return list(req.future.result(timeout=5).tokens)


def _chunks(engine):
    return [t for t in engine.turns if t.kind == "chunk"]


def _spy_on_the_program(engine):
    """Every call of the chunk program, as (arguments, the upload's host
    copy)."""
    calls, real = [], engine._chunk_paged_fn

    def spy(*args):
        calls.append((args, np.asarray(args[1])))
        return real(*args)

    engine._chunk_paged_fn = spy
    return calls


# --- (a) the buffer's fields, through the program's own cut ------------------
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("kind", KINDS)
def test_every_field_comes_back_from_the_programs_cut_bit_for_bit(
        lms, kind, group):
    engine, _ = _engine(lms[kind])
    assert bool(engine._ring_pages) is (kind == "rings")
    W, NP, E = 16, engine._n_table_entries, engine.max_bias_entries
    packed, f = engine._new_chunk_group(group, W)
    assert packed.dtype == np.int32 and packed.shape == (
        group, 2 * W + NP * (2 if kind == "rings" else 1) + 8 + 2 * E)
    rng = np.random.default_rng(group)
    f.tokens[:] = rng.integers(0, 2 ** 31 - 1, (group, W))
    f.mask[:] = rng.integers(0, 2, (group, W))
    f.table[:] = rng.integers(0, 9, (group, NP))
    if f.ring is not None:
        f.ring[:] = rng.integers(0, 9, (group, NP))
    # slot, start, take_idx, top_k, the largest seed a request can name,
    # new_len
    f.meta_i[:] = [3, 128, W - 1, 12, 2 ** 31 - 1, 141]
    f.meta_f[:] = [0.7, 0.9]
    f.bias_ids[:] = rng.integers(0, 500, (group, E))
    f.bias_vals[:] = rng.normal(size=(group, E)) * 50.0
    f.bias_vals[:, :3] = [-1e9, -2.5, -0.0]
    host = {name: np.array(x) for name, x in f._asdict().items()
            if x is not None}
    assert host["meta_f"].dtype == host["bias_vals"].dtype == np.float32
    assert host["meta_f"][0, 0] == np.float32(0.7)

    def cut(upload):
        return {name: x for name, x in
                engine._cut_chunk_group(upload)._asdict().items()
                if x is not None}

    device = jax.jit(cut)(jnp.asarray(packed))
    assert set(device) == set(host)
    for name, want in host.items():
        got = np.asarray(device[name])
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert got.tobytes() == want.tobytes(), name


def test_an_untouched_group_writes_nothing(lms):
    """What the warm-up uploads: every table entry and lengths slot the
    sentinel, greedy, a zero mask; with rings, the sentinel slot's ring
    (page ids past the ring pool: its writes drop too)."""
    engine, _ = _engine(lms["rings"])
    _, f = engine._new_chunk_group(2, 8)
    assert (f.table == engine.num_pages).all()
    assert (f.ring >= engine.num_slots * engine._ring_pages).all()
    assert (f.meta_i[:, 0] == engine.num_slots).all()
    assert (f.meta_f == [0.0, 1.0]).all()
    assert not f.tokens.any() and not f.mask.any()
    assert not f.bias_ids.any() and not f.bias_vals.any()


# --- (b) served tokens --------------------------------------------------------
def test_greedy_and_seeded_sampled_rows_under_a_bias_are_the_references(lms):
    model, params = lms["dense"]
    engine, queue = _engine(lms["dense"])
    rng = np.random.default_rng(3)
    greedy_prompt = rng.integers(1, 500, 10).tolist()
    sampled_prompt = rng.integers(1, 500, 12).tolist()
    # a bias that matters: it bans what the unbiased greedy row would say
    unbiased = teacher_forced(model, params, greedy_prompt, 4)
    biases = [{unbiased[0]: -50.0, unbiased[1]: -50.0},
              {7: 4.0, 11: -2.5, 499: 1.5}]
    sampling = dict(temperature=0.7, top_k=12, seed=2 ** 31 - 1)
    reqs = [
        _request(queue, model.name, greedy_prompt, logit_bias=biases[0]),
        _request(queue, model.name, sampled_prompt, logit_bias=biases[1],
                 **sampling),
    ]
    engine.run_until_idle(timeout_s=300)
    # both prompts rode ONE group of two rows
    assert [t.tokens for t in _chunks(engine)] == [2 * 16]
    for req, bias, kw in zip(reqs, biases, [{}, sampling]):
        ids = np.asarray(list(bias), np.int32)
        vals = np.asarray(list(bias.values()), np.float32)
        biased = types.SimpleNamespace(apply=lambda p, t, m: model.apply(
            p, t, m).astype(jnp.float32).at[:, :, ids].add(vals))
        want = teacher_forced(biased, params, req.payload["tokens"], 4, **kw)
        assert _tokens(req) == want
    assert _tokens(reqs[0])[0] != unbiased[0]


# --- (c) one transfer a launch ------------------------------------------------
def test_a_launch_makes_exactly_one_host_to_device_transfer(
        lms, monkeypatch):
    model, _ = lms["dense"]
    engine, queue = _engine(lms["dense"])
    _request(queue, model.name, range(1, 11))
    engine.run_until_idle(timeout_s=300)       # the shape is compiled now
    calls = _spy_on_the_program(engine)
    uploads, inside = [], []
    issue = engine._issue_chunk_group

    def issuing(trains):
        inside.append(True)
        try:
            return issue(trains)
        finally:
            inside.pop()

    def counting(real):
        def wrapped(x, *a, **kw):
            if inside and not isinstance(x, jax.Array):
                uploads.append(np.shape(x))
            return real(x, *a, **kw)
        return wrapped

    monkeypatch.setattr(engine, "_issue_chunk_group", issuing)
    monkeypatch.setattr(jnp, "asarray", counting(jnp.asarray))
    monkeypatch.setattr(jax, "device_put", counting(jax.device_put))
    req = _request(queue, model.name, range(2, 14),
                   temperature=0.7, top_k=8, seed=5, logit_bias={3: -1.0})
    engine.run_until_idle(timeout_s=300)
    monkeypatch.undo()
    assert len(_tokens(req)) == 4
    (args, upload), = calls
    assert uploads == [upload.shape]
    # ... and nothing rode in as a numpy array for the call to transfer
    assert len(args) == 3 and args[2] is not None
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(args))
    assert args[1].dtype == jnp.int32 and args[1].ndim == 2


# --- (d) the warm-up's shapes are the served ones -----------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_after_the_warm_up_no_bucket_or_group_compiles(lms, kind):
    model, _ = lms[kind]
    engine, queue = _engine(lms[kind])
    engine.warmup()
    program = engine._chunk_paged_fn.__wrapped__
    warmed = program._cache_size()
    assert warmed == 4                      # buckets 8, 16 x groups 1, 2
    rng = np.random.default_rng(11)
    for n in (1, 2):                        # trains in one launch
        for length in (6, 13):              # buckets 8 and 16
            reqs = [_request(queue, model.name,
                             rng.integers(1, 200, length))
                    for _ in range(n)]
            engine.run_until_idle(timeout_s=300)
            assert all(len(_tokens(r)) == 4 for r in reqs)
    assert sorted(t.tokens for t in _chunks(engine)) == [8, 16, 2 * 8, 2 * 16]
    assert program._cache_size() == warmed


# --- (e) filler rows ----------------------------------------------------------
def test_filler_rows_repeat_row_0_under_a_zero_mask(lms):
    model, _ = lms["dense"]
    engine, queue = _engine(lms["dense"], max_admissions_per_step=4)
    calls = _spy_on_the_program(engine)
    rng = np.random.default_rng(5)
    for length in (9, 12, 16):
        _request(queue, model.name, rng.integers(1, 500, length))
    engine.run_until_idle(timeout_s=300)
    (_, upload), = calls                     # three trains, a group of four
    assert upload.shape[0] == 4
    f = engine._cut_chunk_group(upload)
    assert [int(r.sum()) for r in f.mask] == [9, 12, 16, 0]
    filler, first = (np.delete(row, np.s_[16:32]) for row in
                     (upload[3], upload[0]))
    assert (filler == first).all()
    assert len({row.tobytes() for row in upload[:3]}) == 3


def test_an_expert_models_counters_count_real_rows_only(lms):
    model, _ = lms["rings"]
    engine, queue = _engine(lms["rings"], max_admissions_per_step=4)
    calls = _spy_on_the_program(engine)
    rng = np.random.default_rng(6)
    lengths = (9, 12, 16)
    for length in lengths:
        _request(queue, model.name, rng.integers(1, 200, length))
    engine.run_until_idle(timeout_s=300)
    (_, upload), = calls
    assert upload.shape[0] == 4
    f = engine._cut_chunk_group(upload)
    assert (f.ring[3] == f.ring[0]).all() and (f.mask[3] == 0).all()
    chunk, = _chunks(engine)
    expert_layers = RINGS_TINY.num_layers - RINGS_TINY.num_dense_layers
    assert chunk.moe_pairs == sum(lengths) * TOP_K * expert_layers
    assert 0 < chunk.moe_rows <= chunk.moe_pairs
