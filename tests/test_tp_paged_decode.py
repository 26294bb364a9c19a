"""TP-mesh paged decode — the PR 7 exclusion lifted (ROADMAP item 2).

``DecodeEngine(mesh=...)`` shards the page pool over the
mesh's kv-head (tp) axis — codes AND int8 scale planes — while the page
table, lengths, and the host-side free-list allocator stay
replica-global (page indices are shard-invariant). The contract is the
same byte-identical-tokens bar every other cache layout meets: a seeded
workload (greedy rows + one seeded sampled row) through a TP=2 paged
engine must emit EXACTLY the tokens of (a) the single-chip engine
and (b) the model-level reference that shares no engine code
(``tests/decode_reference.py``), f32 and int8-KV, on the forced-8-device
CPU host (tier-1 — the fake-chip cluster runs the real GSPMD paths).

Kept un-marked (tier-1) like the rest of test_paged_decode's tiny-model
engine runs: llama_tiny compiles in seconds and this is exactly the
serving configuration the mesh-placement planner hands out.
"""

import jax
import jax.numpy as jnp
import pytest

from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.parallel.mesh import MeshConfig, build_mesh

from tests.decode_reference import assert_served
from tests.test_paged_decode import _workload


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def lm_int8(lm):
    model = get_model("llama_tiny_int8kv", dtype=jnp.float32)
    # Same weights as the f32 fixture: only the cache dtype differs.
    return model, lm[1]


def tp2_mesh():
    return build_mesh(MeshConfig(tp=2), jax.devices()[:2])


def _run(model, params, mesh=None):
    queue = RequestQueue(model.name, max_len=256)
    engine = DecodeEngine(
        model, params, queue,
        num_slots=4, max_len=64, prompt_buckets=[8, 16],
        default_max_new_tokens=8, decode_horizon=4,
        page_size=128, mesh=mesh,
    )
    reqs = _workload(queue, model.name)
    engine.run_until_idle(timeout_s=180)
    tokens = [tuple(r.future.result(timeout=5).tokens) for r in reqs]
    return tokens, engine, reqs


class TestTPPagedTokenExactness:
    def test_tp2_paged_matches_single_chip_paged_f32(self, lm,
                                                     eight_devices):
        model, params = lm
        single, _, _ = _run(model, params)
        tp, engine, _ = _run(model, params, mesh=tp2_mesh())
        assert tp == single
        # The replica-global allocator's conservation invariants hold
        # under the sharded pool, and a drained engine returns every
        # page (no cache configured -> nothing pinned).
        engine._allocator.check()
        assert engine._allocator.free_pages == engine.num_pages

    @pytest.mark.parametrize("case", ["f32", "int8_kv"])
    def test_tp2_serves_the_reference(self, case, lm, lm_int8,
                                      eight_devices):
        """The sharded pool against the model itself: the full forward
        for f32; for the int8-KV pool (codes and scale planes shard
        together) the model's own int8 slab ``KVCache``, and the
        single-chip engine."""
        model, params = lm_int8 if case == "int8_kv" else lm
        tp, _, reqs = _run(model, params, mesh=tp2_mesh())
        assert_served(model, params, reqs, tp, cached=case == "int8_kv")
        if case == "int8_kv":
            assert tp == _run(model, params)[0]


class TestTPPagedKernel:
    """The shard_map wrapper around the Pallas page-table kernel
    (interpret mode — the CPU-runnable half of the TPU lowering):
    per-shard head slices through the same ``_accumulate_tile`` body must
    reproduce the unsharded kernel bit-for-bit for an int8 pool; a float
    pool's block narrower than 8 heads is folded in ONE contraction over
    the page's tile view (``_fold_flat``), whose width the shard halves:
    the same numbers in another order of f32 sums."""

    def _mesh_out(self, dtype, eight_devices):
        import numpy as np

        from tests.test_paged_decode import TestPagedKernel
        from ray_dynamic_batching_tpu.ops import decode_attention as da

        pool = TestPagedKernel()
        q, k, v, ks, vs, pt, lens, dims = pool._pool(dtype)
        base = da.paged_decode_attention(
            q, k, v, pt, lens, k_scale=ks, v_scale=vs, interpret=True
        )
        mesh = tp2_mesh()
        out = da.paged_decode_attention(
            q, k, v, pt, lens, k_scale=ks, v_scale=vs, interpret=True,
            mesh=mesh,
        )
        assert out is not None and base is not None
        if ks is None:
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(base), rtol=2e-6, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(out), np.asarray(base))

    def test_tp2_kernel_matches_unsharded_f32(self, eight_devices):
        self._mesh_out(jnp.float32, eight_devices)

    def test_tp2_kernel_matches_unsharded_int8(self, eight_devices):
        self._mesh_out(jnp.int8, eight_devices)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                             ids=["bf16", "int8"])
    def test_tp2_kernel_stops_at_unequal_lengths(self, dtype,
                                                 eight_devices):
        """The length guard under ``shard_map``: lengths and table
        replicate, so every shard skips the same pages. Unequal lengths,
        an idle slot, a verify window that crosses a page edge, NaN pages
        past the lengths: the sharded kernel equals the unsharded one bit
        for bit, and the whole-table walk in every consumed row."""
        import numpy as np

        from tests.test_paged_decode import (
            LIVENESS_LENGTHS,
            liveness_case,
            walk_whole_table,
        )
        from ray_dynamic_batching_tpu.ops import decode_attention as da

        lengths = LIVENESS_LENGTHS["mixed"]
        q, k, v, ks, vs, table, lens, clean = liveness_case(
            dtype, lengths, 5, "nan")
        kw = dict(k_scale=ks, v_scale=vs, interpret=True)
        base = da.paged_decode_attention(q, k, v, table, lens, **kw)
        out = da.paged_decode_attention(q, k, v, table, lens,
                                        mesh=tp2_mesh(), **kw)
        assert out is not None and base is not None
        out = np.asarray(out.astype(jnp.float32))
        np.testing.assert_array_equal(
            out, np.asarray(base.astype(jnp.float32)))
        used = [b for b, n in enumerate(lengths) if n >= 0]
        whole = walk_whole_table(q, k, v, ks, vs, clean, lens, kernel=True)
        np.testing.assert_array_equal(
            out[used], np.asarray(whole.astype(jnp.float32))[used])

    def test_kernel_declines_indivisible_heads(self, eight_devices):
        """K=4 heads under tp=8 cannot split: the kernel declines and
        the dispatcher falls back to the GSPMD-partitioned gather."""
        from tests.test_paged_decode import TestPagedKernel
        from ray_dynamic_batching_tpu.ops import decode_attention as da
        from ray_dynamic_batching_tpu.parallel.mesh import (
            MeshConfig,
            build_mesh,
        )

        q, k, v, _ks, _vs, pt, lens, _ = TestPagedKernel()._pool(
            jnp.float32)
        mesh = build_mesh(MeshConfig(tp=8), jax.devices()[:8])
        assert da.paged_decode_attention(
            q, k, v, pt, lens, interpret=True, mesh=mesh
        ) is None


class TestTPPagedPoolLayout:
    def test_pool_sharded_table_replicated(self, lm_int8, eight_devices):
        """The pool's k/v (and scale) planes split on the kv-head dim
        (index 3 of [L, P, ps, K, H]); the page table replicates — the
        shard-invariant-page-indices contract that keeps the allocator
        host-side and replica-global."""
        model, params = lm_int8
        queue = RequestQueue(model.name, max_len=16)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=128,
            prompt_buckets=[8], paged=True, page_size=128,
            mesh=tp2_mesh(),
        )
        cache = engine._cache
        K = cache.k.shape[3]
        for plane in (cache.k, cache.v):
            assert not plane.sharding.is_fully_replicated
            assert plane.sharding.shard_shape(plane.shape)[3] == K // 2
        for plane in (cache.k_scale, cache.v_scale):
            assert plane.sharding.shard_shape(plane.shape)[3] == K // 2
        assert cache.page_table.sharding.is_fully_replicated
        assert cache.lengths.sharding.is_fully_replicated

    def test_indivisible_heads_replicate(self, lm, eight_devices):
        """kv_heads=2 under tp=4: the feasible-spec rule replicates the
        head axis instead of erroring, and the engine still builds."""
        model, params = lm
        mesh = build_mesh(MeshConfig(tp=4), jax.devices()[:4])
        queue = RequestQueue(model.name, max_len=16)
        engine = DecodeEngine(
            model, params, queue, num_slots=2, max_len=128,
            prompt_buckets=[8], paged=True, page_size=128, mesh=mesh,
        )
        k = engine._cache.k
        assert k.sharding.shard_shape(k.shape)[3] == k.shape[3]
