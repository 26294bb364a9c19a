"""Model zoo tests: shapes, jit-ability, KV-cache decode equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy (fast lane excludes)

from ray_dynamic_batching_tpu.models import registry
from ray_dynamic_batching_tpu.models.base import get_model, param_path_specs
from ray_dynamic_batching_tpu.models.kv_state import KVCache

TINY_VISION = ["resnet18_tiny", "shufflenet_tiny", "vit_tiny", "efficientnet_tiny"]


@pytest.mark.parametrize("name", TINY_VISION)
def test_vision_forward_shapes(name):
    model = get_model(name, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    (x,) = model.example_inputs(4)
    logits = jax.jit(model.apply)(params, x)
    assert logits.shape == (4, 10)
    assert jnp.isfinite(logits).all()


def test_registry_contents():
    names = registry.registered_models()
    for required in [
        "resnet50",
        "shufflenet_v2",
        "vit_b_16",
        "efficientnet_v2s",
        "distilbert_sst2",
        "gpt2_medium",
        "llama3_8b",
    ]:
        assert required in names
    assert registry.get_slo("resnet50").latency_slo_ms == 2000.0


def test_distilbert_mask_invariance():
    """Padding tokens must not change the classification output."""
    model = get_model("distilbert_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 1000, size=(2, 8)).astype(np.int32)
    mask = np.ones((2, 8), np.int32)
    out_short = model.apply(params, jnp.array(toks), jnp.array(mask))
    # pad to 16 with garbage tokens, mask them off
    toks_pad = np.concatenate(
        [toks, rng.integers(0, 1000, size=(2, 8)).astype(np.int32)], axis=1
    )
    mask_pad = np.concatenate([mask, np.zeros((2, 8), np.int32)], axis=1)
    out_pad = model.apply(params, jnp.array(toks_pad), jnp.array(mask_pad))
    np.testing.assert_allclose(out_short, out_pad, atol=1e-4)


class TestCausalLM:
    @pytest.fixture(scope="class")
    def lm(self):
        model = get_model("llama_tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0))
        return model, params

    def test_prefill_matches_apply(self, lm):
        model, params = lm
        tokens = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=jnp.int32)
        attn_mask = jnp.ones_like(tokens)
        full_logits = model.apply(params, tokens, attn_mask)
        cache = model.make_cache(1, max_len=16)
        last, cache = model.prefill(params, tokens, attn_mask, cache)
        np.testing.assert_allclose(last, full_logits[:, -1], rtol=2e-4, atol=2e-4)
        assert int(cache.lengths[0]) == 8

    def test_incremental_decode_matches_full_forward(self, lm):
        """Greedy decode via cache == rerunning the full sequence each step."""
        model, params = lm
        prompt = jnp.array([[5, 9, 2, 7]], dtype=jnp.int32)
        attn_mask = jnp.ones_like(prompt)
        cache = model.make_cache(1, max_len=16)
        last, cache = model.prefill(params, prompt, attn_mask, cache)
        seq = list(np.asarray(prompt)[0])
        for _ in range(4):
            nxt = int(jnp.argmax(last, axis=-1)[0])
            # reference: full forward over seq + nxt
            seq.append(nxt)
            ref_tokens = jnp.array([seq], dtype=jnp.int32)
            ref_logits = model.apply(params, ref_tokens, jnp.ones_like(ref_tokens))
            last, cache = model.decode_step(
                params,
                jnp.array([[nxt]], dtype=jnp.int32),
                cache,
                jnp.array([True]),
            )
            np.testing.assert_allclose(
                last, ref_logits[:, -1], rtol=2e-3, atol=2e-3
            )

    def test_ragged_batch_prefill(self, lm):
        """Rows with different true lengths prefill correctly in one batch."""
        model, params = lm
        tokens = jnp.array(
            [[1, 2, 3, 0, 0, 0, 0, 0], [4, 5, 6, 7, 8, 9, 10, 11]], dtype=jnp.int32
        )
        attn_mask = jnp.array(
            [[1, 1, 1, 0, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1, 1, 1]], dtype=jnp.int32
        )
        cache = model.make_cache(2, max_len=16)
        last, cache = model.prefill(params, tokens, attn_mask, cache)
        # row 0 must match an unpadded 3-token prefill
        solo = model.apply(params, tokens[:1, :3], attn_mask[:1, :3])
        np.testing.assert_allclose(last[0], solo[0, -1], rtol=2e-4, atol=2e-4)
        assert list(np.asarray(cache.lengths)) == [3, 8]

    def test_gqa_heads(self, lm):
        model, _ = lm
        assert model.cfg.num_kv_heads < model.cfg.num_heads
        cache = model.make_cache(2, max_len=8)
        assert cache.k.shape == (2, 2, 8, 2, 16)  # [L,B,S,K,H]


def test_sharding_rules_cover_llama_params():
    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    specs = param_path_specs(model, params)
    flat = jax.tree_util.tree_leaves_with_path(specs)
    # Attention + MLP kernels must be TP-sharded; norms replicated.
    tp_count = sum(
        1 for _p, spec in flat if any(ax == "tp" for ax in spec if ax is not None)
    )
    assert tp_count > 0
    for path, spec in flat:
        s = "/".join(str(getattr(k, "key", k)) for k in path)
        if "norm" in s:
            assert spec == jax.sharding.PartitionSpec()
