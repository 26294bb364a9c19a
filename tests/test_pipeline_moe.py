"""Pipeline parallelism (pp) and expert parallelism (ep/MoE) on the fake
8-chip cluster: numerical parity vs the unsharded model and end-to-end
sharded train steps."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy (fast lane excludes)

from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model
from ray_dynamic_batching_tpu.models.moe import MoEBlock
from ray_dynamic_batching_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_dynamic_batching_tpu.parallel.pipeline import (
    PipelinedCausalLM,
    make_pp_train_state,
    make_pp_train_step,
)


def _mesh(**kw):
    cfg = MeshConfig(**kw)
    return build_mesh(cfg, jax.devices()[: cfg.n_devices])


# --- MoE --------------------------------------------------------------------

class TestMoE:
    def test_single_expert_equals_dense_mlp(self):
        """E=1, k=1: MoE must equal the plain expert MLP."""
        D, F, B, T = 16, 32, 2, 8
        block = MoEBlock(
            d_model=D, mlp_dim=F, num_experts=1, top_k=1,
            gated=True, dtype=jnp.float32,
        )
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((B, T, D)), jnp.float32
        )
        params = block.init(jax.random.PRNGKey(0), x)
        y = block.apply(params, x)
        wi = params["params"]["wi"][0]
        wg = params["params"]["wg"][0]
        wo = params["params"]["wo"][0]
        ref = (jax.nn.silu(x @ wg) * (x @ wi)) @ wo
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_moe_model_forward_and_aux(self):
        model = get_model("moe_tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0))
        tokens, mask = model.example_inputs(2, 16)
        logits = model.apply(params, tokens, mask)
        assert logits.shape == (2, 16, model.cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()

    def test_moe_sharded_matches_single_device(self):
        model = get_model("moe_tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        B, T = 4, 16
        tokens = jnp.asarray(
            rng.integers(0, model.cfg.vocab_size, (B, T)), jnp.int32
        )
        mask = jnp.ones((B, T), jnp.int32)
        ref = model.apply(params, tokens, mask)

        from ray_dynamic_batching_tpu.parallel.mesh import shard_params

        mesh = _mesh(dp=2, tp=2, ep=2)
        with mesh:
            sharded = shard_params(mesh, model, params)
            out = jax.jit(model.apply)(sharded, tokens, mask)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-4, rtol=1e-4
        )

    def test_moe_train_step_on_mesh(self):
        from ray_dynamic_batching_tpu.parallel.train import (
            make_sharded_train_state,
            make_train_step,
        )

        model = get_model("moe_tiny", dtype=jnp.float32)
        mesh = _mesh(dp=2, tp=2, ep=2)
        optimizer = optax.adamw(1e-3)
        with mesh:
            params, opt_state = make_sharded_train_state(model, mesh, optimizer)
            step = make_train_step(model, mesh, optimizer)
            rng = np.random.default_rng(3)
            tokens = jnp.asarray(
                rng.integers(0, model.cfg.vocab_size, (4, 16)), jnp.int32
            )
            mask = jnp.ones((4, 16), jnp.int32)
            params, opt_state, loss = step(params, opt_state, tokens, mask)
            assert np.isfinite(float(loss))


# --- pipeline ---------------------------------------------------------------

class TestPipeline:
    @pytest.mark.parametrize("pp,n_micro", [(2, 2), (4, 4), (2, 1)])
    def test_pipelined_forward_matches_unsharded(self, pp, n_micro):
        if pp == 4:  # needs layers % stages == 0
            from ray_dynamic_batching_tpu.models.causal_lm import (
                CausalLM,
                TINY_LM,
            )
            import dataclasses

            cfg = dataclasses.replace(TINY_LM, num_layers=4)
            model = CausalLM(cfg, name="tiny4", dtype=jnp.float32)
        else:
            model = get_model("llama_tiny", dtype=jnp.float32)
        mesh = _mesh(pp=pp, dp=1)
        pmodel = PipelinedCausalLM(model, mesh, n_microbatches=n_micro)
        full = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(4)
        B, T = 4, 16
        tokens = jnp.asarray(
            rng.integers(0, model.cfg.vocab_size, (B, T)), jnp.int32
        )
        mask = jnp.ones((B, T), jnp.int32)
        ref = model.apply(full, tokens, mask)
        split = pmodel.split_params(full)
        with mesh:
            split = jax.device_put(split, pmodel.shardings())
            out = jax.jit(pmodel.apply)(split, tokens, mask)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-4, rtol=1e-4
        )

    def test_pipelined_gpt2_branches_match(self):
        """Learned positions + tied embeddings + LayerNorm (the GPT-2 config
        family) through the pipelined embed/head — parity vs unsharded."""
        import dataclasses

        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM, TINY_LM

        cfg = dataclasses.replace(
            TINY_LM, pos="learned", norm="ln", gated_mlp=False,
            use_bias=True, tie_embeddings=True,
        )
        model = CausalLM(cfg, name="gpt2ish_tiny", dtype=jnp.float32)
        mesh = _mesh(pp=2)
        pmodel = PipelinedCausalLM(model, mesh, n_microbatches=2)
        full = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32
        )
        mask = jnp.ones((4, 16), jnp.int32)
        ref = model.apply(full, tokens, mask)
        with mesh:
            split = jax.device_put(
                pmodel.split_params(full), pmodel.shardings()
            )
            out = jax.jit(pmodel.apply)(split, tokens, mask)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-4, rtol=1e-4
        )

    def test_moe_aux_loss_collected(self):
        """apply_with_aux must surface a positive router balance loss, both
        unsharded and through the pipeline."""
        model = get_model("moe_tiny", dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(8)
        tokens = jnp.asarray(
            rng.integers(0, model.cfg.vocab_size, (4, 16)), jnp.int32
        )
        mask = jnp.ones((4, 16), jnp.int32)
        _, aux = model.apply_with_aux(params, tokens, mask)
        assert float(aux) > 0.5  # ~num_layers * 1.0 at uniform routing

        mesh = _mesh(pp=2)
        pmodel = PipelinedCausalLM(model, mesh, n_microbatches=2)
        with mesh:
            split = jax.device_put(
                pmodel.split_params(params), pmodel.shardings()
            )
            _, aux_pp = jax.jit(pmodel.apply_with_aux)(split, tokens, mask)
        np.testing.assert_allclose(float(aux_pp), float(aux), rtol=1e-4)

    def test_pipeline_degrades_indivisible_tp(self):
        """tp=4 > kv_heads=2: pipelined shardings must replicate the kv
        projections instead of erroring (mesh._feasible_spec parity)."""
        import dataclasses

        from ray_dynamic_batching_tpu.models.causal_lm import CausalLM, TINY_LM

        cfg = dataclasses.replace(TINY_LM, num_heads=4, num_kv_heads=2)
        model = CausalLM(cfg, name="tiny_gqa", dtype=jnp.float32)
        mesh = _mesh(pp=2, tp=4)
        pmodel = PipelinedCausalLM(model, mesh, n_microbatches=2)
        with mesh:
            params = pmodel.shard_init(jax.random.PRNGKey(0))  # must not raise
        assert params is not None

    def test_split_merge_roundtrip(self):
        model = get_model("llama_tiny", dtype=jnp.float32)
        mesh = _mesh(pp=2)
        pmodel = PipelinedCausalLM(model, mesh)
        full = model.init(jax.random.PRNGKey(0))
        back = pmodel.merge_params(pmodel.split_params(full))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            full,
            back,
        )

    def test_pp_train_step(self):
        model = get_model("llama_tiny", dtype=jnp.float32)
        mesh = _mesh(dp=2, pp=2, tp=2)
        pmodel = PipelinedCausalLM(model, mesh, n_microbatches=2)
        optimizer = optax.adamw(1e-3)
        with mesh:
            params, opt_state = make_pp_train_state(pmodel, optimizer)
            step = make_pp_train_step(pmodel, optimizer)
            rng = np.random.default_rng(5)
            tokens = jnp.asarray(
                rng.integers(0, model.cfg.vocab_size, (4, 16)), jnp.int32
            )
            mask = jnp.ones((4, 16), jnp.int32)
            params, opt_state, loss = step(params, opt_state, tokens, mask)
            loss2 = step(params, opt_state, tokens, mask)[2]
            assert np.isfinite(float(loss2)) and float(loss2) < float(loss)

    def test_pp_moe_combined(self):
        """Pipeline + experts + data parallel in one program (pp*ep*dp=8)."""
        model = get_model("moe_tiny", dtype=jnp.float32)
        mesh = _mesh(dp=2, pp=2, ep=2)
        pmodel = PipelinedCausalLM(model, mesh, n_microbatches=2)
        optimizer = optax.adamw(1e-3)
        with mesh:
            params, opt_state = make_pp_train_state(pmodel, optimizer)
            step = make_pp_train_step(pmodel, optimizer)
            rng = np.random.default_rng(6)
            tokens = jnp.asarray(
                rng.integers(0, model.cfg.vocab_size, (4, 16)), jnp.int32
            )
            mask = jnp.ones((4, 16), jnp.int32)
            params, opt_state, loss = step(params, opt_state, tokens, mask)
            assert np.isfinite(float(loss))
