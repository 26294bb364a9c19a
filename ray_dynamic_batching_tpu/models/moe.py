"""Mixture-of-experts FFN block: dropless top-k routing across the whole
batch, computed as a grouped matmul over rows sorted by expert.

Expert parallelism is absent from the reference (SURVEY.md §2.4 lists EP as
a from-scratch TPU design item). One routing, every shape static:

1. *route* — flatten to ``[B*T, D]``; router logits and the softmax over all
   ``E`` experts in float32; the ``k`` largest gates of each token, used as
   they are or renormalised to sum to one (``renormalize``, the published
   ``norm_topk_prob``); the ``B*T*k`` (token, choice) pairs sorted by expert
   and the rows gathered in that order, with ``E`` group sizes counted.
2. *experts* — :func:`ops.moe.expert_mlp` on the sorted rows: a grouped
   Pallas kernel on the TPU, ``jax.lax.ragged_dot`` elsewhere, the same rows
   and group sizes on both.
3. *combine* — rows back to token order (the inverse permutation), times
   their gates, summed over the ``k`` choices in float32.

Every token reaches all ``k`` of its experts: there is no capacity, nothing
is dropped, and a pad token or an inactive decode slot cannot take a real
token's place (it is computed and ignored). The cost follows the rows routed
(``B*T*k``), not ``E`` times the rows.

Expert weights carry a leading expert dim sharded over the ``ep`` mesh axis
(``models/causal_lm.py`` sharding rules). Under a mesh the block takes the
XLA form and GSPMD partitions it from the operands' shardings (tokens
replicated over ``ep``, so the answer is the single-device one); a
token-sharded deployment's all_to_all pair would sit where the rows are
gathered into expert order and where they are put back — the two
``named_scope``s below.

The load-balance auxiliary loss is sown under
``intermediates/moe_aux_loss``; the chosen experts ``[B, T, k]`` under
``moe_routing/top_idx`` (collected only where a caller asks:
``CausalLM``'s routing counters).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_dynamic_batching_tpu.ops import moe as moe_ops


class MoEBlock(nn.Module):
    d_model: int
    mlp_dim: int
    num_experts: int
    top_k: int = 2
    renormalize: bool = True  # top-k gates rescaled to sum to one
    gated: bool = True  # SwiGLU experts (matches the dense MLP family)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:  # [B, T, D]
        B, T, D = x.shape
        E, F, k = self.num_experts, self.mlp_dim, self.top_k
        N = B * T

        with jax.named_scope("moe_route"):
            router = nn.Dense(
                E, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32,
                # [N, D] x [D, E] is small; a near-tie between the k-th and
                # the next gate should not be decided by a bf16 MXU pass
                precision=jax.lax.Precision.HIGHEST, name="router",
            )
            flat = x.reshape(N, D)
            gates = jax.nn.softmax(router(flat.astype(jnp.float32)), axis=-1)
            top_gates, top_idx = jax.lax.top_k(gates, k)          # [N, k]
            if self.renormalize:
                top_gates = top_gates / jnp.maximum(
                    top_gates.sum(axis=-1, keepdims=True), 1e-9
                )
            # pair p = (token p // k, choice p % k); stable, so an expert's
            # rows stay in token order. (A counting sort, one running count
            # and a scatter in place of both argsorts, read the same substep
            # on the chip: 26.43 against 26.30-26.33 ms, PERF.md, PR 27.)
            pair_expert = top_idx.reshape(N * k)
            order = jnp.argsort(pair_expert, stable=True)
            group_sizes = (
                pair_expert[:, None] == jnp.arange(E, dtype=pair_expert.dtype)
            ).sum(axis=0).astype(jnp.int32)
            xs = flat[order // k].astype(self.dtype)               # [N*k, D]

        # expert weights: leading expert dim sharded over ep, F over tp
        init = nn.initializers.lecun_normal()
        wi = self.param("wi", init, (E, D, F), jnp.float32)
        wo = self.param("wo", init, (E, F, D), jnp.float32)
        wg = (self.param("wg", init, (E, D, F), jnp.float32)
              if self.gated else None)
        ys = moe_ops.expert_mlp(
            xs, group_sizes, wi.astype(self.dtype), wo.astype(self.dtype),
            None if wg is None else wg.astype(self.dtype),
        )

        with jax.named_scope("moe_combine"):
            back = jnp.argsort(order)        # where each pair's row went
            y = (ys[back].reshape(N, k, D).astype(jnp.float32)
                 * top_gates[..., None]).sum(axis=1)

        # load-balance aux loss (Shazeer/GShard): E * sum_e f_e * p_e
        density = jax.nn.one_hot(
            top_idx[:, 0].reshape(B, T), E, dtype=jnp.float32
        ).mean(axis=1)                                   # top-1 assignment frac
        mean_gate = gates.reshape(B, T, E).mean(axis=1)  # [B,E]
        aux = (density * mean_gate).sum(axis=-1).mean() * E
        self.sow("intermediates", "moe_aux_loss", aux)
        if not self.is_initializing():   # never part of an init's tree
            self.sow("moe_routing", "top_idx", top_idx.reshape(B, T, k))
        return y.reshape(B, T, D).astype(x.dtype)
