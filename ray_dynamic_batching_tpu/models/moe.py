"""Mixture-of-experts FFN block: dropless top-k routing across the whole
batch, computed as a grouped matmul over rows sorted by expert; optionally
ONE RANK'S SHARE of an expert-parallel layer, and a shared expert beside
the routed ones.

Expert parallelism is absent from the reference (SURVEY.md §2.4 lists EP as
a from-scratch TPU design item). One routing, every shape static:

1. *route* — flatten to ``[B*T, D]``; router logits and the scores over all
   ``E`` experts in float32, by the :class:`RoutingRule` (softmax, or
   sigmoid with a learned selection bias that decides the CHOICE only); the
   ``k`` chosen scores of each token, used as they are or renormalised to
   sum to one (the published ``norm_topk_prob``) and scaled; the ``B*T*k``
   (token, choice) pairs sorted by expert and the rows gathered in that
   order, with the group sizes counted.
2. *experts* — :func:`ops.moe.expert_mlp` on the sorted rows: a grouped
   Pallas kernel on the TPU, ``jax.lax.ragged_dot`` elsewhere, the same rows
   and group sizes on both.
3. *combine* — rows back to token order (the inverse permutation), times
   their gates, summed over the ``k`` choices in float32; plus the shared
   expert (``moe_shared``: the dense SwiGLU's code, on every row).

Every token reaches all ``k`` of its experts: there is no capacity, nothing
is dropped, and a pad token or an inactive decode slot cannot take a real
token's place (it is computed and ignored). The cost follows the rows routed
(``B*T*k``), not ``E`` times the rows.

**One rank's share** (``held_experts < num_experts``): the router scores and
chooses over all ``E``; the block holds ``wi``/``wg``/``wo`` for experts
``[first_expert, first_expert + held_experts)`` only and computes the part
of the result those give. Pairs on absent experts sort behind every held
group: they belong to no group, so the grouped kernel has no work item for
them (no weight read, no matmul), and the combine leaves them out. What the
absent experts would add is simply not in the result — on one chip the layer
runs without its exchange, and nothing here stands in for the other ranks.
Shapes are static, so the sorted row buffer is still ``B*T*k`` rows long
(every choice of every token may land here): the gather and the combine
move that many rows of ``D``, the kernel's work follows the rows routed
HERE.

Expert weights carry a leading expert dim sharded over the ``ep`` mesh axis
(``models/causal_lm.py`` sharding rules). Under a mesh the block takes the
XLA form and GSPMD partitions it from the operands' shardings (tokens
replicated over ``ep``, so the answer is the single-device one); a
token-sharded deployment's all_to_all pair would sit where the rows are
gathered into expert order and where they are put back — the two
``named_scope``s below.

The load-balance auxiliary loss is sown under
``intermediates/moe_aux_loss``; the chosen experts ``[B, T, k]`` (ids among
all ``E``) under ``moe_routing/top_idx`` (collected only where a caller
asks: ``CausalLM``'s routing counters).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_dynamic_batching_tpu.models.decoder import swiglu
from ray_dynamic_batching_tpu.ops import moe as moe_ops


@dataclasses.dataclass(frozen=True)
class RoutingRule:
    """How router logits become a token's experts and their gates."""

    scoring: str = "softmax"       # "softmax" over all E | "sigmoid" each
    selection_bias: bool = False   # a learned [E] bias, for the CHOICE only
    renormalize: bool = True       # chosen gates rescaled to sum to one
    scale: float = 1.0             # ... then times this

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r}")

    def describe(self) -> str:
        return (f"{self.scoring}"
                + (" + selection bias" if self.selection_bias else "")
                + (", renormalised" if self.renormalize else "")
                + (f", x {self.scale:g}" if self.scale != 1.0 else ""))

    def route(self, logits: jax.Array, bias: Optional[jax.Array],
              k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """logits [N, E] float32 -> (gates [N, k], experts [N, k], scores
        [N, E])."""
        scores = (jax.nn.softmax(logits, axis=-1)
                  if self.scoring == "softmax" else jax.nn.sigmoid(logits))
        if bias is None:
            top_gates, top_idx = jax.lax.top_k(scores, k)
        else:
            _, top_idx = jax.lax.top_k(scores + bias, k)
            top_gates = jnp.take_along_axis(scores, top_idx, axis=-1)
        if self.renormalize:
            top_gates = top_gates / jnp.maximum(
                top_gates.sum(axis=-1, keepdims=True), 1e-9
            )
        if self.scale != 1.0:
            top_gates = top_gates * self.scale
        return top_gates, top_idx, scores


def routing_rule(cfg: Any) -> RoutingRule:
    """The rule a ``DecoderConfig``'s ``moe_*`` fields spell."""
    return RoutingRule(
        scoring=cfg.moe_scoring, selection_bias=cfg.moe_selection_bias,
        renormalize=cfg.moe_renormalize, scale=cfg.moe_gate_scale)


class MoEBlock(nn.Module):
    d_model: int
    mlp_dim: int
    num_experts: int
    top_k: int = 2
    rule: RoutingRule = RoutingRule()
    first_expert: int = 0    # this rank holds experts [first, first + held)
    held_experts: int = 0    # 0 = all of them
    shared_dim: int = 0      # width of the shared expert beside them; 0 = none
    gated: bool = True  # SwiGLU experts (matches the dense MLP family)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:  # [B, T, D]
        B, T, D = x.shape
        E, F, k = self.num_experts, self.mlp_dim, self.top_k
        H = self.held_experts or E
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
            bias = (self.param("selection_bias", nn.initializers.zeros,
                               (E,), jnp.float32)
                    if self.rule.selection_bias else None)
            top_gates, top_idx, gates = self.rule.route(
                router(flat.astype(jnp.float32)), bias, k)
            # pair p = (token p // k, choice p % k); stable, so an expert's
            # rows stay in token order. (A counting sort, one running count
            # and a scatter in place of both argsorts, read the same substep
            # on the chip: 26.43 against 26.30-26.33 ms, PERF.md, PR 27.)
            pair_expert = top_idx.reshape(N * k)
            held = None
            if H < E:
                # local ids; a pair on an absent expert sorts behind every
                # held group and belongs to none
                pair_expert = pair_expert - self.first_expert
                held = (pair_expert >= 0) & (pair_expert < H)
                pair_expert = jnp.where(held, pair_expert, H)
            order = jnp.argsort(pair_expert, stable=True)
            group_sizes = (
                pair_expert[:, None] == jnp.arange(H, dtype=pair_expert.dtype)
            ).sum(axis=0).astype(jnp.int32)
            xs = flat[order // k].astype(self.dtype)               # [N*k, D]

        # expert weights: leading expert dim sharded over ep, F over tp
        init = nn.initializers.lecun_normal()
        wi = self.param("wi", init, (H, D, F), jnp.float32)
        wo = self.param("wo", init, (H, F, D), jnp.float32)
        wg = (self.param("wg", init, (H, D, F), jnp.float32)
              if self.gated else None)
        ys = moe_ops.expert_mlp(
            xs, group_sizes, wi.astype(self.dtype), wo.astype(self.dtype),
            None if wg is None else wg.astype(self.dtype),
        )

        with jax.named_scope("moe_combine"):
            back = jnp.argsort(order)        # where each pair's row went
            yk = ys[back].reshape(N, k, D).astype(jnp.float32)
            if held is not None:
                # rows of no group are whatever the buffer held
                yk = jnp.where(held.reshape(N, k, 1), yk, 0.0)
            y = (yk * top_gates[..., None]).sum(axis=1)

        if self.shared_dim:
            with jax.named_scope("moe_shared"):
                dense = lambda feats, name: nn.Dense(  # noqa: E731
                    feats, use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32, name=name)
                y = y + swiglu(
                    dense, flat.astype(self.dtype), self.shared_dim, D,
                    ("shared_gate", "shared_up", "shared_down"),
                ).astype(jnp.float32)

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
