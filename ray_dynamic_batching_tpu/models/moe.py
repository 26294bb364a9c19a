"""Mixture-of-experts FFN block with expert parallelism over the ``ep`` axis.

Expert parallelism is absent from the reference (SURVEY.md §2.4 lists EP as
a from-scratch TPU design item). TPU-first design: GShard-style capacity-based
dispatch expressed as dense one-hot einsums — every shape static, so the
whole block jits once — with expert weights carrying a leading expert dim
sharded over the ``ep`` mesh axis. Under GSPMD the dispatched-token tensor is
sharding-constrained to ``ep``, which makes XLA insert the all_to_all pair
(dispatch/combine) over ICI rather than gathering all tokens everywhere.

Top-k routing (renormalized), per-row capacity C = ceil(k*T/E * capacity
factor); overflow tokens fall through the residual connection (standard
GShard behavior — bounded memory beats tail-token coverage on TPU). The
load-balance auxiliary loss is sown under ``intermediates/moe_aux_loss``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    """Sharding constraint that degrades to a no-op when no mesh is in
    context (single-device eager tests) or a dim isn't divisible by its
    mesh axis (e.g. batch-of-1 init under a dp>1 mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (RuntimeError, ValueError):
        return x


class MoEBlock(nn.Module):
    d_model: int
    mlp_dim: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    gated: bool = True  # SwiGLU experts (matches the dense MLP family)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:  # [B, T, D]
        B, T, D = x.shape
        E, F, k = self.num_experts, self.mlp_dim, self.top_k
        C = max(1, math.ceil(k * T / E * self.capacity_factor))

        router = nn.Dense(
            E, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
            name="router",
        )
        gates = jax.nn.softmax(router(x.astype(jnp.float32)), axis=-1)  # [B,T,E]

        # top-k gate selection, renormalized over the chosen experts
        top_gates, top_idx = jax.lax.top_k(gates, k)          # [B,T,k]
        top_gates = top_gates / jnp.maximum(
            top_gates.sum(axis=-1, keepdims=True), 1e-9
        )

        # position of each (token, choice) within its expert's capacity
        # buffer: running count of prior assignments to the same expert,
        # choice-major priority (all first choices beat all second choices)
        choice_onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # [B,T,k,E]
        flat = choice_onehot.transpose(0, 2, 1, 3).reshape(B, k * T, E)
        pos_flat = jnp.cumsum(flat, axis=1) - flat             # [B,kT,E]
        pos_in_expert = pos_flat.reshape(B, k, T, E).transpose(0, 2, 1, 3)
        within_cap = pos_in_expert < C                          # [B,T,k,E]

        # dispatch [B,T,E,C]: one-hot over capacity slots; overflow tokens
        # get an out-of-range index -> all-zero row (fall through residual)
        # (positions are whole-number floats — running counts — and
        # one_hot wants integer indices)
        cap_idx = jnp.where(within_cap, pos_in_expert, C).astype(jnp.int32)
        cap_onehot = jax.nn.one_hot(cap_idx, C, dtype=jnp.float32)  # [B,T,k,E,C]
        dispatch = jnp.einsum(
            "btke,btkec->btec", choice_onehot, cap_onehot
        )
        gate_per_expert = jnp.einsum("btke,btk->bte", choice_onehot, top_gates)
        combine = dispatch * gate_per_expert[..., None]

        # expert weights: leading expert dim sharded over ep, F over tp
        init = nn.initializers.lecun_normal()
        wi = self.param("wi", init, (E, D, F), jnp.float32)
        wo = self.param("wo", init, (E, F, D), jnp.float32)
        if self.gated:
            wg = self.param("wg", init, (E, D, F), jnp.float32)

        xe = jnp.einsum("btec,btd->becd", dispatch, x.astype(jnp.float32))
        # all_to_all: tokens move to their expert's devices
        xe = _constrain(xe, P("dp", "ep", None, None))
        xe = xe.astype(self.dtype)
        h = jnp.einsum("becd,edf->becf", xe, wi.astype(self.dtype))
        if self.gated:
            g = jnp.einsum("becd,edf->becf", xe, wg.astype(self.dtype))
            h = nn.silu(g) * h
        else:
            h = nn.gelu(h)
        ye = jnp.einsum("becf,efd->becd", h, wo.astype(self.dtype))
        ye = _constrain(ye, P("dp", "ep", None, None))
        y = jnp.einsum("btec,becd->btd", combine, ye.astype(jnp.float32))

        # load-balance aux loss (Shazeer/GShard): E * sum_e f_e * p_e
        density = choice_onehot[:, :, 0].mean(axis=1)   # top-1 assignment frac
        mean_gate = gates.mean(axis=1)                   # [B,E]
        aux = (density * mean_gate).sum(axis=-1).mean() * E
        self.sow("intermediates", "moe_aux_loss", aux)
        return y.astype(x.dtype)
