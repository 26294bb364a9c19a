"""Shared causal-decoder transformer with explicit functional KV cache.

New capability relative to the reference (which serves single-shot vision
models — SURVEY.md section 7 stage 7): autoregressive decode for the
BASELINE.json GPT-2/Llama configs. TPU-first design decisions:

- The KV cache is an explicit pytree argument returned updated from every
  step, so the engine can ``jit(..., donate_argnums=...)`` and XLA updates it
  in place in HBM (no realloc per token).
- Fixed-capacity caches + scatter-at-``lengths`` writes keep every shape
  static; continuous batching varies *contents*, never shapes, so one compiled
  program serves the whole decode stream.
- Attention flows through :mod:`ops.attention` (Pallas-fused on TPU).
- GQA (``num_kv_heads < num_heads``) shrinks cache HBM traffic — the decode
  bottleneck is HBM bandwidth, not MXU FLOPs.

One config-driven module covers both model families (learned-pos/LN/GeLU for
GPT-2; RoPE/RMSNorm/gated-SiLU/GQA for Llama).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_dynamic_batching_tpu.models.kv_state import (
    KVCache,
    LayerState,
    fit_head_dim,
    quantize_kv_rows,
    refuse_unsupported,
    ring_table,
    to_pool_rows,
)
from ray_dynamic_batching_tpu.ops import attention as attn_ops


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    d_model: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    mlp_dim: int
    max_seq_len: int = 2048
    pos: str = "rope"  # "rope" | "learned"
    norm: str = "rms"  # "rms" | "ln"
    gated_mlp: bool = True  # SwiGLU vs plain GeLU MLP
    use_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    num_experts: int = 0      # > 0 switches the MLP to a MoE block (ep axis)
    moe_top_k: int = 2
    # Nothing reads this: routing is dropless (models/moe.py) and has no
    # capacity. The field stays only because a benchmark rehearsal's
    # configuration file, which a model PR may not edit, passes it
    # (ROADMAP.md, Design debt).
    moe_capacity_factor: float = 1.25
    # The top-k gates rescaled to sum to one (the published
    # ``norm_topk_prob``); False uses them as the softmax gave them.
    moe_renormalize: bool = True
    # RMSNorm on the q and k PROJECTIONS (one scale over all heads' width),
    # before the split into heads and before RoPE (OLMoE).
    qk_norm: bool = False
    # ... over each HEAD's values instead, one scale of width ``head_dim``
    # shared by all heads (needs ``qk_norm``).
    qk_norm_per_head: bool = False
    # A head's width; 0 = ``d_model // num_heads`` (``__post_init__`` fills
    # it in, so every reader sees the true width).
    head_dim: int = 0
    # Sliding-window attention: a layer whose letter in ``layer_pattern``
    # is "L" attends the last ``sliding_window`` positions (itself
    # included), one whose letter is "G" its whole prefix. Layer i takes
    # letter ``i % len(layer_pattern)``, so a pattern serves any depth. 0:
    # every layer is full.
    sliding_window: int = 0
    layer_pattern: str = "L"
    # Rotary positions on the sliding layers only: a full layer then has
    # no positional signal at all.
    rope_sliding_only: bool = False
    # An expert model's first ``num_dense_layers`` layers carry a dense MLP
    # of width ``dense_mlp_dim``; ``mlp_dim`` stays ONE routed expert's.
    num_dense_layers: int = 0
    dense_mlp_dim: int = 0
    # The routing rule (models/moe.py::RoutingRule): "softmax" | "sigmoid"
    # scores, a learned bias added for the CHOICE only, the chosen gates
    # times ``moe_gate_scale`` (after ``moe_renormalize``).
    moe_scoring: str = "softmax"
    moe_selection_bias: bool = False
    moe_gate_scale: float = 1.0
    # One rank's share of an expert-parallel layer: the router scores all
    # ``num_experts``, this program holds and computes experts
    # [moe_first_expert, moe_first_expert + moe_held_experts). 0 = all.
    moe_first_expert: int = 0
    moe_held_experts: int = 0
    # Shared experts beside the routed ones: one dense SwiGLU of width
    # ``moe_shared_experts * mlp_dim`` on every token.
    moe_shared_experts: int = 0
    # A learned indexer (ops/sparse_attention.py): ``index_heads`` heads of
    # ``index_head_dim`` score every cached position against ONE index key a
    # position, and a query attends only its ``index_topk`` best positions
    # (all of them while there are no more). 0: no indexer, every layer
    # attends its whole prefix or window. Beside k/v pairs its queries come
    # from the layer's normed input and its whole head is rotated; a LATENT
    # layer's is DeepSeek-V3.2's (``models/latent.py::Indexer``: queries
    # from the q latent, a LayerNorm on the key, rotary over ``rope_dim``).
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    # Every RMSNorm's epsilon (the published ``rms_norm_eps``).
    rms_eps: float = 1e-5
    # A VALUE head's width where it is not the key's (0: ``head_dim``).
    v_head_dim: int = 0
    # A sliding layer's own KV head count and rotary base (0: the full
    # layers' ``num_kv_heads`` / ``rope_theta``).
    sliding_kv_heads: int = 0
    sliding_rope_theta: float = 0.0
    # Rotary positions over the first ``rope_dim`` values of a head only,
    # the rest passing unrotated (0: the whole head).
    rope_dim: int = 0
    # A sliding layer's softmax carries a learned SINK, one scalar a query
    # head: it takes probability mass and adds no value.
    sliding_sink: bool = False
    # Values times this, before they are cached.
    value_scale: float = 1.0
    # LATENT attention (models/latent.py): q through a ``q_lora_rank``
    # bottleneck, k and v expanded from ONE ``kv_lora_rank``-wide latent a
    # position; a head is ``head_dim`` = a part without positions + the
    # LAST ``rope_dim`` values, rotary, whose key is one a position shared
    # by all heads; values ``v_head_dim``. The paged cache holds the latent
    # and that key alone (``PagedKVCache.latent``). 0: k/v pairs.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    # YaRN on the rotary frequencies (a latent layer's): positions
    # stretched ``rope_yarn_factor`` times over ``rope_yarn_original``,
    # blended by dimension between ``beta_fast`` and ``beta_slow`` turns;
    # the softmax scale times ``mscale(factor, mscale_all_dim)^2``. 1: none.
    rope_yarn_factor: float = 1.0
    rope_yarn_original: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    # A residual path of ``hc_mult`` STREAMS (models/hyper_connections.py):
    # every sublayer reads one mix of them and writes into all, through
    # maps computed from the streams; the stream-to-stream map is
    # Sinkhorn-normalised, ``hc_sinkhorn_iters`` rounds with ``hc_eps`` in
    # the denominators, its exponent clamped to ``hc_res_clamp``. 1: the
    # one stream, ``x + F(norm(x))``.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # A layer whose letter in ``layer_pattern`` is "C" mixes its sequence
    # with a gated SHORT CONVOLUTION (models/short_conv.py), not attention:
    # depthwise, causal, ``conv_kernel`` taps. It keeps no KV: its state is
    # the last ``conv_kernel - 1`` inputs of the taps, a fixed size a slot
    # (``PagedKVCache.conv_state``), and the paged pool holds the OTHER
    # layers alone. 0: no layer is one.
    conv_kernel: int = 0
    # A layer whose letter is "H" is a HYBRID: a state-space mixer
    # (models/ssm.py: Mamba-2, ``ssm_heads`` heads of ``ssm_head_dim``
    # channels, each a MATRIX state ``[ssm_head_dim, ssm_state]``, ``B`` and
    # ``C`` shared by the heads of one of ``ssm_groups`` groups, blocks of
    # ``ssm_chunk`` positions in a chunk's scan) AND attention, in parallel
    # on the same normed input, their outputs summed. Its conv (depthwise,
    # ``conv_kernel`` taps, a bias where ``conv_bias``, SiLU) is
    # ``conv_width`` channels wide. Every such layer holds pages and a state
    # a slot (``PagedKVCache.ssm_state`` / ``conv_state``). 0: no layer is.
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    conv_bias: bool = False
    # The fixed scalars a model multiplies its activations by (Falcon-H1's
    # twelve), each applied where it is published; 1: none.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    # ... on the five zones [z | x | B | C | dt] of the mixer's first product
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5
    # ... on the MLP's gate (before its SiLU) and on its output
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)

    def __post_init__(self):
        object.__setattr__(self, "hc_res_clamp", tuple(self.hc_res_clamp))
        object.__setattr__(self, "ssm_multipliers",
                           tuple(self.ssm_multipliers))
        object.__setattr__(self, "mlp_multipliers",
                           tuple(self.mlp_multipliers))
        if not self.head_dim:
            object.__setattr__(
                self, "head_dim", self.d_model // self.num_heads)
        if not self.v_head_dim:
            object.__setattr__(self, "v_head_dim", self.head_dim)
        if (self.kv_by_kind or self.sliding_rope_theta
                or self.sliding_sink) and not (
                    self.sliding_window and "L" in self.layer_pattern):
            raise ValueError(
                "sliding_kv_heads, sliding_rope_theta and sliding_sink are "
                "a sliding layer's, and a v_head_dim of its own needs "
                "state by layer kind, a ring for the sliding layers: no "
                "layer slides")
        if self.latent and not (
                self.q_lora_rank and 0 < self.rope_dim < self.head_dim
                and self.pos == "rope" and self.norm == "rms"
                and self.num_kv_heads == self.num_heads):
            raise ValueError(
                "kv_lora_rank: a latent layer needs q_lora_rank, a head "
                "split by rope_dim, rotary positions, RMSNorm and as many "
                "KV heads as heads")
        if self.latent and (self.sliding_window or self.qk_norm
                            or self.use_bias):
            raise ValueError(
                "kv_lora_rank: a latent layer attends its whole prefix or "
                "an indexer's selection of it, without a window, a q/k "
                "norm per head or biases")
        if self.latent and self.index_head_dim and (
                self.rope_dim > self.index_head_dim):
            raise ValueError(
                f"index_head_dim {self.index_head_dim}: a latent layer's "
                f"indexer rotates the first rope_dim {self.rope_dim} values "
                "of an index head")
        if (self.rope_yarn_factor > 1.0) and not (
                self.latent and self.rope_yarn_original):
            raise ValueError("rope_yarn_factor is a latent layer's and "
                             "needs rope_yarn_original")
        if self.rope_dim % 2 or self.rope_dim > self.head_dim:
            raise ValueError(f"rope_dim {self.rope_dim} is not an even "
                             f"part of a head of {self.head_dim}")
        if self.sliding_window and set(self.layer_pattern) - set("LG"):
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: letters are L "
                "(sliding) and G (full)")
        if self.ssm_state:
            if (set(self.layer_pattern) != {"H"} or self.conv_kernel < 2
                    or not (self.ssm_heads and self.ssm_head_dim)
                    or self.ssm_heads % self.ssm_groups
                    or len(self.ssm_multipliers) != 5
                    or len(self.mlp_multipliers) != 2):
                raise ValueError(
                    "ssm_state: a hybrid layer (H, every layer of its "
                    "model) needs ssm_heads of ssm_head_dim in whole "
                    "ssm_groups, its conv's taps (conv_kernel >= 2), five "
                    "ssm_multipliers and two mlp_multipliers")
            if (self.sliding_window or self.index_topk or self.latent
                    or self.hc_mult > 1 or self.num_experts or self.qk_norm
                    or self.norm != "rms" or self.pos != "rope"
                    or not self.gated_mlp or self.use_bias
                    or self.v_head_dim != self.head_dim):
                raise ValueError(
                    "ssm_state: a hybrid layer is a state-space mixer "
                    "beside full GQA attention of k/v pairs over a dense "
                    "SwiGLU, rotary, RMSNorm, no biases; a window, an "
                    "indexer, a latent cache, residual streams, experts "
                    "and a q/k norm are not built beside it")
        elif "H" in self.layer_pattern:
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: a hybrid layer (H) "
                "needs its state's sizes (ssm_state)")
        elif ("C" in self.layer_pattern) != (self.conv_kernel >= 2):
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r} with conv_kernel "
                f"{self.conv_kernel}: a conv layer (C) needs its taps "
                "(conv_kernel >= 2), and taps need a layer")
        if self.conv_kernel and not self.ssm_state and (
                self.sliding_window or self.index_topk or self.latent
                or self.hc_mult > 1 or set(self.layer_pattern) - set("CG")
                or self.norm != "rms"):
            raise ValueError(
                "conv_kernel: conv layers (C) stand beside full attention "
                "layers (G) of k/v pairs under RMSNorm; a window, an "
                "indexer, a latent cache and residual streams are not "
                "built beside them")
        if self.moe_first_expert + self.held_experts > self.num_experts:
            raise ValueError(
                f"held experts [{self.moe_first_expert}, "
                f"{self.moe_first_expert + self.held_experts}) are not "
                f"among {self.num_experts}")
        if self.index_topk and not (self.index_heads and self.index_head_dim):
            raise ValueError("index_topk needs index_heads and "
                             "index_head_dim")
        if self.index_topk and self.sliding_window:
            raise ValueError("a selecting layer attends its best positions "
                             "of the whole prefix: no sliding_window")

    @property
    def held_experts(self) -> int:
        """Experts this program holds: ``moe_held_experts``, or all."""
        return self.moe_held_experts or self.num_experts

    @property
    def kv_by_kind(self) -> bool:
        """State by layer kind (``PagedKVCache``): the full layers page in
        a pool of their own head count, the sliding layers keep a ring of
        their window a slot. It is what a model whose kinds differ in head
        count, or whose values are narrower than its keys, cannot do
        without: one pool holds one head count and one row width."""
        return not self.latent and bool(
            self.sliding_kv_heads or self.v_head_dim != self.head_dim)

    @property
    def latent(self) -> bool:
        """Every layer's attention is latent (``kv_lora_rank``): the paged
        cache is a pool of rows with no head axis and no k/v pair."""
        return self.kv_lora_rank > 0

    def _slides(self, i: int) -> bool:
        return bool(self.sliding_window) and (
            self.layer_pattern[i % len(self.layer_pattern)] == "L")

    def _convs(self, i: int) -> bool:
        return bool(self.conv_kernel) and (
            self.layer_pattern[i % len(self.layer_pattern)] == "C")

    @property
    def conv_layers(self) -> int:
        """Layers that hold a conv state a slot
        (``PagedKVCache.conv_state``): those whose mixer is a short
        convolution (and no pages), or every layer of a hybrid model."""
        if self.ssm_state:
            return self.num_layers
        return sum(1 for i in range(self.num_layers) if self._convs(i))

    @property
    def conv_width(self) -> int:
        """Channels of the depthwise conv, the last axis of
        ``PagedKVCache.conv_state``: a gated short convolution's is the
        residual's; a state-space mixer's is its ``[x | B | C]``, ``d_ssm +
        2 * groups * state`` (Falcon-H1's 5,120 is its ``d_model`` by
        coincidence)."""
        if self.ssm_state:
            return self.d_ssm + 2 * self.ssm_groups * self.ssm_state
        return self.d_model

    @property
    def d_ssm(self) -> int:
        """A state-space mixer's inner width, heads x head."""
        return self.ssm_heads * self.ssm_head_dim

    def layer_kind(self, i: int) -> "LayerKind":
        """What layer ``i`` is: THE place a layer asks."""
        slides = self._slides(i)
        sparse = self.num_experts > 0 and i >= self.num_dense_layers
        by_kind = {}
        if self.kv_by_kind:
            # its place among the layers of its own kind: its pool's layer
            by_kind = dict(ring=slides, pool_layer=sum(
                1 for j in range(i) if self._slides(j) == slides))
        elif self.ssm_state:
            # every layer holds pages AND a state: its own index in both
            by_kind = dict(ssm=True, pool_layer=i)
        elif self.conv_kernel:
            # likewise: a conv layer's place in the state plane, an
            # attention layer's among the layers that hold pages
            conv = self._convs(i)
            by_kind = dict(conv=conv, pool_layer=sum(
                1 for j in range(i) if self._convs(j) == conv))
        return LayerKind(
            window=self.sliding_window if slides else 0,
            rope=self.pos == "rope" and (
                slides or not self.rope_sliding_only),
            sparse=sparse,
            mlp_dim=(self.mlp_dim if sparse or not self.num_experts
                     else self.dense_mlp_dim),
            select=self.index_topk,
            kv_heads=self.sliding_kv_heads if slides else 0,
            rope_theta=self.sliding_rope_theta if slides else 0.0,
            sink=slides and self.sliding_sink,
            latent=self.latent,
            **by_kind,
        )

    def layers_of(self, ring: bool) -> int:
        """How many layers keep a ring (``ring``) or pages (not)."""
        return sum(1 for i in range(self.num_layers)
                   if self._slides(i) == ring)

    @property
    def pool_layers(self) -> int:
        """Layers of the paged pool where it is ONE pool: every layer but
        the conv layers (a hybrid layer holds pages beside its state)."""
        return self.num_layers - sum(
            1 for i in range(self.num_layers) if self._convs(i))


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One layer's kind (``DecoderConfig.layer_kind``)."""

    window: int     # positions it attends back, itself included; 0 = all
    rope: bool      # rotary positions on q and k
    sparse: bool    # an expert MLP (models/moe.py), else a dense one
    mlp_dim: int    # the dense MLP's width, or ONE routed expert's
    select: int = 0  # positions its indexer keeps a query; 0 = no indexer
    # Its own KV head count and rotary base, where they are not the
    # configuration's ``num_kv_heads`` / ``rope_theta`` (0: they are).
    kv_heads: int = 0
    rope_theta: float = 0.0
    sink: bool = False       # a learned sink in its softmax
    # Under ``kv_by_kind``: whether its state is a ring of its window (else
    # pages), and its layer among its pool's; -1: the model has ONE pool
    # and the layer's own index is its place there.
    ring: bool = False
    pool_layer: int = -1
    # Its attention is latent: its state is ONE row a position
    # (``PagedKVCache.latent``), no k/v pair.
    latent: bool = False
    # Its mixer is a gated short convolution, not attention: its state is
    # the taps' last inputs a slot (``PagedKVCache.conv_state``, layer
    # ``pool_layer`` of it), no pages.
    conv: bool = False
    # A hybrid: a state-space mixer beside its attention, on the same
    # normed input. It holds pages AND a state a slot
    # (``PagedKVCache.ssm_state`` / ``conv_state``), layer ``pool_layer`` of
    # each.
    ssm: bool = False


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0,
    rope_dim: int = 0,
) -> jax.Array:
    """Rotary embedding. x [B, T, N, H], positions [B, T]. ``rope_dim``:
    over the head's first ``rope_dim`` values only (rotate-half within
    them), the rest unrotated; 0: the whole head."""
    if rope_dim and rope_dim < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rope_dim], positions, theta),
             x[..., rope_dim:]], axis=-1)
    H = x.shape[-1]
    half = H // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(x.dtype)


def swiglu(dense, y: jax.Array, width: int, d_model: int,
           names: Tuple[str, str, str] = ("mlp_gate", "mlp_up", "mlp_down"),
           multipliers: Tuple[float, float] = (1.0, 1.0),
           ) -> jax.Array:
    """The dense gated MLP, ``(silu(y Wg) * (y Wu)) Wd``, from a layer's
    ``dense(features, name)`` factory: a dense layer's MLP and an expert
    layer's shared expert are this one code. ``multipliers``: a model's
    fixed scalars on the gate (before its SiLU) and on the output."""
    gate = dense(width, names[0])(y)
    if multipliers[0] != 1.0:
        gate = gate * multipliers[0]
    up = dense(width, names[1])(y)
    out = dense(d_model, names[2])(nn.silu(gate) * up)
    return out if multipliers[1] == 1.0 else out * multipliers[1]


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    dtype: Any = jnp.bfloat16

    def _norm(self, name: str):
        if self.cfg.norm == "rms":
            return RMSNorm(name=name, eps=self.cfg.rms_eps)
        return nn.LayerNorm(dtype=jnp.float32, name=name)

    @nn.compact
    def __call__(
        self,
        x: jax.Array,               # [B, T, D]
        positions: jax.Array,       # [B, T]
        mask: Optional[jax.Array],  # [B, 1, T, S_attended] True = attend
        cache_kv: Optional[LayerState] = None,  # stacked pools, [L, ...]
        token_mask: Optional[jax.Array] = None,  # [B, T] (no-cache path)
        layer_idx: int = 0,
        write_start: Optional[jax.Array] = None,  # scalar: chunk write offset
        scatter_writes: bool = False,  # per-row writes at ``positions``
        page_table: Optional[jax.Array] = None,  # [B, NP]: paged decode
        kv_lengths: Optional[jax.Array] = None,  # [B] paged validity bound
        state_lens: Optional[jax.Array] = None,  # [B] a conv layer's real rows
    ) -> Tuple[jax.Array, Optional[LayerState]]:
        """(x, the layer's state updated, or None without a cache)."""
        cfg = self.cfg
        kind = cfg.layer_kind(layer_idx)
        if cache_kv is not None:
            refuse_unsupported(cfg, error=NotImplementedError,
                               slab=page_table is None)
        dense = lambda feats, name, axis=-1: nn.DenseGeneral(  # noqa: E731
            feats,
            axis=axis,
            use_bias=cfg.use_bias,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name=name,
        )
        # A residual path of STREAMS: x is [B, T, n, D], each sublayer
        # reads one mix of it and writes into all (a model with ``hc_mult``
        # alone loads the module).
        hc = None
        if cfg.hc_mult > 1:
            from ray_dynamic_batching_tpu.models import hyper_connections

            hc = lambda name: hyper_connections.HyperConnection(  # noqa: E731
                n=cfg.hc_mult, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                rms_eps=cfg.rms_eps, clamp=cfg.hc_res_clamp,
                dtype=self.dtype, name=name)
        h, maps = hc("attn_hc")(x) if hc else (x, None)
        y = self._norm("attn_norm")(h).astype(self.dtype)
        ssm_out = None
        if kind.ssm:
            # a state-space mixer beside the attention, on the same normed
            # rows (its model alone loads the module)
            from ray_dynamic_batching_tpu.models import ssm

            ssm_out, cache_kv = ssm.mixer(
                self, dense, kind, y, cache_kv, state_lens)
            if cfg.attention_in_multiplier != 1.0:
                y = y * cfg.attention_in_multiplier
        if kind.conv:
            # a gated short convolution (its model alone loads the module)
            from ray_dynamic_batching_tpu.models import short_conv

            attn_out, new_cache = short_conv.mixer(
                self, dense, kind, y, cache_kv, state_lens)
        elif kind.latent:
            attn_out, new_cache = self._latent_attention(
                kind, y, positions, mask, cache_kv, token_mask, layer_idx,
                page_table, kv_lengths)
        else:
            attn_out, new_cache = self._kv_attention(
                dense, kind, y, positions, mask, cache_kv, token_mask,
                layer_idx, write_start, scatter_writes, page_table,
                kv_lengths)
        if not kind.conv:
            attn_out = dense(cfg.d_model, "o", axis=(-2, -1))(attn_out)
        if ssm_out is not None:
            if cfg.attention_out_multiplier != 1.0:
                attn_out = attn_out * cfg.attention_out_multiplier
            attn_out = attn_out + ssm_out
        x = hyper_connections.mix(x, attn_out, maps) if hc else x + attn_out

        h, maps = hc("mlp_hc")(x) if hc else (x, None)
        y = self._norm("mlp_norm")(h).astype(self.dtype)
        if kind.sparse:
            from ray_dynamic_batching_tpu.models.moe import (
                MoEBlock,
                routing_rule,
            )

            y = MoEBlock(
                d_model=cfg.d_model,
                mlp_dim=cfg.mlp_dim,
                num_experts=cfg.num_experts,
                top_k=cfg.moe_top_k,
                rule=routing_rule(cfg),
                first_expert=cfg.moe_first_expert,
                held_experts=cfg.held_experts,
                shared_dim=cfg.moe_shared_experts * cfg.mlp_dim,
                gated=cfg.gated_mlp,
                dtype=self.dtype,
                name="moe",
            )(y)
        elif cfg.gated_mlp:
            y = swiglu(dense, y, kind.mlp_dim, cfg.d_model,
                       multipliers=cfg.mlp_multipliers)
        else:
            y = nn.gelu(dense(kind.mlp_dim, "mlp_up")(y))
            y = dense(cfg.d_model, "mlp_down")(y)
        x = hyper_connections.mix(x, y, maps) if hc else x + y
        return x, new_cache

    def _latent_attention(self, kind, y, positions, mask, cache_kv,
                          token_mask, layer_idx, page_table, kv_lengths):
        """A latent layer's heads' outputs and its state, the pool updated
        (``models/latent.py``; its model alone loads it). A selecting
        layer hands on its indexer, which ``latent.attention`` gives the q
        latent, and its index keys' plane."""
        from ray_dynamic_batching_tpu.models import latent

        cfg = self.cfg
        allowed = None
        if cache_kv is None:
            B, T = positions.shape
            allowed = (prefill_mask(token_mask) if token_mask is not None
                       else mask if mask is not None
                       else jnp.ones((B, 1, T, T), bool))
        select = {}
        if kind.select:
            if cache_kv is not None and cache_kv.index_k is None:
                raise NotImplementedError(
                    "a selecting layer's index keys live in the paged pool "
                    "(PagedKVCache.index_k): this cache has none")
            select = dict(
                indexer=latent.Indexer(cfg, self.dtype, kind.select),
                index_pool=None if cache_kv is None else cache_kv.index_k)
        out, pool, index_pool = latent.attention(
            cfg, self.dtype,
            lambda name: RMSNorm(name=name, eps=cfg.rms_eps),
            y, positions, pool=None if cache_kv is None else cache_kv.latent,
            layer=layer_idx, page_table=page_table, kv_lengths=kv_lengths,
            allowed=allowed, **select)
        if pool is None:
            return out, None
        return out, cache_kv._replace(latent=pool, index_k=index_pool)

    def _kv_attention(self, dense, kind, y, positions, mask, cache_kv,
                      token_mask, layer_idx, write_start, scatter_writes,
                      page_table, kv_lengths):
        """A layer of k/v pairs: the heads' outputs and its state updated
        (or None), a selecting layer's index keys among it."""
        cfg = self.cfg
        q = dense((cfg.num_heads, cfg.head_dim), "q")(y)
        kv_heads = kind.kv_heads or cfg.num_kv_heads
        k = dense((kv_heads, cfg.head_dim), "k")(y)
        if cfg.key_multiplier != 1.0:
            k = k * cfg.key_multiplier
        v = dense((kv_heads, cfg.v_head_dim), "v")(y)
        if cfg.value_scale != 1.0:
            v = v * cfg.value_scale
        # What only a model with state by layer kind has: a sink, a value
        # head narrower than a key's. Such a layer's attention is
        # ``ops/kind_attention.py``'s wherever the paged kernel is not.
        sink = (self.param("sink", nn.initializers.zeros, (cfg.num_heads,),
                           jnp.float32) if kind.sink else None)
        odd = kind.sink or cfg.v_head_dim != cfg.head_dim
        # This layer's place in its pool.
        li = layer_idx if kind.pool_layer < 0 else kind.pool_layer
        qk_norm = lambda name: RMSNorm(  # noqa: E731
            name=name, eps=cfg.rms_eps)
        if cfg.qk_norm and cfg.qk_norm_per_head:
            q = qk_norm("q_norm")(q)
            k = qk_norm("k_norm")(k)
        elif cfg.qk_norm:
            q = qk_norm("q_norm")(
                q.reshape(*q.shape[:2], -1)).reshape(q.shape)
            k = qk_norm("k_norm")(
                k.reshape(*k.shape[:2], -1)).reshape(k.shape)
        if kind.rope:
            rope = (kind.rope_theta or cfg.rope_theta,) + (
                (cfg.rope_dim,) if cfg.rope_dim else ())
            q = apply_rope(q, positions, *rope)
            k = apply_rope(k, positions, *rope)
        select = None
        if kind.select:
            # Imported here: a model without an indexer never loads it.
            from ray_dynamic_batching_tpu.ops import sparse_attention

            with jax.named_scope("sparse_index"):
                q_i = dense((cfg.index_heads, cfg.index_head_dim),
                            "index_q")(y)
                k_i = dense((1, cfg.index_head_dim), "index_k")(y)
                w_i = dense(cfg.index_heads, "index_w")(y)
                if kind.rope:
                    q_i = apply_rope(q_i, positions, cfg.rope_theta)
                    k_i = apply_rope(k_i, positions, cfg.rope_theta)
                k_i = k_i[:, :, 0]                       # ONE key a position
            if cache_kv is not None and cache_kv.index_k is None:
                raise NotImplementedError(
                    "a selecting layer's index keys live in the paged pool "
                    "(PagedKVCache.index_k): the slab cache has none")
        if kind.window and mask is not None:
            # An explicit mask (the slab cache's, or a whole prompt's)
            # indexes keys by their position: a sliding layer cuts its
            # lower edge.
            mask = mask & sliding_edge(
                positions, mask.shape[-1], kind.window)[:, None]

        if cache_kv is not None:
            # The layer scatters into the FULL stacked [L, B, S, K, H] cache
            # at its own layer index and hands the whole buffer to the next
            # layer. Never slice-out/re-stack per layer: rebuilding the
            # stacked array every decode step forces XLA to materialize a
            # fresh multi-GB copy per token (measured 15 ms/substep for
            # GPT-2-medium at 32 slots vs ~2 ms with in-place updates).
            # The paged READ keeps the same contract (below): the pools
            # are passed whole and the layer is an index — no read makes
            # an array the size of a layer of the pool.
            k_full, v_full = cache_kv.k, cache_kv.v
            ks_full, vs_full = cache_kv.k_scale, cache_kv.v_scale
            index_pool = cache_kv.index_k
            quantized = ks_full is not None
            if quantized:
                k_w, k_s = quantize_kv_rows(k)
                v_w, v_s = quantize_kv_rows(v)
            else:
                k_w, v_w = k, v
            B, T = positions.shape
            if page_table is not None:
                # Paged writes: the cache arrays are page POOLS
                # [L, P, ps, K, H]; each token's logical position maps
                # through the slot's page-table row to a physical
                # (page, offset). Two patterns share the rule — plain
                # decode (T == 1, positions = lengths) and the
                # speculative-verify window (``scatter_writes``: T ==
                # k+1 per-row positions starting at each slot's own
                # length, landing in the round's scratch pages).
                # Unallocated entries carry the sentinel P, and
                # logically-overflowing rows are steered to it too, so
                # mode="drop" voids exactly the writes the slab path's
                # out-of-bounds scatter voids.
                if T != 1 and not scatter_writes:
                    raise NotImplementedError(
                        "paged cache writes support single-token decode "
                        "and per-row scatter windows (spec verify) only; "
                        "prefill runs on row caches and commits through "
                        "the engine's page scatter"
                    )
                P = k_full.shape[1]
                ps = k_full.shape[2]
                # Pool rows are lane-padded (pool_head_dim), or hold
                # several heads side by side (pool_heads_per_row).
                k_w = to_pool_rows(k_w, k_full)
                v_w = to_pool_rows(v_w, v_full)
                n_entries = page_table.shape[1]
                idx = positions  # [B, T]
                rows = jnp.arange(B)[:, None]
                pidx = jnp.minimum(idx // ps, n_entries - 1)
                pid = jnp.where(
                    idx < n_entries * ps, page_table[rows, pidx], P
                )
                off = idx % ps

                def write(pool, x):
                    return pool.at[li, pid, off].set(x, mode="drop")
            elif scatter_writes:
                # Batched multi-token writes at PER-ROW positions (the
                # speculative-verify path: each slot's window starts at its
                # own length). mode="drop" voids rows steered out of
                # bounds, exactly like the single-token decode scatter.
                rows = jnp.arange(B)[:, None]

                def write(pool, x):
                    return pool.at[li, rows, positions].set(x, mode="drop")
            elif T == 1:
                # Decode: scatter this token's k/v at its row position.
                # mode="drop" makes a full row's out-of-bounds write a no-op
                # instead of clamping onto (and corrupting) the last slot.
                idx = positions[:, 0]
                rows = jnp.arange(B)

                def write(pool, x):
                    return pool.at[li, rows, idx].set(x[:, 0], mode="drop")
            else:
                # Prefill: contiguous write at offset 0, or — for chunked
                # prefill of long prompts — at a TRACED start position, so
                # one compiled program serves every chunk of the prompt
                # (dynamic start, static chunk shape).
                start = write_start if write_start is not None else 0

                def write(pool, x):
                    return jax.lax.dynamic_update_slice(
                        pool, x[None], (li, 0, start) + (0,) * (pool.ndim - 3))
            # ONE rule a pattern for every plane of the layer's state: the
            # codes, their scales and a selecting layer's index key (written
            # before it is scored, as k and v are) land at the SAME indices.
            k_full, v_full = write(k_full, k_w), write(v_full, v_w)
            if quantized:
                ks_full, vs_full = write(ks_full, k_s), write(vs_full, v_s)
            if kind.select:
                index_pool = write(index_pool, fit_head_dim(
                    k_i, index_pool.shape[-1]).astype(index_pool.dtype))
                select = sparse_attention.Selection(
                    q_i, w_i, index_pool, kind.select)
            # Quantized caches hand CODES + scales to the dispatcher:
            # the decode kernel scans the 1-byte codes directly (the
            # bandwidth win); non-kernel paths dequantize there.
            scale_kwargs = {}
            if quantized:
                scale_kwargs = {"k_scale": ks_full[li],
                                "v_scale": vs_full[li]}
            new_cache = cache_kv._replace(
                k=k_full, v=v_full, k_scale=ks_full, v_scale=vs_full,
                index_k=index_pool)
            if page_table is not None:
                # Paged read: the STACKED pools go to the dispatcher
                # whole and this layer is an index into them — in the
                # Pallas paged kernel's block map, or in the fallback's
                # one (layer, page) gather + the shared decode mask: one
                # mask rule, token-exact either way. Slicing
                # ``k_full[li]`` here would make XLA materialise
                # a layer of the pool per layer per substep in front of
                # the kernel (a Mosaic operand is a buffer).
                kv = (k_full, v_full)
                scale_kwargs.update(page_table=page_table,
                                    kv_lengths=kv_lengths, layer=li,
                                    sliding=kind.window)
                if select is not None:
                    scale_kwargs["select"] = select
                if odd:
                    scale_kwargs.update(sink=sink, v_dim=cfg.v_head_dim)
                if k_full.shape[3] != kv_heads:
                    scale_kwargs["heads_per_row"] = (
                        kv_heads // k_full.shape[3])
            else:
                kv = (k_full[li], v_full[li])
            attn_out = attn_ops.dot_product_attention(
                q, *kv, mask=mask, **scale_kwargs)
        elif odd:
            # Whole-sequence attention with a sink or a narrower value
            # head: plain XLA under the causal (and valid-token) mask.
            from ray_dynamic_batching_tpu.ops import kind_attention

            B, T = positions.shape
            allowed = (prefill_mask(token_mask) if token_mask is not None
                       else mask if mask is not None
                       else jnp.ones((B, 1, T, T), bool))
            if kind.window and mask is None:
                allowed = allowed & sliding_edge(
                    positions, T, kind.window)[:, None]
            attn_out = kind_attention.dense(q, k, v, allowed, sink)
            new_cache = None
        elif kind.select:
            # A selecting layer's whole-sequence attention: the causal
            # (and valid-token) mask, of which each query keeps its best.
            B, T = positions.shape
            if token_mask is not None:
                allowed = prefill_mask(token_mask)
            elif mask is not None:
                allowed = mask
            else:
                allowed = jnp.ones((B, 1, T, T), bool)
            attn_out = attn_ops.dot_product_attention(
                q, k, v, mask=sparse_attention.select_mask(
                    q_i, w_i, k_i,
                    jnp.broadcast_to(allowed, (B, 1, T, T)), kind.select))
            new_cache = None
        elif token_mask is not None and kind.window:
            # A sliding layer's whole-sequence attention: the causal
            # kernel under the window's lower edge (no ring form).
            attn_out = attn_ops.dot_product_attention(
                q, k, v, causal=True,
                mask=token_mask[:, None, None, :].astype(bool)
                & sliding_edge(positions, k.shape[1], kind.window)[:, None])
            new_cache = None
        elif token_mask is not None:
            # Full-sequence self-attention: routes through ring attention
            # over the sp mesh axis under a sequence_parallel context.
            attn_out = attn_ops.self_attention(q, k, v, token_mask, causal=True)
            new_cache = None
        else:
            attn_out = attn_ops.dot_product_attention(q, k, v, mask=mask)
            new_cache = None

        return attn_out, new_cache


class DecoderModule(nn.Module):
    cfg: DecoderConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,          # [B, T]
        positions: jax.Array,       # [B, T]
        mask: Optional[jax.Array],  # [B, 1, T, S]
        cache: Optional[KVCache] = None,
        token_mask: Optional[jax.Array] = None,  # [B, T] (no-cache path)
        write_start: Optional[jax.Array] = None,  # scalar chunk offset
        scatter_writes: bool = False,  # per-row multi-token cache writes
        page_table: Optional[jax.Array] = None,  # paged decode (T == 1)
        kv_lengths: Optional[jax.Array] = None,
        ring_tables: Optional[jax.Array] = None,  # [B, NP]: rows' rings
        state_lens: Optional[jax.Array] = None,  # [B]: rows' real tokens
        head_rows: Optional[jax.Array] = None,  # [B]: the head reads these
    ) -> Tuple[jax.Array, Optional[KVCache]]:
        """(logits ``[B, T, V]``, the cache updated). ``head_rows``: the
        ONE row a sequence whose logits the caller wants; the final norm and
        the head then read those rows alone and the logits are ``[B, 1,
        V]``."""
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="tok_embed",
        )
        x = embed(tokens)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if cfg.pos == "learned":
            pos_embed = nn.Embed(
                cfg.max_seq_len,
                cfg.d_model,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                name="pos_embed",
            )
            x = x + pos_embed(positions)

        if cfg.hc_mult > 1:
            # every stream starts as the embedding row
            x = jnp.broadcast_to(
                x[:, :, None, :], x.shape[:2] + (cfg.hc_mult, cfg.d_model))

        # Each layer is handed its kind's share of the state and hands it
        # back updated (``models/kv_state.py``): where state is by layer
        # kind, a sliding layer its ring and its table (a row's ring table
        # is its slot's: the caller's for a chunk's rows, slot b's for row
        # b of a decode step).
        ring_pages = getattr(cache, "ring_pages", 0)
        if ring_pages and ring_tables is None:
            ring_tables = ring_table(
                jnp.arange(tokens.shape[0], dtype=jnp.int32),
                ring_pages, page_table.shape[1])
        # A state a slot (a conv layer's, a hybrid layer's) moves on by its
        # row's REAL tokens (a chunk's unpadded rows; 1 or 0 for a decode
        # row that advances or not).
        conv = {"state_lens": state_lens} if cfg.conv_kernel else {}
        for i in range(cfg.num_layers):
            kind = cfg.layer_kind(i)
            x, updated = DecoderLayer(
                cfg, dtype=self.dtype, name=f"layer{i}")(
                x, positions, mask,
                None if cache is None else cache.layer_state(kind),
                token_mask, layer_idx=i,
                write_start=write_start, scatter_writes=scatter_writes,
                page_table=(ring_tables if ring_pages and kind.ring
                            else page_table),
                kv_lengths=kv_lengths, **conv,
            )
            if updated is not None:
                cache = cache.with_layer_state(kind, updated)

        if head_rows is not None:
            x = jnp.take_along_axis(x, head_rows.reshape(
                (-1,) + (1,) * (x.ndim - 1)), axis=1)
        if cfg.hc_mult > 1:
            # ... and the streams' sum is what the head reads
            x = x.astype(jnp.float32).sum(axis=2)
        if cfg.norm == "rms":
            x = RMSNorm(name="final_norm", eps=cfg.rms_eps)(x)
        else:
            x = nn.LayerNorm(dtype=jnp.float32, name="final_norm")(x)

        if cfg.tie_embeddings:
            logits = embed.attend(x.astype(jnp.float32))
        else:
            logits = nn.Dense(
                cfg.vocab_size,
                use_bias=False,
                dtype=jnp.float32,
                param_dtype=jnp.float32,
                name="lm_head",
            )(x)
        if cfg.lm_head_multiplier != 1.0:
            logits = logits * cfg.lm_head_multiplier

        return logits, cache


def prefill_mask(attn_mask: jax.Array) -> jax.Array:
    """Causal mask limited to valid tokens. attn_mask [B, T] -> [B, 1, T, T]."""
    T = attn_mask.shape[1]
    causal = jnp.tril(jnp.ones((T, T), dtype=bool))
    valid = attn_mask[:, None, None, :].astype(bool)
    return causal[None, None, :, :] & valid


def decode_mask(lengths: jax.Array, capacity: int) -> jax.Array:
    """Attend to positions [0, lengths] inclusive. lengths [B] -> [B,1,1,S]."""
    pos = jnp.arange(capacity)[None, None, None, :]
    return pos <= lengths[:, None, None, None]


def sliding_edge(positions: jax.Array, capacity: int,
                 sliding: int) -> jax.Array:
    """The LOWER edge of a sliding layer: the query at position i attends
    key j only if ``i - j < sliding`` (``sliding`` positions, itself
    included). positions [B, T] -> [B, T, S] over keys 0..capacity-1. The
    upper edge (j <= i) is the caller's mask. The paged kernel computes
    the same two edges in-kernel, and takes from the slot's table only the
    columns this edge leaves (``ops/tile_math.py::window_first_page``)."""
    pos = jnp.arange(capacity)[None, None, :]
    return pos > positions[:, :, None] - sliding


def paged_window_mask(lengths: jax.Array, capacity: int,
                      window: int, sliding: int = 0,
                      base: Optional[jax.Array] = None) -> jax.Array:
    """STAIRCASE window over the paged logical view: verify-window row t
    (the token written at position ``lengths + t``) attends positions
    [0, lengths + t] inclusive, and of those a ``sliding`` layer the last
    ``sliding`` only (:func:`sliding_edge`). lengths [B] ->
    [B, 1, window, S]. ``base`` [B]: the view's column 0 is logical
    position ``base[b]`` (a sliding layer's view starts at its window's
    first page, ``ops/decode_attention.py::window_table``).

    This is THE paged window rule — the Pallas paged kernel computes the
    same staircase in-kernel from the prefetched lengths, and the gather
    fallback streams this mask — so kernel and fallback can never
    disagree about what a spec-verify row may attend. ``window == 1`` is
    exactly :func:`decode_mask` (plain paged decode)."""
    pos = jnp.arange(capacity)[None, None, None, :]
    bound = (lengths[:, None] + jnp.arange(window)[None, :])
    if base is not None:     # positions relative to the view's first one
        bound = bound - base[:, None]
    mask = pos <= bound[:, None, :, None]
    if sliding:
        mask = mask & sliding_edge(bound, capacity, sliding)[:, None]
    return mask
