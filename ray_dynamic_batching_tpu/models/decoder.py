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
import numpy as np
from flax.struct import dataclass as pytree_dataclass

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
    # attends its whole prefix or window.
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

    def __post_init__(self):
        object.__setattr__(self, "hc_res_clamp", tuple(self.hc_res_clamp))
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
        if self.latent and (self.sliding_window or self.index_topk
                            or self.qk_norm or self.use_bias):
            raise ValueError(
                "kv_lora_rank: a latent layer attends its whole prefix, "
                "without an indexer, a q/k norm per head or biases")
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

    def layer_kind(self, i: int) -> "LayerKind":
        """What layer ``i`` is: THE place a layer asks."""
        slides = self._slides(i)
        sparse = self.num_experts > 0 and i >= self.num_dense_layers
        by_kind = {}
        if self.kv_by_kind:
            # its place among the layers of its own kind: its pool's layer
            by_kind = dict(ring=slides, pool_layer=sum(
                1 for j in range(i) if self._slides(j) == slides))
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


@pytree_dataclass
class KVCache:
    """Per-model cache: k/v [L, B, S, K, H]; lengths [B] = valid prefix.

    With ``dtype=int8`` the cache is weight-free quantized storage:
    k/v hold int8 codes and ``k_scale``/``v_scale`` [L, B, S, K] f32
    hold one scale per cached (token, head) row (absmax/127, computed
    at write). The guaranteed win is CAPACITY: half the HBM per slot,
    so auto-sizing fits ~2x the slots per chip. The bandwidth win on
    the decode scan (its dominant HBM traffic) is realized where the
    dequant fuses into the attention read; the XLA fallback path
    materializes a dequantized operand, trading scan bandwidth for
    capacity. Scales are pytree fields: donation and sharding treat
    them as part of the cache automatically; the row seed/extract paths
    (admission copies, prefix/session segments) thread them explicitly
    as part of every stored segment tuple."""

    k: jax.Array
    v: jax.Array
    lengths: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @staticmethod
    def zeros(
        cfg: DecoderConfig, batch_size: int, max_len: Optional[int] = None,
        dtype: jnp.dtype = jnp.bfloat16,
    ) -> "KVCache":
        S = max_len or cfg.max_seq_len
        shape = (cfg.num_layers, batch_size, S, cfg.num_kv_heads, cfg.head_dim)
        quantized = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
        return KVCache(
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype),
            lengths=jnp.zeros((batch_size,), dtype=jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.float32) if quantized else None,
            v_scale=jnp.zeros(shape[:-1], jnp.float32) if quantized else None,
        )

    @property
    def capacity(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


@pytree_dataclass
class PagedKVCache:
    """Paged KV pool: k/v ``[L, P, page_size, K // f, Hp]`` fixed HBM
    pages: ``f`` = :func:`pool_heads_per_row` heads side by side in a
    row where a head is narrower than the 128 lanes and the heads pair off
    (16 x 64: ``[.., 8, 128]``, row ``r`` of a position holds heads
    ``r * f .. r * f + f - 1``; :func:`to_pool_rows` /
    :func:`from_pool_rows` are the two ways across), else ``f`` = 1 and
    ``Hp`` = :func:`pool_head_dim`: the head, lane-padded. Every reader
    takes ``f`` off the shape (``num_kv_heads // k.shape[3]``);
    gathered per slot through ``page_table`` ``[B, NP]`` int32 (entry j
    names the physical page backing logical positions
    ``[j*page_size, (j+1)*page_size)`` of that slot; unallocated entries
    carry the sentinel ``P`` — one past the last page — so writes
    through them drop and gathers clamp into masked territory).

    The slab cache gives every slot a private ``max_len`` KV run whether
    it uses 3 tokens or 300; here HBM occupancy follows *actual* cached
    tokens at page granularity, prefix/session reuse shares pages by
    refcount instead of copying rows (``engine/paging.py``), and EOS
    returns pages to the free list mid-cycle. Shapes stay fully static —
    continuous batching still varies contents, never shapes — so the
    one-compiled-program-per-stream property of the slab path survives.

    Quantized pools mirror the slab layout: k/v hold int8 codes,
    ``k_scale``/``v_scale`` ``[L, P, page_size, K]`` hold the per-row
    f32 scales, paged with the SAME page table. So is ``index_k``
    ``[L, P, page_size, Hip]``, a selecting model's index keys (one a
    position a layer, ``Hip`` the indexer's head lane-padded; the model's
    own dtype in an int8 pool too): a second kind of per-position state
    in the one pool, None for a model without an indexer.

    State BY LAYER KIND (``DecoderConfig.kv_by_kind``): ``k``/``v`` hold
    the FULL layers only (``L`` their count, ``K`` their head count; a v
    row as wide as a value head, lane-padded, where that is narrower than
    a key's), and the sliding layers keep ``ring_k``/``ring_v``
    ``[L_w, B * R, page_size, K_w, Hp]``: a ring of ``R`` pages a slot
    (:attr:`ring_pages`), read and written through :func:`ring_table`, a
    page table that is arithmetic (logical column ``c`` of slot ``b`` is
    page ``b * R + c % R``), so a window layer uses the paged write, the
    gather and the kernel's window walk as they are and the allocator
    hands out full-layer pages only. A position older than the ring is
    overwritten by a newer one; nothing attends it (the window's lower
    edge is the kernel's and the fallback's mask, by position), so a
    reused slot's ring is never cleared. None for every other model.

    A LATENT model (``DecoderConfig.latent``) has no k/v pair at all:
    ``k`` and ``v`` are None and ``latent`` ``[L, P, page_size, Wp]`` holds
    one row a position a layer, ``[c_kv | k_r | 0]`` (``Wp``:
    ``ops/latent_attention.py::row_width``), with NO head axis, paged with
    the same table. None for every other model."""

    k: Optional[jax.Array]
    v: Optional[jax.Array]
    page_table: jax.Array  # [B, NP] int32, sentinel P = unallocated
    lengths: jax.Array     # [B] valid logical prefix per slot
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    index_k: Optional[jax.Array] = None
    ring_k: Optional[jax.Array] = None
    ring_v: Optional[jax.Array] = None
    latent: Optional[jax.Array] = None

    @staticmethod
    def zeros(
        cfg: DecoderConfig, batch_size: int, num_pages: int,
        page_size: int, max_len: int,
        dtype: jnp.dtype = jnp.bfloat16,
        index_dtype: jnp.dtype = jnp.bfloat16,
        widest_chunk: Optional[int] = None,
        tp: int = 1,
    ) -> "PagedKVCache":
        """``widest_chunk`` (state by layer kind only): the most rows one
        program writes to a slot at once, which with the window sets the
        pages of a slot's ring (:attr:`ring_pages`). ``tp``: the width of
        the mesh the pool's head axis is split over
        (:func:`pool_heads_per_row` asks)."""
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len {max_len} must be a multiple of page_size "
                f"{page_size} (logical capacity is whole pages)"
            )
        n_entries = max_len // page_size
        quantized = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
        if cfg.latent:
            if quantized:
                raise NotImplementedError(
                    "kv_lora_rank: a latent row has no scale plane (an "
                    "int8 pool)")
            from ray_dynamic_batching_tpu.ops.latent_attention import (
                row_width,
            )

            return PagedKVCache(
                k=None, v=None,
                page_table=jnp.full((batch_size, n_entries), num_pages,
                                    dtype=jnp.int32),
                lengths=jnp.zeros((batch_size,), dtype=jnp.int32),
                latent=jnp.zeros(
                    (cfg.num_layers, num_pages, page_size,
                     row_width(cfg.kv_lora_rank, cfg.rope_dim)), dtype))
        if cfg.kv_by_kind:
            if quantized or cfg.index_topk:
                raise NotImplementedError(
                    "kv_by_kind: the ring has no scale planes and no index "
                    "keys (an int8 pool, an indexer)")
            rows = lambda layers, pages, heads, width: jnp.zeros(  # noqa: E731
                (layers, pages, page_size, heads, pool_head_dim(width)),
                dtype)
            if widest_chunk is None:
                raise ValueError(
                    "state by layer kind: a slot's ring is sized for the "
                    "widest chunk written to it at once; pass widest_chunk")
            from ray_dynamic_batching_tpu.ops.tile_math import (
                window_table_width,
            )

            full, slide = cfg.layers_of(False), cfg.layers_of(True)
            # The table columns that a chunk's rows can attend between
            # them (window 128, 512 rows, pages of 128: 6), so that no row
            # of a chunk is written over a position another row attends.
            ring = batch_size * window_table_width(
                cfg.sliding_window, widest_chunk, page_size, n_entries)
            k_w = cfg.sliding_kv_heads or cfg.num_kv_heads
            return PagedKVCache(
                k=rows(full, num_pages, cfg.num_kv_heads, cfg.head_dim),
                v=rows(full, num_pages, cfg.num_kv_heads, cfg.v_head_dim),
                page_table=jnp.full((batch_size, n_entries), num_pages,
                                    dtype=jnp.int32),
                lengths=jnp.zeros((batch_size,), dtype=jnp.int32),
                ring_k=rows(slide, ring, k_w, cfg.head_dim),
                ring_v=rows(slide, ring, k_w, cfg.v_head_dim),
            )
        f = pool_heads_per_row(cfg.head_dim, cfg.num_kv_heads, dtype, tp,
                               indexed=bool(cfg.index_topk))
        shape = (cfg.num_layers, num_pages, page_size,
                 cfg.num_kv_heads // f, pool_head_dim(cfg.head_dim * f))
        return PagedKVCache(
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype),
            page_table=jnp.full((batch_size, n_entries), num_pages,
                                dtype=jnp.int32),
            lengths=jnp.zeros((batch_size,), dtype=jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.float32) if quantized else None,
            v_scale=jnp.zeros(shape[:-1], jnp.float32) if quantized else None,
            index_k=jnp.zeros(
                shape[:3] + (pool_head_dim(cfg.index_head_dim),),
                index_dtype) if cfg.index_topk else None,
        )

    @property
    def pages(self) -> jax.Array:
        """The paged pool whose axes 1 and 2 are (page, position): ``k``,
        or a latent model's rows."""
        return self.latent if self.k is None else self.k

    @property
    def page_size(self) -> int:
        return self.pages.shape[2]

    @property
    def num_pages(self) -> int:
        return self.pages.shape[1]

    @property
    def capacity(self) -> int:
        """Per-slot LOGICAL capacity (page_table width x page size) —
        the same contract as ``KVCache.capacity``."""
        return self.page_table.shape[1] * self.page_size

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def ring_pages(self) -> int:
        """Pages in a slot's ring; 0: one pool for every layer."""
        if self.ring_k is None:
            return 0
        return self.ring_k.shape[1] // self.page_table.shape[0]


def ring_table(slots, ring: int, n_entries: int):
    """The sliding layers' page table, ``[len(slots), n_entries]``: logical
    column ``c`` of slot ``b`` is ring page ``b * ring + c % ring``.
    Arithmetic on ``slots`` (a numpy or a traced array alike): nothing is
    allocated, freed or stored."""
    cols = np.arange(n_entries, dtype=np.int32) % ring
    return slots[:, None] * ring + cols[None, :]


def pool_head_dim(head_dim: int) -> int:
    """Width of a (token, head) row in the PAGED pool: the head size
    rounded up to the 128 lanes. The paged kernel and XLA's in-place page
    write both read rows lane-major, so a row narrower than the lanes is
    lane-padded on the device whatever the array says; saying it in the
    SHAPE makes that row-major layout the device's default for the pool.
    With the true width in the shape (64), the default layout puts the
    page's position axis minor-most instead, and every program that
    touches the pool converts k and v on the way in and back on the way
    out: four pool-sized copies a dispatch. A layout kept by
    ``jax.experimental.layout`` would say the same thing without the
    padding showing, but an executable loaded from the persistent compile
    cache forgets it (PERF.md, PR 25). A head that fills the lanes (128,
    256) is not padded."""
    return -(-head_dim // 128) * 128


def pool_heads_per_row(head_dim: int, kv_heads: int, dtype: Any,
                       tp: int = 1, indexed: bool = False) -> int:
    """``f``, the KV heads that lie side by side in ONE 128-lane row of
    the paged pool: the rule, owned here; every reader takes ``f`` off
    the pool's shape (``kv_heads // pool.shape[3]``). Where a head is
    narrower than the lanes and divides them, ``f = 128 // head_dim``
    whole heads fill a row instead of one head and zeros: a position's
    ``[K, head_dim]`` block read as ``[K // f, 128]``, the same bytes in
    the same order, so a gpt2-medium pool (16 x 64) is ``[.., 8, 128]``,
    half the padded bytes, and the paged kernel walks a page once, in the
    8 x 128 geometry of a 128-wide-head model. 1 (a head a row, lane-padded:
    :func:`pool_head_dim`) where the heads do not pair off (``kv_heads %
    f``), for an int8 pool (a scale plane holds one value a (position,
    head): two heads in a row want two), under a TP mesh that ``kv_heads
    // f`` rows do not divide over, and for a selecting model (its sparse
    kernel reads a head a row)."""
    if head_dim <= 0 or 128 % head_dim:
        return 1
    f = 128 // head_dim
    if (kv_heads % f or indexed or (kv_heads // f) % max(1, tp)
            or jnp.dtype(dtype) == jnp.dtype(jnp.int8)):
        return 1
    return f


def to_pool_rows(x: jax.Array, pool: jax.Array) -> jax.Array:
    """x [..., K, H] -> [..., K_pool, Hp], the rows of ``pool``
    ``[L, P, ps, K_pool, Hp]``: ``f`` heads a row (a reshape: the same
    bytes) where the pool packs them (:func:`pool_heads_per_row`), else
    a head a row, lane-padded."""
    if pool.shape[-2] != x.shape[-2]:
        return x.reshape(x.shape[:-2] + pool.shape[-2:])
    return fit_head_dim(x, pool.shape[-1])


def from_pool_rows(rows, kv_heads: int, head_dim: int):
    """:func:`to_pool_rows` back: rows [..., K_pool, Hp] (a jax or a numpy
    array) -> [..., kv_heads, head_dim], the form a slab view, a parcel
    and the spill hold whatever the pool's rows look like."""
    if rows.shape[-2] != kv_heads:
        return rows.reshape(rows.shape[:-2] + (kv_heads, head_dim))
    return rows[..., :head_dim]


def fit_head_dim(x: jax.Array, width: int) -> jax.Array:
    """x [..., H] -> [..., width]: zero-pad the head axis up to the
    pool's row width, or cut a pool row back to the head. Zeros are
    inert on both sides of attention (q . 0 adds nothing to a score, p .
    0 nothing to an output lane that is then cut)."""
    H = x.shape[-1]
    if width == H:
        return x
    if width < H:
        return x[..., :width]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - H)])


def quantize_kv_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(token, head) absmax int8 quantization: x [..., H] ->
    (codes int8 [..., H], scale f32 [...])."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    codes = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return codes, scale


def dequantize_kv(codes: jax.Array, scale: jax.Array,
                  dtype: jnp.dtype) -> jax.Array:
    """codes int8 [..., H] * scale [...] -> [..., H] in ``dtype``.
    Single source of the dequant rule — the attention dispatcher's
    fallback path uses this exact function, so kernel-vs-fallback
    parity cannot drift."""
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


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
           ) -> jax.Array:
    """The dense gated MLP, ``(silu(y Wg) * (y Wu)) Wd``, from a layer's
    ``dense(features, name)`` factory: a dense layer's MLP and an expert
    layer's shared expert are this one code."""
    gate = dense(width, names[0])(y)
    up = dense(width, names[1])(y)
    return dense(d_model, names[2])(nn.silu(gate) * up)


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
        cache_kv: Optional[Tuple[jax.Array, jax.Array]] = None,  # k/v [L,B,S,K,H]
        token_mask: Optional[jax.Array] = None,  # [B, T] (no-cache path)
        layer_idx: int = 0,
        write_start: Optional[jax.Array] = None,  # scalar: chunk write offset
        scatter_writes: bool = False,  # per-row writes at ``positions``
        page_table: Optional[jax.Array] = None,  # [B, NP]: paged decode
        kv_lengths: Optional[jax.Array] = None,  # [B] paged validity bound
        index_pool: Optional[jax.Array] = None,  # [L, P, ps, Hip] index keys
    ) -> Tuple[jax.Array, ...]:
        """(x, updated cache or None); a selecting layer handed the paged
        ``index_pool`` returns it, updated, as a third result."""
        cfg = self.cfg
        kind = cfg.layer_kind(layer_idx)
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
        if kind.latent:
            attn_out, new_cache = self._latent_attention(
                y, positions, mask, cache_kv, token_mask, layer_idx,
                page_table, kv_lengths)
        else:
            attn_out, new_cache, index_pool = self._kv_attention(
                dense, kind, y, positions, mask, cache_kv, token_mask,
                layer_idx, write_start, scatter_writes, page_table,
                kv_lengths, index_pool)
        attn_out = dense(cfg.d_model, "o", axis=(-2, -1))(attn_out)
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
            y = swiglu(dense, y, kind.mlp_dim, cfg.d_model)
        else:
            y = nn.gelu(dense(kind.mlp_dim, "mlp_up")(y))
            y = dense(cfg.d_model, "mlp_down")(y)
        x = hyper_connections.mix(x, y, maps) if hc else x + y
        if index_pool is not None:
            return x, new_cache, index_pool
        return x, new_cache

    def _latent_attention(self, y, positions, mask, cache_kv, token_mask,
                          layer_idx, page_table, kv_lengths):
        """A latent layer's heads' outputs and its pool, updated, as a
        1-tuple (``models/latent.py``; its model alone loads it)."""
        from ray_dynamic_batching_tpu.models import latent

        if cache_kv is not None and page_table is None:
            raise NotImplementedError(
                "a latent layer's rows live in the paged pool "
                "(PagedKVCache.latent): the slab cache has none")
        allowed = None
        if cache_kv is None:
            B, T = positions.shape
            allowed = (prefill_mask(token_mask) if token_mask is not None
                       else mask if mask is not None
                       else jnp.ones((B, 1, T, T), bool))
        out, pool = latent.attention(
            self.cfg, self.dtype,
            lambda name: RMSNorm(name=name, eps=self.cfg.rms_eps),
            y, positions, pool=None if cache_kv is None else cache_kv[0],
            layer=layer_idx, page_table=page_table, kv_lengths=kv_lengths,
            allowed=allowed)
        return out, None if pool is None else (pool,)

    def _kv_attention(self, dense, kind, y, positions, mask, cache_kv,
                      token_mask, layer_idx, write_start, scatter_writes,
                      page_table, kv_lengths, index_pool):
        """A layer of k/v pairs: the heads' outputs, the cache updated (or
        None) and a selecting layer's index pool."""
        cfg = self.cfg
        q = dense((cfg.num_heads, cfg.head_dim), "q")(y)
        kv_heads = kind.kv_heads or cfg.num_kv_heads
        k = dense((kv_heads, cfg.head_dim), "k")(y)
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
            if cache_kv is not None and index_pool is None:
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
            # A 4-tuple carries the int8 cache's per-row scales; every
            # write path scatters codes and scales with the SAME indices.
            quantized = len(cache_kv) == 4
            if quantized:
                k_full, v_full, ks_full, vs_full = cache_kv
                k_w, k_s = quantize_kv_rows(k)
                v_w, v_s = quantize_kv_rows(v)
            else:
                k_full, v_full = cache_kv
                ks_full = vs_full = None
                k_w, v_w = k, v
            B, T = positions.shape
            if page_table is None and kind.pool_layer >= 0:
                raise NotImplementedError(
                    "state by layer kind is the paged cache's: the slab "
                    "cache has one shape for every layer")
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
                k_full = k_full.at[li, pid, off].set(
                    k_w, mode="drop"
                )
                v_full = v_full.at[li, pid, off].set(
                    v_w, mode="drop"
                )
                if quantized:
                    ks_full = ks_full.at[li, pid, off].set(
                        k_s, mode="drop"
                    )
                    vs_full = vs_full.at[li, pid, off].set(
                        v_s, mode="drop"
                    )
                if kind.select:
                    # The index key rides the SAME (page, offset): written
                    # before it is scored, as k and v are.
                    index_pool = index_pool.at[li, pid, off].set(
                        fit_head_dim(k_i, index_pool.shape[-1]).astype(
                            index_pool.dtype), mode="drop")
                    select = sparse_attention.Selection(
                        q_i, w_i, index_pool, kind.select)
            elif scatter_writes:
                # Batched multi-token writes at PER-ROW positions (the
                # speculative-verify path: each slot's window starts at its
                # own length). mode="drop" voids rows steered out of
                # bounds, exactly like the single-token decode scatter.
                rows = jnp.arange(B)[:, None]
                k_full = k_full.at[li, rows, positions].set(
                    k_w, mode="drop"
                )
                v_full = v_full.at[li, rows, positions].set(
                    v_w, mode="drop"
                )
                if quantized:
                    ks_full = ks_full.at[li, rows, positions].set(
                        k_s, mode="drop"
                    )
                    vs_full = vs_full.at[li, rows, positions].set(
                        v_s, mode="drop"
                    )
            elif T == 1:
                # Decode: scatter this token's k/v at its row position.
                # mode="drop" makes a full row's out-of-bounds write a no-op
                # instead of clamping onto (and corrupting) the last slot.
                idx = positions[:, 0]
                rows = jnp.arange(B)
                k_full = k_full.at[li, rows, idx].set(
                    k_w[:, 0], mode="drop"
                )
                v_full = v_full.at[li, rows, idx].set(
                    v_w[:, 0], mode="drop"
                )
                if quantized:
                    ks_full = ks_full.at[li, rows, idx].set(
                        k_s[:, 0], mode="drop"
                    )
                    vs_full = vs_full.at[li, rows, idx].set(
                        v_s[:, 0], mode="drop"
                    )
            else:
                # Prefill: contiguous write at offset 0, or — for chunked
                # prefill of long prompts — at a TRACED start position, so
                # one compiled program serves every chunk of the prompt
                # (dynamic start, static chunk shape).
                start = write_start if write_start is not None else 0
                k_full = jax.lax.dynamic_update_slice(
                    k_full, k_w[None], (li, 0, start, 0, 0)
                )
                v_full = jax.lax.dynamic_update_slice(
                    v_full, v_w[None], (li, 0, start, 0, 0)
                )
                if quantized:
                    ks_full = jax.lax.dynamic_update_slice(
                        ks_full, k_s[None], (li, 0, start, 0)
                    )
                    vs_full = jax.lax.dynamic_update_slice(
                        vs_full, v_s[None], (li, 0, start, 0)
                    )
            # Quantized caches hand CODES + scales to the dispatcher:
            # the decode kernel scans the 1-byte codes directly (the
            # bandwidth win); non-kernel paths dequantize there.
            scale_kwargs = {}
            if quantized:
                scale_kwargs = {"k_scale": ks_full[li],
                                "v_scale": vs_full[li]}
                new_cache = (k_full, v_full, ks_full, vs_full)
            else:
                new_cache = (k_full, v_full)
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

        return attn_out, new_cache, index_pool


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
    ) -> Tuple[jax.Array, Optional[KVCache]]:
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="tok_embed",
        )
        x = embed(tokens)
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

        cache_kv = None
        if getattr(cache, "latent", None) is not None:
            cache_kv = (cache.latent,)
        elif cache is not None:
            cache_kv = (
                (cache.k, cache.v, cache.k_scale, cache.v_scale)
                if cache.quantized else (cache.k, cache.v)
            )
        # A selecting model's index keys, paged beside k and v.
        index_kw = {}
        if getattr(cache, "index_k", None) is not None:
            index_kw["index_pool"] = cache.index_k
        # State by layer kind: the sliding layers' ring rides beside the
        # full layers' pool, each layer handed its own kind's and its table
        # (a row's ring table is its slot's: the caller's for a chunk's
        # rows, slot b's for row b of a decode step).
        ring_kv = None
        if getattr(cache, "ring_k", None) is not None:
            ring_kv = (cache.ring_k, cache.ring_v)
            if ring_tables is None:
                ring_tables = ring_table(
                    jnp.arange(tokens.shape[0], dtype=jnp.int32),
                    cache.ring_pages, page_table.shape[1])
        for i in range(cfg.num_layers):
            ring = ring_kv is not None and cfg.layer_kind(i).ring
            x, updated, *index = DecoderLayer(
                cfg, dtype=self.dtype, name=f"layer{i}")(
                x, positions, mask, ring_kv if ring else cache_kv,
                token_mask, layer_idx=i,
                write_start=write_start, scatter_writes=scatter_writes,
                page_table=ring_tables if ring else page_table,
                kv_lengths=kv_lengths, **index_kw,
            )
            if updated is not None and ring:
                ring_kv = updated
            elif updated is not None:
                cache_kv = updated
            if index:
                index_kw["index_pool"] = index[0]

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

        out_cache = None
        if cache is not None and len(cache_kv) == 1:
            out_cache = PagedKVCache(
                k=None, v=None, page_table=page_table,
                lengths=cache.lengths, latent=cache_kv[0])
        elif cache is not None:
            scales = dict(
                k_scale=cache_kv[2] if len(cache_kv) == 4 else None,
                v_scale=cache_kv[3] if len(cache_kv) == 4 else None,
            )
            if page_table is not None:
                out_cache = PagedKVCache(
                    k=cache_kv[0], v=cache_kv[1], page_table=page_table,
                    lengths=cache.lengths, **scales,
                    index_k=index_kw.get("index_pool"),
                    **({} if ring_kv is None else
                       {"ring_k": ring_kv[0], "ring_v": ring_kv[1]}),
                )
            else:
                out_cache = KVCache(
                    k=cache_kv[0], v=cache_kv[1], lengths=cache.lengths,
                    **scales,
                )
        return logits, out_cache


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
