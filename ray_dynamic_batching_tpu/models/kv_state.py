"""The KV state: what a model caches a position, and everything that has
to know its form (ARCHITECTURE.md, "Paged KV & continuous batching").

``PagedKVCache`` is the pytree the programs carry (``KVCache``: the slab the
draft model and the tests' references keep); its fields are the programs'
argument lists. The engine, the deployment and the page fabric ask the class
(``planes``, the byte counts, ``describe``, ``read_pages`` / ``write_pages``,
``layer_state``, ``pspec``) and the ``CANNOT`` table instead
of listing ``k_scale``, ``index_k``, ``ring_k``, ``latent`` by hand. Nothing
of ``models/decoder.py`` is imported: a configuration is read by attribute.
"""

from __future__ import annotations

import math
from typing import TYPE_CHECKING, Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax.struct import dataclass as pytree_dataclass
from jax.sharding import PartitionSpec as P

if TYPE_CHECKING:
    from ray_dynamic_batching_tpu.models.decoder import (
        DecoderConfig,
        LayerKind,
    )


def _is_int8(dtype: Any) -> bool:
    return dtype is not None and jnp.dtype(dtype) == jnp.dtype(jnp.int8)


# --- what a kind of state cannot serve (ROADMAP D10) ---------------------------
def state_kind(cfg: Any) -> str:
    """``"pair"`` (k/v pages for every layer; scale planes and index keys
    ride them), ``"by_kind"`` (full layers' pages + sliding layers' rings),
    ``"latent"`` (one row a position, no k/v pair; a selecting layer's index
    keys lie in a plane beside the rows), ``"conv"`` (k/v pages for the
    attention layers + a fixed-size state a slot for the conv
    layers) or ``"ssm"`` (k/v pages for EVERY layer + a matrix state a head
    and a conv state, a slot, for every layer's state-space mixer)."""
    if getattr(cfg, "latent", False):
        return "latent"
    if getattr(cfg, "ssm_state", 0):
        return "ssm"
    if getattr(cfg, "conv_kernel", 0):
        return "conv"
    return "by_kind" if getattr(cfg, "kv_by_kind", False) else "pair"


# For each kind, what it cannot serve and why: the engine's options, by name
# (``kv_dtype int8`` the model's), and the operations ``parcel`` (the page
# fabric) and ``slab`` (a slab cache under the layer), whose reason is the
# whole message. What reaches a slot's KV by PAGE REFERENCE cannot work with
# a ring, the slot's own; what moves or scales it as k/v pages of heads
# cannot work with a latent row, which has neither. A conv layer's state is
# the slot's own too, and is of its sequence's END: a page of a prefix holds
# nothing of it, and no write to it can be undone without a snapshot. A
# state-space mixer's state (a matrix a head, 25 MB a slot for Falcon-H1's six
# layers) is refused the same things for the same reasons.
_STATE = {"by_kind": "state by layer kind", "latent": "a latent pool",
          "conv": "a conv state a slot", "ssm": "a state-space state a slot"}
CANNOT: Dict[str, Dict[str, str]] = {
    "pair": {},
    "by_kind": {
        "prefix_cache_size": (
            "a borrowed page holds the full layers' KV of a shared prefix "
            "and nothing of the sliding layers', whose ring is the slot's "
            "own"),
        "session_cache_size": (
            "a stored session pins pages; the slot's ring is overwritten by "
            "its next tenant"),
        "host_spill_pages": "it spills the prefix cache, which is refused",
        "draft_model": (
            "spec verify writes a window into scratch pages and rolls a "
            "rejected tail back; a ring's write is over the position 6 "
            "pages back and cannot be undone"),
        "mesh": "the ring's pool has no sharding layout",
        "kv_dtype int8": "the ring has no scale planes",
        "parcel": (
            "{name}: the page fabric moves a stream as the pages of its "
            "table; with state by layer kind the sliding layers' ring is "
            "not among them"),
        "slab": (
            "state by layer kind is the paged cache's: the slab cache has "
            "one shape for every layer"),
    },
    "latent": {
        "host_spill_pages": (
            "a spilled page is stored and restored as a k/v pair of heads; "
            "a latent page has neither"),
        "draft_model": (
            "spec verify scores a window of rows a slot; the absorbed "
            "decode kernel folds one row a slot"),
        "mesh": "a latent row has no head axis to shard",
        "kv_dtype int8": (
            "a latent row has no scale plane, nor has a selecting layer's "
            "index key beside it"),
        "parcel": (
            "{name}: the page fabric moves a stream as k/v pages of heads; "
            "a latent pool has one row a position (and one index key where "
            "its layers select) and no such pair"),
        "slab": (
            "a latent layer's rows live in the paged pool "
            "(PagedKVCache.latent): the slab cache has none"),
    },
    "conv": {
        "prefix_cache_size": (
            "a borrowed page holds the attention layers' KV of a shared "
            "prefix and nothing of the conv layers' state at its end: that "
            "takes a snapshot of the state a prefix"),
        "session_cache_size": (
            "a stored session pins pages; the slot's conv state is "
            "overwritten by its next tenant"),
        "host_spill_pages": "it spills the prefix cache, which is refused",
        "draft_model": (
            "spec verify rolls a rejected tail back by its lengths; a conv "
            "state moved on by the window cannot be moved back without a "
            "snapshot"),
        "mesh": "the conv state has no sharding layout",
        "kv_dtype int8": "the conv state has no scale plane",
        "parcel": (
            "{name}: the page fabric moves a stream as the pages of its "
            "table; the conv layers' state a slot is not among them"),
        "slab": (
            "a conv layer's state is the paged cache's "
            "(PagedKVCache.conv_state): the slab cache has none"),
    },
    "ssm": {
        "prefix_cache_size": (
            "a borrowed page holds the layers' KV of a shared prefix and "
            "nothing of their state-space state at its end: that takes a "
            "snapshot of the state a prefix"),
        "session_cache_size": (
            "a stored session pins pages; the slot's state-space state is "
            "overwritten by its next tenant"),
        "host_spill_pages": "it spills the prefix cache, which is refused",
        "draft_model": (
            "spec verify rolls a rejected tail back by its lengths; a "
            "state-space state moved on by the window cannot be moved back "
            "without a snapshot"),
        "mesh": "the state-space state has no sharding layout",
        "kv_dtype int8": "the state-space state has no scale plane",
        "parcel": (
            "{name}: the page fabric moves a stream as the pages of its "
            "table; the layers' state-space state a slot is not among them"),
        "slab": (
            "a state-space mixer's state is the paged cache's "
            "(PagedKVCache.ssm_state): the slab cache has none"),
    },
}


def refuse_unsupported(cfg: Any, name: str = "model",
                       error: type = ValueError, **asked: Any) -> None:
    """Raise ``error`` with the table's reason for the first of ``asked``
    that ``cfg``'s kind of state cannot serve. ``asked``: an engine
    option's value (refused where truthy; ``kv_dtype`` where it is int8) or
    ``parcel=True`` / ``slab=True`` for an operation."""
    kind = state_kind(cfg)
    if "kv_dtype" in asked:
        asked["kv_dtype int8"] = _is_int8(asked.pop("kv_dtype"))
    for what, why in CANNOT[kind].items():
        if asked.get(what):
            raise error(why.format(name=name) if what in ("parcel", "slab")
                        else f"{name}: {what} cannot be used with "
                             f"{_STATE[kind]}: {why}")


def kv_bytes_per_slot(cfg: "DecoderConfig", dtype: Any, kv_dtype: Any,
                      max_len: Optional[int] = None) -> int:
    """One slot's ``max_len`` positions at their true widths, the planner's
    figure (the pool's padded rows: :meth:`PagedKVCache.logical_bytes`)."""
    c = cfg
    S = max_len or c.max_seq_len
    itemsize = jnp.dtype(kv_dtype or dtype).itemsize
    per_row = c.head_dim * itemsize
    if _is_int8(kv_dtype):
        per_row += 4  # one f32 scale per cached (token, head) row
    # an indexer's ONE key a position a layer, in the model's own dtype
    index_row = (c.index_head_dim * jnp.dtype(dtype).itemsize
                 if c.index_topk else 0)
    if c.latent:
        # ONE row a position a layer: the latent and the shared key (and
        # a selecting layer's index key)
        return c.num_layers * S * (
            (c.kv_lora_rank + c.rope_dim) * itemsize + index_row)
    if c.kv_by_kind:
        # the full layers a position, the sliding layers their window
        row = (c.head_dim + c.v_head_dim) * itemsize
        return (c.layers_of(False) * S * c.num_kv_heads * row
                + c.layers_of(True) * min(S, c.sliding_window)
                * (c.sliding_kv_heads or c.num_kv_heads) * row)
    if c.conv_kernel:
        # the layers that hold pages a position; the layers that hold a
        # state their taps' last inputs (as wide as the CONV, not the
        # residual: ``conv_width``) and a state-space mixer's matrices in
        # float32, whatever the length
        return (c.pool_layers * S * 2 * c.num_kv_heads * per_row
                + c.conv_layers * (c.conv_kernel - 1) * c.conv_width
                * jnp.dtype(dtype).itemsize
                + (c.num_layers * c.d_ssm * c.ssm_state
                   * jnp.dtype(SSM_STATE_DTYPE).itemsize
                   if c.ssm_state else 0))
    return c.num_layers * S * (2 * c.num_kv_heads * per_row + index_row)


class LayerState(NamedTuple):
    """What ONE layer is handed of the state and hands back updated
    (``layer_state`` / ``with_layer_state``): its kind's stacked pools, WHOLE
    (the layer is an index into them), by the cache's field names; a sliding
    layer's ring is its ``k`` / ``v``."""

    k: Optional[jax.Array] = None
    v: Optional[jax.Array] = None
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    index_k: Optional[jax.Array] = None
    latent: Optional[jax.Array] = None
    conv_state: Optional[jax.Array] = None  # a conv layer's: the rows' states
    ssm_state: Optional[jax.Array] = None   # a state-space mixer's, likewise


class Plane(NamedTuple):
    """One array of a paged cache's state (``PagedKVCache.planes``)."""

    name: str     # the cache's field
    array: Any
    # what finds a position in it: "pages" (the slot's table) | "ring" |
    # "slot" (no position: ONE state a slot, whatever its length)
    table: str
    kind: str     # its bytes count under: "full" | "ring" | "latent" | "state"
    heads: bool   # rows are heads (to_pool_rows / from_pool_rows) or flat


# THE list of the per-position state, in the order of the fields: (field,
# the table that pages it, whether its rows are heads).
_PLANES = (
    ("k", "pages", True), ("v", "pages", True),
    ("k_scale", "pages", False), ("v_scale", "pages", False),
    ("index_k", "pages", False),
    ("ring_k", "ring", True), ("ring_v", "ring", True),
    ("latent", "pages", False),
    ("conv_state", "slot", False),
    ("ssm_state", "slot", False),
)

# A state-space mixer's matrices are kept in float32 whatever the model's
# dtype: what is rounded into the state stays for the rest of the sequence.
SSM_STATE_DTYPE = jnp.float32


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

    def layer_state(self, kind: "LayerKind") -> "LayerState":
        return LayerState(self.k, self.v, self.k_scale, self.v_scale)

    def with_layer_state(self, kind: "LayerKind",
                         updated: "LayerState") -> "KVCache":
        return self.replace(k=updated.k, v=updated.v,
                            k_scale=updated.k_scale, v_scale=updated.v_scale)


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
    the same table. None for every other model. Where its layers SELECT
    (``index_topk``), ``index_k`` ``[L, P, page_size, Hip]`` lies beside the
    rows on that table, as it does beside a k/v pair.

    A model with CONV layers (``DecoderConfig.conv_kernel``): ``k``/``v``
    hold its attention layers only (``L`` their count) and ``conv_state``
    ``[L_conv, B, conv_kernel - 1, W]`` the conv layers' taps' last inputs
    (``W`` the conv's channels, ``DecoderConfig.conv_width``: the residual's
    width for a gated short convolution):
    ONE state a slot a layer, no pages, no table, as large whatever the
    slot's length. It is of the slot's sequence at its END, so the engine's
    chunk program zeroes it where a prompt starts (a conv layer reads it
    unconditionally at position 0; a ring's stale rows are never attended)
    and hands it from chunk to chunk. None for every other model.

    A HYBRID model (``DecoderConfig.ssm_state``): every layer holds pages
    in ``k``/``v`` AND, for its state-space mixer, a ``conv_state`` as above
    (``W`` = ``d_ssm + 2 * groups * state``) and ``ssm_state`` ``[L, B,
    heads, head_dim, state]``: a MATRIX a head, in float32
    (``SSM_STATE_DTYPE``) whatever the model's dtype: the one plane with a
    dtype of its own. Zeroed and handed over as the conv state is; a decode
    step updates it in place. None for every other model."""

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
    conv_state: Optional[jax.Array] = None
    ssm_state: Optional[jax.Array] = None

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
        quantized = _is_int8(dtype)
        table = dict(
            page_table=jnp.full((batch_size, n_entries), num_pages,
                                dtype=jnp.int32),
            lengths=jnp.zeros((batch_size,), dtype=jnp.int32))
        refuse_unsupported(cfg, "PagedKVCache.zeros", NotImplementedError,
                           kv_dtype=dtype)
        if cfg.latent:
            from ray_dynamic_batching_tpu.ops.latent_attention import (
                row_width,
            )

            rows = (cfg.num_layers, num_pages, page_size)
            return PagedKVCache(
                k=None, v=None, **table, latent=jnp.zeros(
                    rows + (row_width(cfg.kv_lora_rank, cfg.rope_dim),),
                    dtype),
                # a selecting layer's index keys: a plane beside the rows,
                # on the same table
                index_k=jnp.zeros(
                    rows + (pool_head_dim(cfg.index_head_dim),),
                    index_dtype) if cfg.index_topk else None)
        if cfg.kv_by_kind:
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
                **table,
                ring_k=rows(slide, ring, k_w, cfg.head_dim),
                ring_v=rows(slide, ring, k_w, cfg.v_head_dim),
            )
        f = pool_heads_per_row(cfg.head_dim, cfg.num_kv_heads, dtype, tp,
                               indexed=bool(cfg.index_topk))
        conv = {}
        if cfg.conv_kernel:
            conv["conv_state"] = jnp.zeros(
                (cfg.conv_layers, batch_size, cfg.conv_kernel - 1,
                 cfg.conv_width), dtype)
        if cfg.ssm_state:
            conv["ssm_state"] = jnp.zeros(
                (cfg.num_layers, batch_size, cfg.ssm_heads,
                 cfg.ssm_head_dim, cfg.ssm_state), SSM_STATE_DTYPE)
        # every layer, but for a model's conv layers: they hold no pages
        shape = (cfg.pool_layers, num_pages, page_size,
                 cfg.num_kv_heads // f, pool_head_dim(cfg.head_dim * f))
        return PagedKVCache(
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype),
            **table,
            k_scale=jnp.zeros(shape[:-1], jnp.float32) if quantized else None,
            v_scale=jnp.zeros(shape[:-1], jnp.float32) if quantized else None,
            index_k=jnp.zeros(
                shape[:3] + (pool_head_dim(cfg.index_head_dim),),
                index_dtype) if cfg.index_topk else None,
            **conv,
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

    @property
    def latent_layers(self) -> int:
        """Layers whose state is a latent row; 0: k/v pairs."""
        return 0 if self.latent is None else self.latent.shape[0]

    def planes(self) -> Tuple[Plane, ...]:
        """The arrays of state this cache holds, in the order of its
        fields: every array leaf but ``page_table`` and ``lengths``. The
        scale planes and index keys count under their pool's kind."""
        pool = "full" if self.latent is None else "latent"
        kinds = {"pages": pool, "ring": "ring", "slot": "state"}
        return tuple(
            Plane(name, getattr(self, name), table, kinds[table], heads)
            for name, table, heads in _PLANES
            if getattr(self, name) is not None)

    # --- a layer's share ---------------------------------------------------
    def layer_state(self, kind: "LayerKind") -> "LayerState":
        """What a layer of ``kind`` reads and writes: the sliding layers'
        ring where state is by layer kind, a conv layer's states, else the
        paged pools."""
        if kind.ring:
            return LayerState(self.ring_k, self.ring_v)
        if kind.conv:
            return LayerState(conv_state=self.conv_state)
        if kind.ssm:
            return LayerState(self.k, self.v, conv_state=self.conv_state,
                              ssm_state=self.ssm_state)
        return LayerState(self.k, self.v, self.k_scale, self.v_scale,
                          self.index_k, self.latent)

    def with_layer_state(self, kind: "LayerKind",
                         updated: "LayerState") -> "PagedKVCache":
        if kind.ring:
            return self.replace(ring_k=updated.k, ring_v=updated.v)
        if kind.conv:
            return self.replace(conv_state=updated.conv_state)
        pools = updated._asdict()
        if not kind.ssm:            # an attention layer is handed no state
            del pools["conv_state"], pools["ssm_state"]
        return self.replace(**pools)

    # --- bytes ---------------------------------------------------------------
    def resident_bytes(self) -> int:
        """What the planes occupy on the device (a placed cache's)."""
        return sum(self.bytes_by_kind().values())

    def bytes_by_kind(self) -> Dict[str, int]:
        """:meth:`resident_bytes` by the planes' kind: ``full`` the paged
        pool, ``ring`` the sliding layers' rings, ``latent`` the rows,
        ``state`` the states a slot (conv and state-space)."""
        out: Dict[str, int] = {}
        for p in self.planes():
            out[p.kind] = out.get(p.kind, 0) + (
                p.array.on_device_size_in_bytes())
        return out

    def logical_bytes(self) -> int:
        """The planes' bytes off their shapes (an ``eval_shape``d cache's
        too): what the deployment prices a slot's page run at."""
        return sum(math.prod(p.array.shape) * p.array.dtype.itemsize
                   for p in self.planes())

    def describe(self, cfg: "DecoderConfig") -> Dict[str, Any]:
        """``snapshot()["kv_pool"]``'s lines of a placed cache: the order
        of the pool's axes on the device (row-major is what the paged kernel
        and the page write read), its bytes there, each kind's own lines."""
        layout = self.pages.format.layout
        out: Dict[str, Any] = {
            "layout": (None if layout is None
                       else list(layout.major_to_minor)),
            "resident_bytes": self.resident_bytes(),
        }
        if self.k is not None:
            # KV heads side by side in a pool row, off the pool's shape.
            out.update(pool_shape=list(self.k.shape),
                       heads_per_row=cfg.num_kv_heads // self.k.shape[3])
        if self.ring_k is not None:
            out.update(ring_pages_per_slot=self.ring_pages,
                       bytes_by_kind=self.bytes_by_kind())
        if self.latent is not None:
            rows = self.latent
            out.update(
                kind="latent", shape=list(rows.shape),
                row_width=rows.shape[-1],
                row_bytes=rows.shape[-1] * rows.dtype.itemsize,
                bytes_by_kind=self.bytes_by_kind())
        if self.conv_state is not None:
            # a state a slot: each plane's shape, its OWN dtype and bytes
            out.update(
                kind="conv" if self.ssm_state is None else "ssm",
                pool_layers=self.k.shape[0],
                bytes_by_kind=self.bytes_by_kind(),
                **{p.name: {
                    "shape": list(p.array.shape), "dtype": str(p.array.dtype),
                    "bytes_per_slot": math.prod(p.array.shape[2:])
                    * p.array.shape[0] * p.array.dtype.itemsize}
                   for p in self.planes() if p.table == "slot"})
        if self.index_k is not None:
            out["index_pool"] = {
                "shape": list(self.index_k.shape),
                "dtype": str(self.index_k.dtype),
                "resident_bytes": self.index_k.on_device_size_in_bytes()}
        return out

    # --- pages across the host (a parcel, the spill) -------------------------
    def _paged(self) -> Tuple[Plane, ...]:
        return tuple(p for p in self.planes() if p.table == "pages")

    def read_pages(self, idx: np.ndarray,
                   cfg: "DecoderConfig") -> Dict[str, np.ndarray]:
        """The listed pages of every plane the table pages, on the host, by
        the planes' names. Rows of heads travel as ``[.., K, head_dim]`` (cut
        or reshaped AFTER the gather: a gather of part of a row makes XLA
        re-lay the whole pool out for it). A page travels with its scales and
        index keys: a selecting layer would score zeros there without them."""
        refuse_unsupported(cfg, parcel=True)
        out = {}
        for p in self._paged():
            rows = np.asarray(p.array[:, idx])
            out[p.name] = (
                from_pool_rows(rows, cfg.num_kv_heads, cfg.head_dim)
                if p.heads else rows)
        return out

    def write_pages(self, idx: jax.Array, payload: Dict[str, np.ndarray],
                    cfg: "DecoderConfig") -> "PagedKVCache":
        """:meth:`read_pages` back, into pages ``idx``: a functional
        update of every plane the table pages."""
        refuse_unsupported(cfg, parcel=True)
        repl = {}
        for p in self._paged():
            rows = jnp.asarray(payload[p.name], p.array.dtype)
            repl[p.name] = p.array.at[:, idx].set(
                to_pool_rows(rows, p.array) if p.heads else rows)
        return self.replace(**repl)

    # --- sharding --------------------------------------------------------------
    @staticmethod
    def pspec(cfg: "DecoderConfig", kv_dtype: Any = None,
              name: str = "model") -> "PagedKVCache":
        """PartitionSpecs for the PAGED KV pool (ROADMAP item 2): pages
        shard on the kv-head dim exactly like the slab cache — the pool
        is ``[L, P, ps, K // f, Hp]``, so the heads sit at the same index
        3 (``f`` side by side in a row only where the rows still divide
        over the mesh: ``pool_heads_per_row``) and a shard owns the full
        page set for its head slice. The page table
        and lengths REPLICATE: page indices are shard-invariant (every
        shard's slice of page ``p`` backs the same logical positions),
        which is what lets the host-side ``PageAllocator`` stay
        replica-global. Scale planes (``[L, P, ps, K]``) shard with
        their heads; a selecting model's index keys replicate."""
        refuse_unsupported(cfg, name, NotImplementedError, mesh=True)
        scale_spec = P(None, None, None, "tp") if _is_int8(kv_dtype) else None
        return PagedKVCache(
            k=P(None, None, None, "tp", None),   # type: ignore[arg-type]
            v=P(None, None, None, "tp", None),   # type: ignore[arg-type]
            page_table=P(None, None),             # type: ignore[arg-type]
            lengths=P(None),                      # type: ignore[arg-type]
            k_scale=scale_spec,                   # type: ignore[arg-type]
            v_scale=scale_spec,                   # type: ignore[arg-type]
            # ONE index key a position, whatever the head shard: replicated
            index_k=(P(None, None, None, None)    # type: ignore[arg-type]
                     if cfg.index_topk else None),
        )


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



def commit_row(cache: KVCache, row: KVCache, slot) -> KVCache:
    """Copy a single finished row cache into the shared cache at ``slot``,
    slicing the (whole-chunk-rounded, possibly longer) row down to shared
    capacity: the commit of the draft model's prompt replay."""
    S = cache.capacity
    k = jax.lax.dynamic_update_slice(
        cache.k, row.k[:, :, :S], (0, slot, 0, 0, 0)
    )
    v = jax.lax.dynamic_update_slice(
        cache.v, row.v[:, :, :S], (0, slot, 0, 0, 0)
    )
    ks, vs = cache.k_scale, cache.v_scale
    if ks is not None:
        ks = jax.lax.dynamic_update_slice(
            ks, row.k_scale[:, :, :S], (0, slot, 0, 0)
        )
        vs = jax.lax.dynamic_update_slice(
            vs, row.v_scale[:, :, :S], (0, slot, 0, 0)
        )
    lengths = jax.lax.dynamic_update_slice(
        cache.lengths, row.lengths, (slot,)
    )
    return cache.replace(k=k, v=v, lengths=lengths,
                         k_scale=ks, v_scale=vs)
