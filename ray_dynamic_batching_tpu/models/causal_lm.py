"""Causal-LM servable models (GPT-2 family, Llama family) on the shared decoder.

BASELINE.json configs 3-4: "GPT-2-medium autoregressive decode (KV-cache,
continuous batching)" and "Llama-3-8B TP=4 over ICI (pjit-sharded replica)".
The engine drives these through two compiled programs — ``prefill`` (one per
(batch, seq) bucket) and ``decode_step`` (one per batch-slot count) — with the
KV cache donated between steps.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_dynamic_batching_tpu.models.base import (
    ModelSLO,
    ServableModel,
    register_model,
)
from ray_dynamic_batching_tpu.models import kv_state
from ray_dynamic_batching_tpu.models.decoder import (
    DecoderConfig,
    DecoderModule,
    decode_mask,
    prefill_mask,
)
from ray_dynamic_batching_tpu.models.kv_state import KVCache, PagedKVCache


def routing_counters(routing: Any, valid: jax.Array, first_expert: int,
                     held_experts: int) -> jax.Array:
    """``[rows, experts_hit, max_rows, pairs]`` int32 of one forward's
    expert routing, over REAL tokens only: ``routing`` is the
    ``moe_routing`` collection (each expert layer's chosen experts
    ``[B, T, k]``, ids among all the router's) and ``valid`` ``[B, T]``
    says which tokens are real (pad tokens of a bucket and inactive decode
    slots are computed and not counted). ``rows`` is the token-expert pairs
    routed to the experts HELD here (``[first_expert, first_expert +
    held_experts)``: all of them, or one rank's share), ``experts_hit`` the
    held experts with at least one real row, both summed over layers;
    ``max_rows`` the most one held expert took in one layer; ``pairs`` all
    real pairs, wherever they went (``rows`` where every expert is held)."""
    experts = first_expert + jnp.arange(held_experts, dtype=jnp.int32)
    real = valid.astype(bool)[..., None, None]
    rows = hit = most = pairs = jnp.zeros((), jnp.int32)
    for idx in jax.tree_util.tree_leaves(routing):
        took = ((idx[..., None] == experts) & real).sum(axis=(0, 1, 2))
        rows += took.sum()
        hit += (took > 0).sum()
        most = jnp.maximum(most, took.max())
        pairs += real.sum() * idx.shape[-1]
    return jnp.stack([rows, hit, most, pairs]).astype(jnp.int32)


def merge_routing_counters(counters: jax.Array) -> jax.Array:
    """Several forwards' counters ``[n, 4]`` as one dispatch's ``[4]``:
    rows, experts hit and pairs add, the most rows is the largest."""
    return jnp.stack([counters[:, 0].sum(), counters[:, 1].sum(),
                      counters[:, 2].max(), counters[:, 3].sum()])


# A chunk group's logits are made over EVERY row, ``[B, W, V]`` float32, and
# one row a sequence is taken — where a row of them is at most this many
# bytes (131,072 columns). A wider vocabulary (Falcon-H1's 261,120: 1.07 GB
# for two 512-row chunks, twice that with the head's multiplier, beside a
# model that fills the chip, and 7 ms of head product a 512-row chunk of
# which one row is read) has the head read the taken rows alone
# (``DecoderModule``'s ``head_rows``). From shapes; every configuration
# under it (the widest: 65,536) traces the program it did (ROADMAP S13).
CHUNK_LOGITS_ROW_BYTES = 2 ** 19


class CausalLM(ServableModel):
    family = "causal_lm"

    def __init__(
        self,
        cfg: DecoderConfig,
        name: str,
        dtype: jnp.dtype = jnp.bfloat16,
        kv_dtype: Optional[jnp.dtype] = None,
    ):
        super().__init__(dtype)
        self.name = name
        self.cfg = cfg
        # KV-cache storage dtype (None = activations dtype). int8 halves
        # the decode scan's HBM traffic: codes + per-(token, head) f32
        # scales, quantized at write (models/kv_state.py::quantize_kv_rows).
        self.kv_dtype = kv_dtype
        self.module = DecoderModule(cfg, dtype=dtype)

    @property
    def has_experts(self) -> bool:
        return self.cfg.num_experts > 0

    @property
    def layer_windows(self) -> Tuple[int, ...]:
        """Each attention layer's sliding window (0: it attends its whole
        prefix); a conv layer walks no table and is not listed."""
        kinds = (self.cfg.layer_kind(i) for i in range(self.cfg.num_layers))
        return tuple(k.window for k in kinds if not k.conv)

    def _forward(self, params, *args, moe_valid=None, **kwargs):
        """``module.apply``; with ``moe_valid`` [B, T] (an expert model's
        caller asking for them) also this forward's
        :func:`routing_counters`, as a third result."""
        if moe_valid is None:
            return self.module.apply(params, *args, **kwargs)
        (logits, cache), state = self.module.apply(
            params, *args, mutable=["moe_routing"], **kwargs)
        return logits, cache, routing_counters(
            # (nothing sown: a depth cut that leaves the dense layers alone)
            state.get("moe_routing", {}), moe_valid,
            self.cfg.moe_first_expert,
            self.cfg.held_experts)

    # --- ServableModel interface (apply == prefill logits for profiling) ---
    def init(self, rng: jax.Array):
        tokens, attn_mask = self.example_inputs(1, 8)
        positions = jnp.arange(8)[None, :]
        mask = prefill_mask(attn_mask)
        return self.module.init(rng, tokens, positions, mask)

    def apply(self, params, tokens: jax.Array, attn_mask: jax.Array) -> jax.Array:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1])[None, :], tokens.shape
        )
        # token_mask path: attention builds its own causal+padding mask and
        # can route through ring attention under a sequence_parallel context.
        logits, _ = self.module.apply(
            params, tokens, positions, None, token_mask=attn_mask
        )
        return logits

    def apply_with_aux(
        self, params, tokens: jax.Array, attn_mask: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Forward plus the MoE load-balance auxiliary loss (0 for dense
        models). Training losses must add ``aux_coef * aux`` or the router
        collapses onto one expert and overflow tokens get zeroed."""
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1])[None, :], tokens.shape
        )
        (logits, _), state = self.module.apply(
            params, tokens, positions, None, token_mask=attn_mask,
            mutable=["intermediates"],
        )
        aux_leaves = [
            jnp.asarray(x).sum()
            for x in jax.tree_util.tree_leaves(state.get("intermediates", {}))
        ]
        aux = sum(aux_leaves) if aux_leaves else jnp.zeros((), jnp.float32)
        return logits, aux

    def example_inputs(self, batch_size: int, seq_len: Optional[int] = None):
        T = seq_len or 128
        return (
            jnp.zeros((batch_size, T), dtype=jnp.int32),
            jnp.ones((batch_size, T), dtype=jnp.int32),
        )

    # --- decode interface (used by engine.decode) -------------------------
    def make_cache(
        self, batch_size: int, max_len: Optional[int] = None
    ) -> KVCache:
        return KVCache.zeros(
            self.cfg, batch_size, max_len, dtype=self.kv_dtype or self.dtype
        )

    def prefill(
        self, params, tokens: jax.Array, attn_mask: jax.Array, cache: KVCache
    ) -> Tuple[jax.Array, KVCache]:
        """Run the prompt through the model, filling the cache.

        tokens [B, T] right-padded; attn_mask [B, T]. Returns last-valid-token
        logits [B, V] and the cache with ``lengths`` set per row.
        """
        B, T = tokens.shape
        S = cache.capacity
        if T > S:
            raise ValueError(
                f"prompt length {T} exceeds KV-cache capacity {S}; "
                "bucket the prompt or allocate a larger cache"
            )
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        lengths = attn_mask.sum(axis=1).astype(jnp.int32)
        # Queries may attend causally within the prompt; cache positions
        # beyond T are empty, mask them off.
        base = prefill_mask(attn_mask)  # [B,1,T,T]
        if S > T:
            pad = jnp.zeros((B, 1, T, S - T), dtype=bool)
            mask = jnp.concatenate([base, pad], axis=-1)
        else:
            mask = base
        logits, new_cache = self.module.apply(params, tokens, positions, mask, cache)
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1
        )[:, 0]
        return last, new_cache.replace(lengths=lengths)

    def prefill_chunk(
        self,
        params,
        tokens: jax.Array,     # [B, C] one chunk (last chunk right-padded)
        attn_mask: jax.Array,  # [B, C] 1 = real token
        cache: KVCache,
        start: jax.Array,      # scalar int32: global position of tokens[:,0]
        take_idx: jax.Array,   # scalar int32: logits row to return
    ) -> Tuple[jax.Array, KVCache]:
        """One chunk of a long prompt: write k/v at [start, start+C), attend
        to every cached position up to each token's own. ``start`` and
        ``take_idx`` are TRACED, so one compiled program per chunk width C
        serves every chunk of every prompt — the point is bounding how long
        a single prefill dispatch can stall active decode slots (chunked
        prefill; admission interleaving happens in the engine).

        Caller contract: chunks arrive in order; all chunks are full except
        the last. Padded tail positions write garbage k/v beyond the final
        ``lengths``, which decode masks off exactly as it does for the
        one-shot prefill path. Returns (logits at ``take_idx`` [B, V],
        updated cache) — only the final chunk's call uses the logits.
        """
        B, C = tokens.shape
        S = cache.capacity
        positions = start + jnp.broadcast_to(jnp.arange(C)[None, :], (B, C))
        # Query at global pos p attends cache slots [0, p]: earlier chunks
        # are already resident, in-chunk attention stays causal, and slot 0
        # is always visible so padded query rows keep a sane softmax.
        s_idx = jnp.arange(S)[None, None, None, :]
        mask = s_idx <= positions[:, None, :, None]
        logits, new_cache = self.module.apply(
            params, tokens, positions, mask, cache, write_start=start
        )
        new_lengths = cache.lengths + attn_mask.sum(axis=1).astype(jnp.int32)
        taken = jax.lax.dynamic_slice_in_dim(logits, take_idx, 1, axis=1)
        return taken[:, 0], new_cache.replace(lengths=new_lengths)

    def verify_step(
        self,
        params,
        tokens: jax.Array,   # [B, T] pending token + proposed continuation
        cache: KVCache,
        active: jax.Array,   # [B] bool
    ) -> Tuple[jax.Array, KVCache]:
        """Score a T-token window per row in ONE forward (the speculative-
        verify primitive): row b's window starts at its own ``lengths[b]``,
        k/v scatter per row at those positions, and logits[b, j] scores the
        token AFTER window position j. ``lengths`` are NOT advanced — the
        caller accepts a per-row prefix and sets them. Inactive rows are
        steered out of bounds (writes dropped, logits garbage)."""
        B, T = tokens.shape
        S = cache.capacity
        base = cache.lengths[:, None]  # [B,1]
        positions = base + jnp.arange(T)[None, :]
        # Out-of-bounds positions for inactive/overflowing rows: their
        # scatter is dropped and their outputs are never used.
        positions = jnp.where(
            active[:, None] & (positions < S), positions, S
        )
        s_idx = jnp.arange(S)[None, None, None, :]
        mask = s_idx <= positions[:, None, :, None]
        logits, new_cache = self.module.apply(
            params, tokens, positions, mask, cache, scatter_writes=True
        )
        return logits, new_cache

    def prefill_chunk_paged(
        self,
        params,
        tokens: jax.Array,     # [B, W] one chunk per row (tail right-padded)
        attn_mask: jax.Array,  # [B, W] 1 = real token
        cache: PagedKVCache,
        tables: jax.Array,     # [B, NP] per-row page-table rows
        starts: jax.Array,     # [B] global position of tokens[:, 0] per row
        take_idx: jax.Array,   # [B] per-row logits row to return
        moe_counters: bool = False,
        ring_tables: Optional[jax.Array] = None,  # [B, NP] the rows' rings
        state_slots: Optional[jax.Array] = None,  # [B] the rows' slots
    ) -> Tuple[jax.Array, ...]:
        """Pages-DIRECT chunked prefill: one chunk of B independent (and
        independently-positioned) prompt fills, written straight through
        per-row page-table rows — no private row cache, no commit copy.
        The speculative-verify primitive generalized to KNOWN tokens: row
        b's chunk occupies global positions ``[starts[b], starts[b]+W)``,
        k/v scatter through ``tables`` into the pages the engine granted
        for this chunk (positions past logical capacity steer to the
        sentinel and DROP — a CoW-borrowed prefix page is below
        ``starts`` by construction and is never written), and attention
        reads the STAIRCASE window (row t attends positions <=
        starts + t — the ``paged_window_mask`` rule with the chunk's
        start as the length; the Tq==1 case is ``decode_mask``). Padded
        tail positions write garbage k/v beyond the final length exactly
        like the slab chunk path; nothing ever attends them.
        ``lengths``/``page_table`` pass through untouched — the caller
        owns both (the engine scatters verified lengths itself at the
        final chunk). Returns (logits at ``take_idx`` [B, V], cache) and,
        with ``moe_counters`` (an expert model), the chunk's
        :func:`routing_counters` over ``attn_mask``'s real tokens.
        ``ring_tables`` (a model with state by layer kind): each row's
        slot's ring table (``models/kv_state.py::ring_table``), through
        which its sliding layers write and read.
        ``state_slots`` (a model with a state a slot: conv layers, hybrid
        layers): each row's slot, whose state (every plane of it:
        ``conv_state``, ``ssm_state``) the row starts from — ZEROS where
        the row starts its prompt (``starts`` 0), whatever the slot's last
        tenant left — and leaves at its true end (``attn_mask``'s real
        tokens); a row without a real token (a filler, warm-up's) writes
        none back."""
        B, W = tokens.shape
        S = tables.shape[1] * cache.page_size
        conv = {}
        # the planes that are ONE state a slot: [L, slots, ...]
        slot_planes = [p for p in cache.planes() if p.table == "slot"]
        if slot_planes:
            n_slots = slot_planes[0].array.shape[1]
            real = attn_mask.sum(axis=1).astype(jnp.int32)
            conv["state_lens"] = real
            at = jnp.minimum(state_slots, n_slots - 1)
            # The layers see the ROWS' states; the slots' come back below.
            # A row's state is cut out of the plane where it lies (a slice
            # a row: a gather over a 1.6 GB plane of matrices made XLA lay
            # half of it out anew, twice).
            cache = cache.replace(**{p.name: jnp.where(
                (starts == 0).reshape((1, B) + (1,) * (p.array.ndim - 2)),
                0, jnp.concatenate([
                    jax.lax.dynamic_slice_in_dim(p.array, at[r], 1, axis=1)
                    for r in range(B)], axis=1)) for p in slot_planes})
        positions = starts[:, None] + jnp.broadcast_to(
            jnp.arange(W)[None, :], (B, W)
        )
        # Overflowing positions (an unaligned continuation's padded tail
        # can run past logical capacity) steer to S: their scatter drops
        # at the sentinel and their outputs are never taken.
        positions = jnp.where(positions < S, positions, S)
        head = {}
        if 4 * self.cfg.vocab_size > CHUNK_LOGITS_ROW_BYTES:
            head["head_rows"] = take_idx
            take_idx = jnp.zeros_like(take_idx)     # of the ONE row made
        logits, new_cache, *counters = self._forward(
            params, tokens, positions, None, cache, scatter_writes=True,
            page_table=tables, kv_lengths=starts,
            moe_valid=attn_mask if moe_counters else None,
            ring_tables=ring_tables, **conv, **head,
        )
        for p in slot_planes:
            # ... and written back where it lay, a row at a time, in place;
            # a row without a real token writes back what is there
            plane, rows = p.array, getattr(new_cache, p.name)
            for r in range(B):
                kept = jax.lax.dynamic_slice_in_dim(plane, at[r], 1, axis=1)
                plane = jax.lax.dynamic_update_slice_in_dim(
                    plane, jnp.where(real[r] > 0, rows[:, r:r + 1], kept),
                    at[r], axis=1)
            new_cache = new_cache.replace(**{p.name: plane})
        taken = jnp.take_along_axis(
            logits, take_idx[:, None, None], axis=1
        )[:, 0]
        return (taken, new_cache, *counters)

    def verify_step_paged(
        self,
        params,
        tokens: jax.Array,   # [B, T] pending token + proposed continuation
        cache: PagedKVCache,
        active: jax.Array,   # [B] bool
    ) -> Tuple[jax.Array, PagedKVCache]:
        """Paged mirror of :meth:`verify_step` — the speculative-verify
        primitive over the page pool. Row b's T-token window starts at
        its own ``lengths[b]``; k/v scatter through the page table into
        the round's scratch pages (per-row positions, ``mode="drop"``
        for rows steered out of bounds), and attention reads the
        STAIRCASE window (row t attends positions <= lengths + t — the
        ``paged_window_mask`` rule, fused in the paged kernel and
        streamed by the gather fallback). ``lengths`` are NOT advanced —
        the caller accepts a per-row prefix and sets them, exactly the
        slab contract, which is what keeps paged+spec greedy decoding
        byte-identical to slab+spec."""
        B, T = tokens.shape
        S = cache.capacity
        base = cache.lengths[:, None]  # [B, 1]
        positions = base + jnp.arange(T)[None, :]
        # Out-of-bounds positions for inactive/overflowing rows: their
        # scatter steers to the sentinel page and their outputs are
        # never accepted (the engine clamps n_out to remaining room).
        positions = jnp.where(
            active[:, None] & (positions < S), positions, S
        )
        logits, new_cache = self.module.apply(
            params, tokens, positions, None, cache, scatter_writes=True,
            page_table=cache.page_table, kv_lengths=cache.lengths,
        )
        return logits, new_cache

    def decode_step(
        self,
        params,
        tokens: jax.Array,   # [B, 1] current token per slot
        cache: KVCache,
        active: jax.Array,   # [B] bool — which slots advance
        moe_counters: bool = False,
    ) -> Tuple[jax.Array, ...]:
        """One decode step for all slots; returns logits [B, V] + new cache
        (and, with ``moe_counters``, the step's :func:`routing_counters`
        over the rows that advance).

        Rows whose cache is full are force-deactivated: their out-of-bounds
        scatter is explicitly dropped (decoder writes with mode="drop"), their
        logits are garbage, and ``lengths`` stops advancing at capacity, so
        the engine detects exhaustion via ``lengths == capacity`` instead of
        silently decoding on (or corrupting the last cache slot).
        """
        in_bounds = cache.lengths < cache.capacity
        active = jnp.logical_and(active, in_bounds)
        positions = cache.lengths[:, None]
        mask = decode_mask(cache.lengths, cache.capacity)
        logits, new_cache, *counters = self._forward(
            params, tokens, positions, mask, cache,
            moe_valid=active[:, None] if moe_counters else None,
        )
        new_lengths = cache.lengths + active.astype(jnp.int32)
        return (logits[:, 0], new_cache.replace(lengths=new_lengths),
                *counters)

    def make_paged_cache(
        self, batch_size: int, num_pages: int, page_size: int,
        max_len: int, widest_chunk: Optional[int] = None, tp: int = 1,
    ) -> PagedKVCache:
        """A paged KV pool: ``num_pages`` fixed HBM pages + a
        ``[batch_size, max_len // page_size]`` page table (engine-owned
        allocation — ``engine/paging.py``). ``widest_chunk``: the most
        rows one program writes to a slot at once, which sizes the
        sliding layers' ring where state is by layer kind. ``tp``: the
        width of the mesh the head axis will be split over (the rows'
        layout asks: ``models/kv_state.py::pool_heads_per_row``)."""
        return PagedKVCache.zeros(
            self.cfg, batch_size, num_pages, page_size, max_len,
            dtype=self.kv_dtype or self.dtype, index_dtype=self.dtype,
            widest_chunk=widest_chunk, tp=tp,
        )

    def decode_step_paged(
        self,
        params,
        tokens: jax.Array,   # [B, 1] current token per slot
        cache: PagedKVCache,
        active: jax.Array,   # [B] bool — which slots advance
        moe_counters: bool = False,
    ) -> Tuple[jax.Array, ...]:
        """One decode step against the paged pool — the exact
        :meth:`decode_step` contract (force-deactivation at logical
        capacity, lengths advance only for active rows, garbage logits
        on inactive rows, ``moe_counters``) with writes and reads routed
        through the page
        table. Token-exact vs the slab step by construction: the write
        rule maps the same logical position to a physical (page,
        offset), and attention sees the same positions <= lengths window
        through the dispatcher's paged gather/kernel."""
        in_bounds = cache.lengths < cache.capacity
        active = jnp.logical_and(active, in_bounds)
        positions = cache.lengths[:, None]
        # a slot's state (conv, state-space) moves on only where the slot
        # advances
        conv = ({} if cache.conv_state is None
                else {"state_lens": active.astype(jnp.int32)})
        logits, new_cache, *counters = self._forward(
            params, tokens, positions, None, cache,
            page_table=cache.page_table, kv_lengths=cache.lengths,
            moe_valid=active[:, None] if moe_counters else None, **conv,
        )
        new_lengths = cache.lengths + active.astype(jnp.int32)
        return (logits[:, 0], new_cache.replace(lengths=new_lengths),
                *counters)

    # --- planning ---------------------------------------------------------
    def flops_per_sample(self, seq_len: Optional[int] = None) -> float:
        T = seq_len or 128
        c = self.cfg
        total = 0
        for i in range(c.num_layers):
            kind = c.layer_kind(i)
            mlp = kind.mlp_dim * (
                c.moe_top_k + c.moe_shared_experts if kind.sparse else 1)
            if kind.conv:
                # in (D -> 3D) and out (D -> D); the taps are no matmul
                proj = 4 * c.d_model * c.d_model
            elif kind.ssm:
                # attention's four products, and beside them the mixer's in
                # ([z | x B C | dt]) and out, and a token's pass over the
                # state (decay, outer product, read-out: 3 multiply-adds an
                # element)
                proj = (c.d_model * c.head_dim * (
                    c.num_heads + 2 * c.num_kv_heads)
                    + c.num_heads * c.head_dim * c.d_model
                    + c.d_model * (c.d_ssm + c.conv_width + c.ssm_heads)
                    + c.d_ssm * c.d_model + 3 * c.d_ssm * c.ssm_state)
            elif kind.latent:
                # the low-rank q and kv paths, keys and values expanded
                nope = c.head_dim - c.rope_dim
                proj = (c.d_model * (c.q_lora_rank + c.kv_lora_rank
                                     + c.rope_dim)
                        + c.num_heads * (
                            c.q_lora_rank * c.head_dim
                            + c.kv_lora_rank * (nope + c.v_head_dim)
                            + c.v_head_dim * c.d_model))
                if kind.select:
                    # the indexer's three projections (its queries from
                    # the q latent)
                    proj += (c.q_lora_rank * c.index_heads * c.index_head_dim
                             + c.d_model * (c.index_head_dim
                                            + c.index_heads))
            else:
                proj = (c.d_model * c.head_dim * (
                    c.num_heads + 2 * c.num_kv_heads)
                    + c.num_heads * c.head_dim * c.d_model)
            per_tok = 2 * (
                proj + (3 if c.gated_mlp else 2) * c.d_model * mlp
            )
            # score+value flops per token, avg T/2 ctx * 2
            attn = 0 if kind.conv else 4 * (
                min(T, 2 * kind.window) if kind.window else T) * (
                c.num_heads * c.head_dim)
            if kind.latent and kind.select:
                # a query attends at most ``select`` positions, and its
                # index heads score every one up to its own (avg T/2 * 2)
                attn = (4 * min(T, 2 * kind.select) * (
                    c.num_heads * c.head_dim)
                    + 2 * T * c.index_heads * c.index_head_dim)
            total += (per_tok + attn) * T
        return total + 2 * c.d_model * c.vocab_size * T

    def kv_bytes_per_slot(self, max_len: Optional[int] = None) -> int:
        """One slot's KV bytes at its true widths (the planner's figure)."""
        return kv_state.kv_bytes_per_slot(
            self.cfg, self.dtype, self.kv_dtype, max_len)

    def sharding_rules(self):
        return [
            (r"/q/kernel", P(None, "tp", None)),
            (r"/k/kernel", P(None, "tp", None)),
            (r"/v/kernel", P(None, "tp", None)),
            (r"/o/kernel", P("tp", None, None)),
            (r"mlp_gate/kernel", P(None, "tp")),
            (r"mlp_up/kernel", P(None, "tp")),
            (r"mlp_down/kernel", P("tp", None)),
            (r"moe/wi", P("ep", None, "tp")),
            (r"moe/wg", P("ep", None, "tp")),
            (r"moe/wo", P("ep", "tp", None)),
            (r"moe/shared_gate/kernel", P(None, "tp")),
            (r"moe/shared_up/kernel", P(None, "tp")),
            (r"moe/shared_down/kernel", P("tp", None)),
            (r"tok_embed/embedding", P("tp", None)),
            (r"lm_head/kernel", P(None, "tp")),
        ]

    def paged_cache_pspec(self) -> PagedKVCache:
        """PartitionSpecs for the paged pool (``PagedKVCache.pspec``)."""
        return PagedKVCache.pspec(self.cfg, self.kv_dtype, self.name)


GPT2_MEDIUM = DecoderConfig(
    vocab_size=50257,
    d_model=1024,
    num_layers=24,
    num_heads=16,
    num_kv_heads=16,
    mlp_dim=4096,
    max_seq_len=1024,
    pos="learned",
    norm="ln",
    gated_mlp=False,
    use_bias=True,
    tie_embeddings=True,
)

LLAMA3_8B = DecoderConfig(
    vocab_size=128256,
    d_model=4096,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    mlp_dim=14336,
    max_seq_len=8192,
    pos="rope",
    norm="rms",
    gated_mlp=True,
    use_bias=False,
    rope_theta=500000.0,
)

# Draft companion for gpt2_medium (ISSUE 13 bench A/B): same vocab and
# position style so its proposals index the target's logit space, ~1/40
# of the FLOPs — the Leviathan-shaped draft geometry. Random-init
# weights make on-chip acceptance ~0 (the captured row then measures the
# bounded-degradation floor, honestly stamped via spec_acceptance);
# trained weights turn the same arm into the speedup measurement.
GPT2_DRAFT = DecoderConfig(
    vocab_size=50257,
    d_model=256,
    num_layers=4,
    num_heads=4,
    num_kv_heads=4,
    mlp_dim=1024,
    max_seq_len=1024,
    pos="learned",
    norm="ln",
    gated_mlp=False,
    use_bias=True,
    tie_embeddings=True,
)

TINY_LM = DecoderConfig(
    vocab_size=512,
    d_model=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    mlp_dim=128,
    max_seq_len=256,
)

TINY_MOE = DecoderConfig(
    vocab_size=512,
    d_model=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    mlp_dim=128,
    max_seq_len=256,
    num_experts=4,
    moe_top_k=2,
)

# allenai/OLMoE-1B-7B-0125-Instruct as published: 64 experts of width
# 1,024, 8 a token with the gates as the softmax gave them, no shared
# expert; MHA 16 x 128 with RMSNorm on the q and k projections.
OLMOE_1B_7B = DecoderConfig(
    vocab_size=50304,
    d_model=2048,
    num_layers=16,
    num_heads=16,
    num_kv_heads=16,
    mlp_dim=1024,
    max_seq_len=4096,
    pos="rope",
    norm="rms",
    gated_mlp=True,
    use_bias=False,
    rope_theta=10000.0,
    num_experts=64,
    moe_top_k=8,
    moe_renormalize=False,
    qk_norm=True,
)


# LGAI-EXAONE/K-EXAONE-236B-A23B as published: layers LLLG (three sliding
# over 128 positions, one full and without positions), GQA 64/8 heads of 128
# (not 6144 / 64), RMSNorm per head on q and k; layer 0 a dense SwiGLU of
# 18,432, the other 47 an expert layer: 128 routed experts of 2,048, 8 a
# token by sigmoid scores plus a selection bias, renormalised, x 2.5, and
# one shared expert. No chip holds one such layer whole: a deployment gives
# each rank ``moe_first_expert`` / ``moe_held_experts`` (benchmark/configs).
# The multi-token-prediction layer is not part of this decoder.
K_EXAONE_236B = DecoderConfig(
    vocab_size=153600,
    d_model=6144,
    num_layers=48,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    mlp_dim=2048,
    max_seq_len=262144,
    pos="rope",
    norm="rms",
    gated_mlp=True,
    use_bias=False,
    rope_theta=1000000.0,
    qk_norm=True,
    qk_norm_per_head=True,
    sliding_window=128,
    layer_pattern="LLLG",
    rope_sliding_only=True,
    num_dense_layers=1,
    dense_mlp_dim=18432,
    num_experts=128,
    moe_top_k=8,
    moe_renormalize=True,
    moe_scoring="sigmoid",
    moe_selection_bias=True,
    moe_gate_scale=2.5,
    moe_shared_experts=1,
)


@register_model("gpt2_medium", slo=ModelSLO(latency_slo_ms=500.0))
def _gpt2_medium(**kwargs) -> CausalLM:
    return CausalLM(GPT2_MEDIUM, name="gpt2_medium", **kwargs)


@register_model("llama3_8b", slo=ModelSLO(latency_slo_ms=150.0))
def _llama3_8b(**kwargs) -> CausalLM:
    return CausalLM(LLAMA3_8B, name="llama3_8b", **kwargs)


@register_model("gpt2_draft")
def _gpt2_draft(**kwargs) -> CausalLM:
    return CausalLM(GPT2_DRAFT, name="gpt2_draft", **kwargs)


@register_model("llama_tiny")
def _llama_tiny(**kwargs) -> CausalLM:
    return CausalLM(TINY_LM, name="llama_tiny", **kwargs)


@register_model("llama_tiny_int8kv")
def _llama_tiny_int8kv(**kwargs) -> CausalLM:
    """llama_tiny with the int8 KV cache — a DISTINCT registry name so
    its decode/prefill tables land beside (not over) the bf16 ones:
    quantized engines must plan from tables measured at their own cache
    dtype (plan_from_tables docstring)."""
    kwargs.setdefault("kv_dtype", jnp.int8)
    return CausalLM(TINY_LM, name="llama_tiny_int8kv", **kwargs)


@register_model("moe_tiny")
def _moe_tiny(**kwargs) -> CausalLM:
    return CausalLM(TINY_MOE, name="moe_tiny", **kwargs)


@register_model("olmoe_1b_7b", slo=ModelSLO(latency_slo_ms=1000.0))
def _olmoe_1b_7b(**kwargs) -> CausalLM:
    return CausalLM(OLMOE_1B_7B, name="olmoe_1b_7b", **kwargs)


@register_model("k_exaone_236b", slo=ModelSLO(latency_slo_ms=1000.0))
def _k_exaone_236b(**kwargs) -> CausalLM:
    return CausalLM(K_EXAONE_236B, name="k_exaone_236b", **kwargs)
