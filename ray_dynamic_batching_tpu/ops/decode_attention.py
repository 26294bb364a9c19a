"""Pallas TPU decode attention — the KV-scan kernel for small query
windows (plain decode Tq == 1, speculative-verify Tq == k+1, small
prefill buckets).

Decode is HBM-bandwidth-bound: every substep reads the full KV capacity
(static shapes — see ``serve/llm.py``'s capacity-bucket rationale) to
produce one token per slot. The XLA fallback pays two avoidable HBM
costs on that scan (``ops/attention.py::_xla_attention``):

- **GQA materialization**: ``jnp.repeat`` expands K/V to the full query
  head count before the einsum — N/K fresh copies of the cache read
  land in HBM every substep (llama-3 geometry: 4x).
- **Logit round-trip**: the [B, N, Tq, S] f32 logits + softmax
  intermediates materialize between two einsums instead of living in
  VMEM.

This kernel fuses the scan FlashAttention-style over a grid
(B, K // kb, S // Sb): each program instance owns one slot's block of
``kb`` KV heads for one [Sb] KV tile. The S grid axis IS the KV tiling:
TPU grid steps run sequentially with the innermost axis fastest, so the
online-softmax state (m, l, acc) lives in VMEM scratch carried across
the S steps of each (slot, head-block) — initialized at s == 0,
finalized into the output at the last tile — while Pallas pipelines the
next tile's HBM->VMEM copy behind the current tile's compute. Every
[Sb, H] K/V slab is read exactly once (all Tq window rows and all
G = N/K query heads sharing a KV head ride the same read) — GQA via
layout, no repeat, any capacity.

The PAGED kernel (``_paged_decode_attention``) has no tile axis in its
grid, which is (B, K // kb): a slot's pages are walked by a LOOP inside
the step, over the table columns its length makes live
(``tile_math.live_pages``), a page a tile, each copied by the kernel
itself out of the pool in HBM into a small VMEM ring that runs on from
one step into the next. A table entry past the length, or behind a
sliding layer's window, costs nothing: no step, no copy, no arithmetic.
Measured on a v5e (PERF.md, PR 33): a live page 0.69 us against its
0.64 us copy, a step about nothing beyond its pages, where the grid
that walked the whole table paid 0.85 us a live step and 0.27 a dead
one.

The fold of one tile (``_accumulate_tile``, shared by the slab kernel's
grid step and the paged kernel's loop) takes one of two forms, chosen
from the tile's static shape alone (``tile_math.flat_heads``):

- **flat heads** (a block of 8 KV heads; score tiles within
  ``tile_math.FLAT_SCORE_MAX_BYTES``): the tile arrives as [Sb, kb, H],
  whose trailing (kb, H) dims are whole (8, 128) tiles, so [Sb * kb, H]
  is the same bytes — column c is position c // kb of head c % kb. The
  block's kb * R query rows (q, out and the f32 state are laid out
  [kb * R, ...], rows ordered head, t, g) are scored against all
  Sb * kb columns in ONE contraction; row i keeps only the columns of
  its own head i // R, then one softmax update and one value
  contraction. The MXU does kb times the useful multiply-adds of a
  step that is memory-bound anyway; what the body no longer pays for
  is eight sublane-strided head slices, sixteen one-to-four-row dots
  and two dozen small scratch updates a step. Measured on a v5e
  (PERF.md, PR 31): a live page of the paged kernel 1.75 -> 0.85 us
  against a 0.64 us copy, and cheaper than the per-head form at every
  row count tried (1 to 32 a head).
- **per head** (K < 8, or tiles past the cap): each head's [Sb, H]
  slice against its own R rows, state [kb, R].

Two TPU lowering rules shape the blocking (trailing two block dims must
be (8, 128)-tile-aligned or span the array):

- K/V live as [B, S, K, H], so a one-head block (trailing dims (1, H))
  is illegal — heads move in blocks of ``kb`` (8 when K divides into
  8-groups, else all of K). A layout transpose instead would
  materialize a full KV-cache copy every substep, which is the exact
  HBM cost this kernel exists to avoid.
- The [B, Tq, S] mask's trailing dim is the S tile, so Sb must be a
  multiple of 128 or span S (``_pick_sb``).
Large prefill tiles stay on the flash kernel
(``ops/flash_attention.py``); this covers the decode half (the
reference has no decode engine to compare against — its
serving path is fixed-shape vision forwards,
``293-project/src/scheduler.py:435-452``).

Masking: windows arrive as a [B, 1, Tq, S] boolean (True = attend —
``models/decoder.py::decode_mask`` for Tq == 1, ``verify_step``'s
per-row scatter windows for the speculative path), streamed as int8
[Tq, S] per row — Tq bytes per KV position vs the 2H-byte K/V read they
gate.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_dynamic_batching_tpu.ops import tile_math
from ray_dynamic_batching_tpu.ops.pallas_common import (
    declined,
    resolve_interpret,
)
from ray_dynamic_batching_tpu.ops.tile_math import (
    VMEM_BLOCK_BUDGET_BYTES,
    VMEM_LIMIT_BYTES,
)
from ray_dynamic_batching_tpu.utils.compile_ledger import current_program

# Grid (slot, head block, KV tile): the KV axis carries the
# online-softmax scratch, so it is sequential; the scoped-VMEM limit is
# the one the tile budget was sized against (ops/tile_math.py).
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES,
)

NEG_INF = -1e30

# Windows past this ride the flash kernel (>= 16) or XLA (9..15): wide
# windows are prefill-shaped work where the flash kernel's query-tiled
# grid wins; this kernel's per-program q/scratch footprint grows with
# window * G.
MAX_WINDOW_FOR_KERNEL = 8


FORM_FLAT = "flat heads"
FORM_PER_HEAD = "per head"
WALK_LOOP = "a loop over the live pages"


@dataclasses.dataclass(frozen=True)
class DecodePath:
    """The body one paged-kernel call took, recorded while its program
    traced (as ``ops/moe.py::moe_paths`` records the expert path)."""

    program: str     # compile-ledger program ("" outside one)
    kb: int          # KV heads a tile (a TP shard's block)
    rows: int        # query rows a head: window * G
    page_size: int
    head_dim: int    # the pool's row width (lane-padded)
    kv_dtype: str
    form: str        # FORM_*
    why: str
    sliding: int = 0      # a sliding layer's window (0: a full layer)
    table_width: int = 0  # page-table columns a slot's walk can reach
    walk: str = ""        # WALK_*: how the pages of a slot are walked
    depth: int = 0        # pages in the walk's VMEM ring
    kv_heads: int = 0     # the pool's KV heads (a layer kind's own)
    v_dim: int = 0        # a v row's width where not the k row's
    sink: bool = False    # a learned sink joins the sum at the scan's end
    heads_per_row: int = 1  # KV heads side by side in a pool row
    pages: int = 1        # live pages an online-softmax update (_walk)

    def describe(self) -> str:
        return ((f"{self.heads_per_row} heads a pool row: "
                 if self.heads_per_row > 1 else "")
                + f"{self.kb} heads x {self.rows} rows over "
                f"[{self.page_size}, {self.kb}, {self.head_dim}] "
                f"{self.kv_dtype} pages -> {self.form} ({self.why}); "
                f"{self.walk}, {self.pages} "
                f"page{'s' if self.pages > 1 else ''} a fold, a ring of "
                f"{self.depth}, of "
                + (f"window {self.sliding}: the " if self.sliding else "")
                + f"{self.table_width} table columns a slot"
                + (f"; v rows {self.v_dim} wide" if self.v_dim else "")
                + ("; a sink" if self.sink else ""))


# Bounded, trace-time only: a call a layer of each traced program.
_PATHS: collections.deque = collections.deque(maxlen=4096)


def decode_paths() -> List[DecodePath]:
    """The recorded paged-kernel calls, oldest first."""
    return list(_PATHS)


def clear_decode_paths() -> None:
    _PATHS.clear()


def _record_path(kb: int, rows: int, ps: int, H: int, dtype,
                 sliding: int, table_width: int, depth: int,
                 fold: int = 1, pages: int = 1, **kind) -> None:
    if tile_math.flat_heads(kb, rows, ps):
        form, why = FORM_FLAT, (
            f"{kb * rows} rows x {ps * kb} columns in one contraction")
    elif kb % 8:
        form, why = FORM_PER_HEAD, (
            f"a {kb}-head block is no whole (8, 128) tile")
    else:
        form, why = FORM_PER_HEAD, (
            f"flat score tiles of {kb * rows} rows x {ps * kb} columns "
            f"pass {tile_math.FLAT_SCORE_MAX_BYTES >> 20} MiB")
    if fold > 1:    # _narrow_fold
        form, why = FORM_FLAT, (
            f"a {kb}-head page read as {kb * fold}-row tiles: {kb * rows} "
            f"rows x {ps * kb} columns in one contraction")
    _PATHS.append(DecodePath(
        program=current_program(), kb=kb, rows=rows, page_size=ps,
        head_dim=H, kv_dtype=str(jnp.dtype(dtype)), form=form, why=why,
        sliding=sliding, table_width=table_width, walk=WALK_LOOP,
        depth=depth, pages=pages, **kind))


def _window_rows(mask_ref, rows: int, R: int, window: int):
    """The slab kernel's streamed int8 window [Tq, cols] as one boolean
    row per query row: row ``i`` of ``rows`` (R of them, or the block's
    kb * R in the flat form, whose heads share the window) is window
    row ``(i % R) // G`` — g shares t's window."""
    # Widen the streamed int8 to 32 bits BEFORE any comparison and pick
    # each row's window with iota selects: Mosaic refuses to carry an i1
    # vector born from an 8-bit tile into the f32 select below
    # ("changeBitwidth when src bitwidth and dst bitwidth differs too
    # much", TPU v5e, Tq*G rows with G > 1), and a [1, cols] row
    # broadcast over sublanes is a layout it always has.
    m32 = mask_ref[0, :, :].astype(jnp.int32)  # [Tq, cols]
    cols = m32.shape[1]
    t_of_row = (jax.lax.broadcasted_iota(
        jnp.int32, (rows, cols), 0) % R) // (R // window)
    picked = jnp.zeros((rows, cols), jnp.int32)
    for t in range(window):  # static unroll: window <= 8
        picked = jnp.where(t_of_row == t, m32[t:t + 1, :], picked)
    return picked != 0


def _decode_kernel(
    q_ref,      # [1, kb*R, H]       rows ordered (head, t, g); R = Tq*G
    k_ref,      # [1, Sb, kb, H]     this grid step's KV tile
    v_ref,      # [1, Sb, kb, H]
    mask_ref,   # [1, Tq, Sb] int8 ([1, Tq, Sb*kb] flat form), or None
    ks_ref,     # f32 per-row K scales (int8 cache), or None: [1, kb, Sb],
    vs_ref,     # in the flat form [1, 1, 1, 1, Sb*kb] (``_flat_columns``)
    o_ref,      # [1, kb*R, H]
    m_ref,      # VMEM scratch f32 [kb*R, 1] (per head: [kb, R]) —
    l_ref,      # carried across S steps
    acc_ref,    # VMEM scratch [kb*R, H] f32
    *,
    scale: float,
    num_s: int,
    window: int,
):
    Sb, kb = k_ref.shape[1], k_ref.shape[2]
    R = q_ref.shape[1] // kb
    # Head-invariant per-tile validity: every head block shares the
    # per-(t, g)-row window. Sb divides S (``_pick_sb``), so there is no
    # ragged tail to mask. The flat form's mask arrives in its column
    # order (each position kb times), one row per row of the block.
    valid = None
    if mask_ref is not None:
        flat = tile_math.flat_heads(kb, R, Sb)
        valid = _window_rows(mask_ref, kb * R if flat else R, R, window)
    _scan_tile(
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
        acc_ref, valid=valid, scale=scale, num_s=num_s,
    )


def _scan_tile(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
    *, valid, scale: float, num_s: int,
):
    """One KV tile of the slab kernel's online-softmax scan: init scratch
    at tile 0 (:func:`_scan_begin`), accumulate this tile
    (:func:`_accumulate_tile`), finalize into the output on the last
    tile (:func:`_scan_end`). The paged kernel calls the same three
    pieces round a loop of its own: begin, a fold a live page, end. The
    math being ONE function is what keeps the paged and slab kernels
    numerically identical."""
    pl.when(pl.program_id(2) == 0)(
        lambda: _scan_begin(m_ref, l_ref, acc_ref))
    _accumulate_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                     acc_ref, valid=valid, scale=scale)
    pl.when(pl.program_id(2) == num_s - 1)(
        lambda: _scan_end(o_ref, m_ref, l_ref, acc_ref))


def _scan_begin(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _scan_end(o_ref, m_ref, l_ref, acc_ref, sink_ref=None):
    # A fully-masked row (inactive spec rows are steered out of
    # bounds; their outputs are never consumed) -> zeros, not NaN.
    # ``sink_ref`` (laid out as the state is): a learned sink a row, one
    # more term of the sum and nothing of the accumulator.
    def denominator(at):
        l = l_ref[at]
        if sink_ref is not None:
            l = l + jnp.where(
                l > 0.0, jnp.exp(sink_ref[at] - m_ref[at]), 0.0)
        return jnp.where(l == 0.0, 1.0, l)

    if l_ref.shape[1] == 1:     # flat heads: [kb * R, 1] (and the
        # per-head [kb, R] at R == 1, the same layout and division)
        o_ref[0, :, :] = (
            acc_ref[...] / denominator(...)).astype(o_ref.dtype)
        return
    kb, R = l_ref.shape         # per head: [kb, R]
    for h in range(kb):
        l = denominator((h, slice(None)))
        o_ref[0, h * R:(h + 1) * R, :] = (
            acc_ref[h * R:(h + 1) * R, :] / l[:, None]
        ).astype(o_ref.dtype)


def _softmax_fold(s, v, vs, m_prev, l_prev, acc_prev):
    """One online-softmax update from masked scores ``s`` [n, cols] and
    the values ``v`` [cols, H] they weigh: the new running max, running
    sum and accumulator [n, H]. The state is [n, 1] columns (the flat
    form's scratch) or [n] vectors (a head's row of the per-head
    scratch); ``vs`` [1, cols] is the int8 pool's V scale, riding on p."""
    keep = m_prev.ndim == 2
    col = (lambda x: x) if keep else (lambda x: x[:, None])
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=keep))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - col(m_cur))                              # [n, cols]
    l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=keep)
    if vs is not None:
        p = p * vs
    return m_cur, l_cur, acc_prev * col(alpha) + jax.lax.dot_general(
        p.astype(v.dtype), v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                        # [n, H]


def _accumulate_tile(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref, acc_ref,
    *, valid, scale: float,
):
    """Fold one KV tile [Sb, kb, H] (the slab kernel's grid step, a page
    of the paged kernel's loop) into the online-softmax
    scratch of the block's kb * R query rows. One algorithm in the form
    its shapes allow (``tile_math.flat_heads``):

    - **flat heads**: the tile is read as [Sb * kb, H], the layout it
      arrives in (column ``c`` is position ``c // kb`` of head
      ``c % kb``), and ALL the block's rows are scored against ALL its
      columns in one contraction; row ``i`` keeps only the columns of
      its own head ``i // R`` (the rest go to ``NEG_INF`` before the
      running maximum, so they add exactly 0 to ``l`` and to the value
      dot), then ONE softmax update and ONE value contraction. The MXU
      does kb times the useful multiply-adds of a memory-bound step;
      the body pays for no strided head slice and no one-row dot.
      ``valid`` is [kb * R, Sb * kb], the scales [1, Sb * kb].
    - **per head**: each head's [Sb, H] slice of the tile against its
      own R rows (``valid`` [R, Sb], the scales [kb, Sb]).

    Either way the int8 cache's per-row scales factor OUT of both dots
    — scores scale per key column, and V's scale rides on p — so the
    kernel reads 1-byte codes and never materializes an H-wide
    dequantized tile (the bandwidth win); codes cast exactly (<= +-127).
    """
    Sb, kb, H = k_ref.shape[1], k_ref.shape[2], k_ref.shape[3]
    R = q_ref.shape[1] // kb
    compute_dtype = q_ref.dtype

    def scores(q, k_tile, ks):
        s = jax.lax.dot_general(
            q, k_tile.astype(compute_dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                            # [n, cols]
        return s if ks is None else s * ks

    if tile_math.flat_heads(kb, R, Sb):
        rows, cols = kb * R, Sb * kb
        k_flat = k_ref[0].reshape(cols, H)
        v_flat = v_ref[0].reshape(cols, v_ref.shape[3]).astype(compute_dtype)
        s = scores(q_ref[0], k_flat,
                   None if ks_ref is None else ks_ref[0, 0, 0])
        own = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) % kb
               == jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) // R)
        if valid is not None:
            own = own & valid
        m_ref[...], l_ref[...], acc_ref[...] = _softmax_fold(
            jnp.where(own, s, NEG_INF), v_flat,
            None if vs_ref is None else vs_ref[0, 0, 0],
            m_ref[...], l_ref[...], acc_ref[...])
        return
    for h in range(kb):         # static unroll: this program's KV heads
        rows = slice(h * R, (h + 1) * R)
        s = scores(q_ref[0, rows, :], k_ref[0, :, h, :],
                   None if ks_ref is None else ks_ref[0, h:h + 1, :])
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        m_ref[h, :], l_ref[h, :], acc_ref[rows, :] = _softmax_fold(
            s, v_ref[0, :, h, :].astype(compute_dtype),
            None if vs_ref is None else vs_ref[0, h:h + 1, :],
            m_ref[h, :], l_ref[h, :], acc_ref[rows, :])


def _pick_heads_block(K: int) -> int:
    """Largest-tile-legal KV-head block: trailing-two block dims on the
    [B, S, K, H] cache are (kb, H), so kb must be a multiple of 8 or span
    K exactly (the TPU lowering's divisible-by-(8,128)-or-equal rule)."""
    if K % 8 == 0 and K > 8:
        return 8
    return K


# The VMEM budget and the padded-footprint model live in
# ops/tile_math.py, SHARED with the static vmem-budget checker
# (tools/lint) — one implementation, so the static model and this
# runtime picker cannot drift. H=64 geometries (gpt2_medium,
# llama_tiny, whisper heads) double under 128-lane padding; budgeting
# the raw H undercounted the K/V block ~2x and picked tiles whose true
# double-buffered footprint overran the block budget — the exact bug
# class the shared model (and its lint rule) pins down.


def _pick_sb(S: int, kb: int, H: int, kv_itemsize: int,
             with_mask: bool, target: Optional[int] = None,
             with_scales: bool = False, rows: int = 0) -> int:
    """Largest KV tile Sb that (a) divides S, (b) is mask-tile-legal
    (a multiple of 128, or S itself — the mask block's trailing dim is
    Sb), and (c) fits the VMEM budget with double buffering. A
    ``target`` caps the tile when a legal tile under it exists
    (callers tune pipeline granularity; tests force multi-tile scans
    on small capacities). ``rows`` (Tq * G) budgets each candidate in
    the form its body would take (``tile_math.decode_tile_bytes``)."""
    def tile_bytes(sb: int) -> int:
        return tile_math.decode_tile_bytes(
            sb, kb, H, kv_itemsize, with_mask, with_scales=with_scales,
            rows=rows,
        )

    cands = [S] + [
        sb for sb in range((S // 128) * 128, 127, -128) if S % sb == 0
    ]
    cands = [sb for sb in cands
             if tile_bytes(sb) <= VMEM_BLOCK_BUDGET_BYTES]
    if not cands:
        return 0  # no legal tile: caller declines to XLA
    if target is not None:
        capped = [sb for sb in cands if sb <= target]
        if capped:
            return max(capped)
    return max(cands)


def _flat_columns(x: jax.Array, kb: int, sb: int) -> jax.Array:
    """Per-position planes [lead, S, K] (the int8 cache's scales) in the
    flat form's column order: [lead, K // kb, S // sb, 1, sb * kb],
    whose [.., j, s, 0, :] is tile ``s`` of head block ``j`` as one lane
    row, column ``c`` = position ``c // kb`` of head ``c % kb``. A copy
    of a plane H times smaller than the codes it scales."""
    lead, S, K = x.shape
    x = x.reshape(lead, S // sb, sb, K // kb, kb).transpose(0, 3, 1, 2, 4)
    return x.reshape(lead, K // kb, S // sb, 1, sb * kb)


def _flat_scale_spec(cols: int, index_map) -> pl.BlockSpec:
    """One tile of :func:`_flat_columns`: a [1, cols] lane row, what a
    per-column factor of a [rows, cols] score tile broadcasts from."""
    return pl.BlockSpec(  # rdb-lint: disable=tile-alignment (a [1, cols] f32 lane row pads to 8 sublanes: 32 KB for 4 KB of scales beside a 256 KB code tile; any taller layout would need an in-kernel relayout to lanes)
        (1, 1, 1, 1, cols), index_map)


def _fold_flat(q_ref, k_tile, v_tile, m_ref, l_ref, acc_ref, *, ps: int,
               kb: int, valid, scale: float):
    """:func:`_accumulate_tile`'s flat-heads fold for a head block of any
    width (no scales: a bf16 pool): the page of ``ps`` positions of ``kb``
    heads read as [ps * kb, H] (column ``c`` = position ``c // kb`` of
    head ``c % kb``), every row scored against every column, a row keeping
    its own head's. The tile is [1, ps, kb, H] or the view
    [1, ps // f, kb * f, H] of the same bytes
    (``tile_math.page_view_fold``), whose rows flatten in the same order
    with no relayout. A v row may be narrower than a k row."""
    H = k_tile.shape[-1]
    rows, cols = q_ref.shape[1], ps * kb
    R = rows // kb
    s = jax.lax.dot_general(
        q_ref[0], k_tile[0].reshape(cols, H),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    own = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) % kb
           == jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) // R)
    m_ref[...], l_ref[...], acc_ref[...] = _softmax_fold(
        jnp.where(own & valid, s, NEG_INF),
        v_tile[0].reshape(cols, v_tile.shape[-1]),
        None, m_ref[...], l_ref[...], acc_ref[...])


def _narrow_fold(K: int, kb: int, R: int, ps: int, has_scales: bool) -> int:
    """``f`` > 1 where the paged kernel folds a head block NARROWER than 8
    that is all of K in one contraction (:func:`_fold_flat`), from the
    shapes alone: the per-head fold's strided head slices of a (4, 128)
    tile a position cost 6.6 us a live page on a v5e against the copy's
    0.7 (PERF.md, PR 41). ``f`` is ``tile_math.page_view_fold``: a pool
    whose rows are one lane tile wide is then read through its tile view.
    1 (the form :func:`tile_math.flat_heads` picks) for a block of 8, an
    int8 pool (the fold reads no scales), rows that are not whole
    sublane tiles, and score tiles past the flat form's budget."""
    if (kb == K and not has_scales and (kb * R) % 8 == 0
            and tile_math.flat_heads(8, R, ps)):
        return tile_math.page_view_fold(kb, ps)
    return 1


def _scratch(kb: int, R: int, H: int, flat: bool):
    """The online-softmax state of a block's kb * R rows, f32: running
    max and running sum (one [rows, 1] column for the flat form's one
    update; [kb, R], a row a head, for the per-head form) and the
    accumulator [rows, H]."""
    state = (kb * R, 1) if flat else (kb, R)
    return [
        pltpu.VMEM(state, jnp.float32),
        pltpu.VMEM(state, jnp.float32),
        pltpu.VMEM((kb * R, H), jnp.float32),
    ]


@functools.partial(
    jax.jit, static_argnames=("scale", "sb", "window", "interpret")
)
def _decode_attention(
    q: jax.Array,      # [B, K, Tq*G, H]  rows ordered (t, g)
    k: jax.Array,      # [B, S, K, H]
    v: jax.Array,
    mask: Optional[jax.Array],  # [B, Tq, S] int8, or None
    k_scale: Optional[jax.Array],  # [B, S, K] f32 (int8 cache), or None
    v_scale: Optional[jax.Array],
    *,
    scale: float,
    sb: int,
    window: int,
    interpret: bool,
) -> jax.Array:
    B, K, R, H = q.shape
    S = k.shape[1]
    kb = _pick_heads_block(K)
    num_s = S // sb
    flat = tile_math.flat_heads(kb, R, sb)
    rows_spec = pl.BlockSpec((1, kb * R, H), lambda b, j, s: (b, j, 0))
    in_specs = [
        rows_spec,
        pl.BlockSpec((1, sb, kb, H), lambda b, j, s: (b, s, j, 0)),
        pl.BlockSpec((1, sb, kb, H), lambda b, j, s: (b, s, j, 0)),
    ]
    # A head block's rows are one contiguous [kb * R, H] tile (free: the
    # same bytes).
    args = [q.reshape(B, K * R, H), k, v]
    has_mask = mask is not None
    has_scales = k_scale is not None
    if has_mask:
        # The flat form's columns are (position, head): each position's
        # window byte kb times (Tq * kb bytes a position against the
        # 2 * kb * H-byte K/V read they gate).
        cols = sb * kb if flat else sb
        in_specs.append(
            pl.BlockSpec((1, window, cols), lambda b, j, s: (b, 0, s))
        )
        args.append(jnp.repeat(mask, kb, axis=2) if flat else mask)
    if has_scales and flat:
        scale_spec = _flat_scale_spec(
            sb * kb, lambda b, j, s: (b, j, s, 0, 0))
        in_specs += [scale_spec, scale_spec]
        args += [_flat_columns(k_scale, kb, sb),
                 _flat_columns(v_scale, kb, sb)]
    elif has_scales:
        # Scales travel as [B, K, S]: block (1, kb, sb) has trailing
        # dims (kb -> 8-sublane pad, sb = lane multiple of 128) — pad
        # free. A [B, S, K, 1] layout would be tile-legal but its
        # (kb, 1) trailing dims pad to (8, 128): a ~128x VMEM blowup
        # invisible to export-based lowering tests. The transpose copies
        # only the S*K*4-byte scale plane (<0.1% of the cache read).
        scale_spec = pl.BlockSpec(
            (1, kb, sb), lambda b, j, s: (b, j, s)
        )
        in_specs += [scale_spec, scale_spec]
        args += [k_scale.transpose(0, 2, 1), v_scale.transpose(0, 2, 1)]

    def kernel(q_ref, k_ref, v_ref, *rest):
        idx = 0
        mask_ref = rest[idx] if has_mask else None
        idx += 1 if has_mask else 0
        ks_ref = rest[idx] if has_scales else None
        vs_ref = rest[idx + 1] if has_scales else None
        idx += 2 if has_scales else 0
        o_ref, m_ref, l_ref, acc_ref = rest[idx:idx + 4]
        _decode_kernel(
            q_ref, k_ref, v_ref, mask_ref, ks_ref, vs_ref,
            o_ref, m_ref, l_ref, acc_ref,
            scale=scale, num_s=num_s, window=window,
        )

    return pl.pallas_call(
        kernel,
        grid=(B, K // kb, num_s),
        in_specs=in_specs,
        out_specs=rows_spec,
        out_shape=jax.ShapeDtypeStruct((B, K * R, H), q.dtype),
        scratch_shapes=_scratch(kb, R, H, flat),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*args).reshape(B, K, R, H)


def window_table(page_table: jax.Array, lengths: jax.Array, sliding: int,
                 rows: int, page_size: int):
    """The columns of a slot's page table that a SLIDING layer's ``rows``
    window rows (the first at position ``lengths``) can attend, as a
    narrower table ``[B, tile_math.window_table_width(...)]``, and the
    logical page ``[B]`` of its first column
    (``tile_math.window_first_page``). Columns past the table's end repeat
    its last entry: their positions lie past the capacity, where nothing
    is attended. The gather fallback's view (a chunk's rows), this wide;
    the paged kernel walks the same columns from the table itself
    (``tile_math.live_pages``)."""
    n_entries = page_table.shape[1]
    width = tile_math.window_table_width(sliding, rows, page_size, n_entries)
    first = tile_math.window_first_page(
        lengths.astype(jnp.int32), sliding, page_size)
    cols = first[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(
        page_table, jnp.minimum(cols, n_entries - 1), axis=1), first


@functools.partial(
    jax.jit, static_argnames=(
        "scale", "window", "sliding", "interpret", "pages", "depth"))
def _paged_decode_attention(
    q: jax.Array,          # [B, K, Tq*G, H]  rows ordered (t, g)
    k: jax.Array,          # [L, P, ps, K, H] the STACKED page pool, whole
    v: jax.Array,
    page_table: jax.Array,  # [B, NP] int32, sentinel P
    lengths: jax.Array,     # [B] int32 — row t attends pos <= lengths[b]+t
    layer: jax.Array,       # [1] int32 — which layer of the stack to read
    k_scale: Optional[jax.Array],  # [P, ps, K] f32 (int8 pool), or None
    v_scale: Optional[jax.Array],
    sink: Optional[jax.Array] = None,  # [K, R] f32: a sink a query row
    *,
    scale: float,
    window: int,
    interpret: bool,
    sliding: int = 0,
    pages: int,             # _walk: live pages an online-softmax update,
    depth: int,             # ... and the ring's slots of that many pages
) -> jax.Array:
    B, K, R, H = q.shape
    G = R // window
    P, ps = k.shape[1], k.shape[2]
    Hv = v.shape[-1]            # a v row may be narrower than a k row
    NP = page_table.shape[1]
    kb = _pick_heads_block(K)
    nj = K // kb
    steps = B * nj
    has_scales = k_scale is not None
    # A narrow block that is all of K (:func:`_narrow_fold`) is folded by
    # :func:`_fold_flat`. A pool whose rows are ONE lane tile wide (128) is
    # read through its tile view [L, P, ps // view, kb * view, 128]
    # (``tile_math.page_view_fold``: a reshape XLA takes as a bitcast), so
    # its page arrives as whole (8, 128) tiles; a wider row's view is no
    # bitcast (a position's two lane tiles lie side by side: XLA would
    # copy the pool), so that pool is read as it lies and its page relaid
    # in the fold.
    view = _narrow_fold(K, kb, R, ps, has_scales)
    flat = tile_math.flat_heads(kb, R, ps) or view > 1
    view_k, view_v = (view if H == 128 else 1), (view if Hv == 128 else 1)
    if view > 1:
        k = k.reshape(k.shape[:2] + (ps // view_k, K * view_k, H))
        v = v.reshape(v.shape[:2] + (ps // view_v, K * view_v, Hv))
    ahead = depth - 1
    span = pages * ps           # positions a fold covers
    assert pages == 1 or view > 1, "only _fold_flat folds several pages"

    # The grid is (slot, head block) and nothing else: the PAGES of a slot
    # are a loop inside the step, over the table columns
    # ``tile_math.live_pages`` names from the prefetched length (a full
    # layer: column 0 to the last window row's; a sliding layer: from its
    # window's oldest position), so an entry past the length or behind
    # the window is never read: no step, no copy, no arithmetic, whatever
    # it holds (the sentinel, a page reserved ahead of the length, a freed
    # page's NaN). The pool stays in HBM, WHOLE (the layer is a prefetched
    # scalar, so XLA has no layer slice to materialise in front of the
    # call), and the kernel copies each live page's [ps, kb, H] K and V
    # tiles (and the int8 pool's scale rows) itself into a ring of
    # ``depth`` scratch slots, ``ahead`` copies in flight behind the page
    # being folded.
    #
    # The ring does not stop at a step's edge. The live pages of all
    # (slot, head block) steps are ONE stream, item n in ring slot
    # n % depth; each fold first starts the copy of the item ``ahead``
    # places on, which near a step's end is the NEXT step's first page
    # (its slot, head block and column worked out from the same
    # prefetched tables), so a step finds its first page arriving and not
    # a cold copy's latency. Both grid axes are sequential for that. The
    # cursor (the step and page of the next item to start) and the
    # stream index of this step's first item ride in SMEM across steps.
    #
    # Where the block is narrow and :func:`_walk` says so (``pages`` > 1),
    # the stream's item is a GROUP of ``pages`` consecutive live pages of
    # the slot, one after another in one ring slot, each copied by its own
    # DMA, and a group is ONE :func:`_fold_flat` over ``span`` positions:
    # the update's serial chain (score product, running max, exp, value
    # product, rescale) is paid once a group, ``ceil(count / pages)`` times
    # a slot. A short last group starts NO copy for a page past ``count``:
    # its part of the ring slot keeps an earlier page's finite bytes (the
    # v ring is zeroed once, before the stream's first copy), behind the
    # position bound.
    def bounds(b, len_ref):
        """A slot's first live column, its live pages and its folds."""
        first, count = tile_math.live_pages(
            len_ref[b], window, sliding, ps, NP)
        return first, count, (
            count if pages == 1 else (count + (pages - 1)) // pages)

    def kernel(pt_ref, len_ref, ly_ref, q_ref, k_hbm, v_hbm, *rest):
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
        if has_scales:
            ks_hbm, vs_hbm, *rest = rest
        sink_ref = None
        if sink is not None:
            sink_ref, *rest = rest
        o_ref, k_buf, v_buf, *rest = rest
        if has_scales:
            ks_buf, vs_buf, *rest = rest
        sem, cur, m_ref, l_ref, acc_ref = rest
        s = pl.program_id(0) * nj + pl.program_id(1)

        def copies(t, page, slot, left=None):
            """The copies of step ``t``'s table column ``page`` into ring
            slot ``slot`` (a sentinel or garbage entry clamps to a real
            page; only an idle slot's column 0 can hold one, and the
            length bound masks everything it could contribute), each as
            ``(live, copy)``: ``live`` None where the copy always runs."""
            if pages > 1:
                # The group of columns from ``page`` on (nj == 1, no
                # scales: the narrow arm), page r into its part of the
                # slot; one past ``left``, the slot's live pages from
                # ``page`` on, is not copied.
                out = []
                for r in range(pages):
                    phys = jnp.minimum(
                        pt_ref[t, jnp.minimum(page + r, NP - 1)], P - 1)
                    for n, (hbm, buf) in enumerate(
                            ((k_hbm, k_buf), (v_hbm, v_buf))):
                        rows = buf.shape[1] // pages
                        out.append((r < left if r else None,
                                    pltpu.make_async_copy(
                            hbm.at[ly_ref[0], pl.ds(phys, 1)],
                            buf.at[pl.ds(slot, 1), pl.ds(r * rows, rows)],
                            sem.at[n, slot, r])))
                return out
            b, j = (t, 0) if nj == 1 else (t // nj, t % nj)
            phys = jnp.minimum(pt_ref[b, page], P - 1)
            heads = (slice(None) if nj == 1
                     else pl.ds(pl.multiple_of(j * kb, kb), kb))
            pairs = [(hbm.at[ly_ref[0], pl.ds(phys, 1), :, heads, :], buf)
                     for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf))]
            if has_scales and flat:
                pairs += [(hbm.at[pl.ds(phys, 1), pl.ds(j, 1)], buf)
                          for hbm, buf in ((ks_hbm, ks_buf),
                                           (vs_hbm, vs_buf))]
            elif has_scales:
                pairs += [(hbm.at[pl.ds(phys, 1), heads, :], buf)
                          for hbm, buf in ((ks_hbm, ks_buf),
                                           (vs_hbm, vs_buf))]
            return [(None, pltpu.make_async_copy(
                src, buf.at[pl.ds(slot, 1)], sem.at[n, slot]))
                for n, (src, buf) in enumerate(pairs)]

        def each(pairs, act: str):
            """``copy.start()`` / ``copy.wait()`` of every live copy."""
            for live, c in pairs:
                run = getattr(c, act)
                run() if live is None else pl.when(live)(run)

        def start_next(t, i, slot):
            """Start the copies of the cursor's item (step ``t``, the
            ``pages`` live pages from its ``i``-th on) if there is one, and
            move the cursor on."""
            t_in = jnp.minimum(t, steps - 1)
            first, count, _ = bounds(
                t_in if nj == 1 else t_in // nj, len_ref)

            @pl.when(t < steps)
            def _start():
                each(copies(t_in, first + i, slot,
                            count - i if pages > 1 else None), "start")

            roll = i + pages >= count
            return (jnp.where(roll, t + 1, t),
                    jnp.where(roll, 0, i + pages))

        @pl.when(s == 0)
        def _stream_begins():
            if pages > 1:   # a part of a slot no copy has reached is read
                v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
            cursor = (jnp.int32(0), jnp.int32(0))
            for n in range(ahead):
                cursor = start_next(*cursor, n)
            cur[0] = 0
            cur[1], cur[2] = cursor

        b = pl.program_id(0)
        first, count, folds = bounds(b, len_ref)
        base = cur[0]
        _scan_begin(m_ref, l_ref, acc_ref)

        def fold(i, cursor):
            cursor = start_next(*cursor, (base + i + ahead) % depth)
            at = i if pages == 1 else i * pages     # the item's first page
            page, slot = first + at, (base + i) % depth
            each(copies(s, page, slot, count - at if pages > 1 else None),
                 "wait")
            # In-kernel STAIRCASE validity from the prefetched lengths:
            # page p covers logical positions [p*ps, (p+1)*ps); window
            # row t (row r = t*G + g of its head) attends pos <=
            # lengths[b] + t — the spec-verify window rule, whose
            # Tq == 1 degenerate case is exactly the slab decode_mask
            # bound. No mask array is streamed at all. In the flat form
            # a column is (position, head) and a row (head, t, g).
            shape = (kb * R, span * kb) if flat else (R, ps)
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            pos = page * ps + (col // kb if flat else col)
            t_of_row = (jax.lax.broadcasted_iota(
                jnp.int32, shape, 0) % R) // G
            bound = len_ref[b] + t_of_row
            # (a short last group's tail lies past every row's bound, or,
            # where a window row's bound passes the table's end, past the
            # table: the walk of single pages stops there too)
            valid = pos <= (bound if pages == 1 else jnp.minimum(
                bound, (first + count) * ps - 1))
            if sliding:
                # the lower edge (``models/decoder.py::sliding_edge``)
                valid = valid & (pos > bound - sliding)
            tile = lambda buf: (
                None if buf is None else buf.at[pl.ds(slot, 1)])
            if view > 1:
                _fold_flat(q_ref, tile(k_buf), tile(v_buf), m_ref, l_ref,
                           acc_ref, ps=span, kb=kb, valid=valid,
                           scale=scale)
                return cursor
            _accumulate_tile(
                q_ref, tile(k_buf), tile(v_buf), tile(ks_buf), tile(vs_buf),
                m_ref, l_ref, acc_ref, valid=valid, scale=scale,
            )
            return cursor

        cur[1], cur[2] = jax.lax.fori_loop(
            0, folds, fold, (cur[1], cur[2]))
        cur[0] = (base + folds) % depth
        _scan_end(o_ref, m_ref, l_ref, acc_ref, sink_ref)

    a_block = lambda b, j, pt, ln, ly: (b, j, 0)  # noqa: E731
    rows_spec = pl.BlockSpec((1, kb * R, H), a_block)
    out_spec = pl.BlockSpec((1, kb * R, Hv), a_block)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    # A head block's rows are one contiguous [kb * R, H] tile (free: the
    # same bytes).
    args = [q.reshape(B, K * R, H), k, v]
    ring = [pltpu.VMEM((depth, span // view_k, kb * view_k, H), k.dtype),
            pltpu.VMEM((depth, span // view_v, kb * view_v, Hv), v.dtype)]
    if has_scales and flat:
        # A page's scales as ONE lane row in the flat column order.
        args += [_flat_columns(k_scale, kb, ps),
                 _flat_columns(v_scale, kb, ps)]
        ring += [pltpu.VMEM((depth, 1, 1, 1, ps * kb), jnp.float32)] * 2
    elif has_scales:
        # [P, ps, K] -> [P, K, ps]: the page becomes the (lane) trailing
        # dim of the scale tile — pad-free because pages are lane-aligned
        # (the [B, S, K, 1]-layout ~128x blowup documented on the slab
        # path is the same trap this transpose avoids).
        args += [k_scale.transpose(0, 2, 1), v_scale.transpose(0, 2, 1)]
        ring += [pltpu.VMEM((depth, kb, ps), jnp.float32)] * 2
    in_specs = [rows_spec] + [in_hbm] * (len(args) - 1)
    if sink is not None:
        # laid out as the softmax state is: a column a row of the flat
        # form, [kb, R] a head block of the per-head form
        state = (kb * R, 1) if flat or R == 1 else (kb, R)
        args.append(sink.reshape(K * R // state[1], state[1]))
        in_specs.append(pl.BlockSpec(
            state, lambda b, j, pt, ln, ly: (j, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nj),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=ring + [
            pltpu.SemaphoreType.DMA(
                (len(ring), depth) + ((pages,) if pages > 1 else ())),
            pltpu.SMEM((3,), jnp.int32),
        ] + _scratch(kb, R, Hv, flat),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K * R, Hv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(page_table, lengths, layer, *args).reshape(B, K, R, Hv)


def paged_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    page_table: jax.Array,
    kv_lengths: jax.Array,
    *,
    layer: int = 0,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    mesh: Optional[Any] = None,
    mesh_axis: str = "tp",
    why: Optional[List[str]] = None,
    sliding: int = 0,
    sink: Optional[jax.Array] = None,
    v_dim: int = 0,
    heads_per_row: int = 1,
) -> Optional[jax.Array]:
    """Fused page-table decode attention; returns None when the shapes
    aren't the paged decode pattern (caller falls back to the explicit
    gather — same decline contract as :func:`decode_attention`), with
    the reason appended to ``why``.

    q [B, Tq, N, H] with Tq <= MAX_WINDOW_FOR_KERNEL; k/v the STACKED
    page pools [L, P, ps, K, H], passed WHOLE, with ``layer`` naming the
    layer to read (an index into the kernel's block map — never slice
    the pool for this call: a Mosaic operand is a buffer, so XLA would
    materialise the slice, a layer-sized copy per layer per substep). A
    single layer's [P, ps, K, H] pool is served as a one-layer stack
    (``k[None]``, layer 0 — a free reshape). K divides N; page_table
    [B, NP] int32 (sentinel P = unallocated); kv_lengths [B]. Window row
    t attends logical positions <= kv_lengths[b] + t — the STAIRCASE
    rule of the speculative-verify window
    (``models/decoder.py::paged_window_mask`` owns it), whose Tq == 1
    case is exactly the plain-decode ``decode_mask`` bound. The scan is
    a loop over the slot's LIVE pages and stops there: a table entry
    past ``(kv_lengths[b] + Tq - 1) // ps`` is never visited, whatever it
    holds, so a slot's scan costs what its live KV costs, not what the
    table is wide.
    ``k_scale``/``v_scale`` [P, ps, K] (this layer's planes, H times
    smaller than the codes) enable the int8-pool path. ``sliding`` > 0 is
    a sliding-window layer: row t attends the last ``sliding`` of those
    positions only, and the loop is bounded from BELOW too — it starts
    at the column of the window's oldest position
    (``tile_math.live_pages``: at most ``window_table_width`` columns, 2
    for a window of one page), so it costs what the window costs, flat
    in the slot's length.

    Eligibility is the lane-alignment + VMEM-budget contract of
    ``ops/tile_math.py``: the page IS the KV tile, so the least ring of
    them the walk needs (``paged_tile_bytes``: a page folding, a page
    arriving) must fit the shared budget (the ring it takes,
    ``paged_walk_depth``, is the deepest up to three that does; where the
    head block is narrow an item of the ring is several pages, folded in
    one update: :func:`_walk`), and
    the page size must be a 128-lane multiple (the int8 scale tile's
    lane dim is the page). The static ``vmem-budget`` lint rule holds
    the call's scratch to the same budget.

    ``mesh`` (a TP serving slice; ROADMAP item 2) runs the SAME kernel
    per shard under ``shard_map`` over ``mesh_axis``: q and the pools
    split on the kv-head axis (the slab TP layout — pages are
    shard-invariant, so the page table and lengths replicate), each
    shard scans its own head slice with the same ``_accumulate_tile``
    body, and the VMEM guard budgets the PER-SHARD block
    (``tile_math.shard_heads`` — a head-sharded kernel's bytes divide
    by the TP degree). Declines (None) when the head axis does not
    divide — replicated heads fall back to the gather path, which GSPMD
    partitions from the pool's NamedSharding.

    ``v_dim`` > 0 (a model with state by layer kind) is the value head's
    width where it is not the key's: the v pool's rows may then be
    narrower than the k pool's (their own lane-padded width: the ring of v
    tiles, the accumulator and the output are that wide), and the output
    is ``v_dim`` wide. ``sink`` [N] float32 is a learned sink a query
    head: ``exp(sink - m)`` joins the softmax's sum when a slot's scan
    ends and adds nothing to the accumulator.

    ``heads_per_row`` = ``f`` > 1: the pools are ``[L, P, ps, K // f,
    f * H]``, row ``r`` of a position holding KV heads ``r * f .. r * f +
    f - 1`` side by side (``models/decoder.py::pool_heads_per_row``). To
    the kernel that is a GQA pool of ``K // f`` heads x ``f * H`` with
    ``f * G`` query rows a head: query head ``n``'s values go into the
    lane segment of ITS KV head (``(n // G) % f``) of a row otherwise
    zeros, which add nothing to a score; ``p . v`` over the whole row
    gives every segment's values and the row's own segment is taken.
    The same body, no other arm: 16 x 64 is walked as 8 x 128.
    """
    if k.ndim == 4 and v.ndim == 4:
        k, v = k[None], v[None]  # one layer's pool: a one-layer stack
    if q.ndim != 4 or k.ndim != 5:
        return declined(why, "paged kernel: q is not rank 4 or the pool "
                             "is not [L, P, ps, K, H]")
    B, Tq, N, H = q.shape
    if not (1 <= Tq <= MAX_WINDOW_FOR_KERNEL):
        # Wide windows are prefill-shaped: gather, then the flash kernel.
        return declined(
            why, f"paged kernel: window Tq={Tq} > "
            f"{MAX_WINDOW_FOR_KERNEL} is prefill-shaped")
    f, seg = heads_per_row, None
    if f > 1:
        if (k.shape[-1] != f * H or sink is not None or v_dim
                or k_scale is not None):
            return declined(
                why, f"paged kernel: {f} heads a pool row, and the row is "
                "not f heads wide, or a sink, a narrower v row or scale "
                "planes came with it")
        # [.., n, H] -> [.., n, f * H]: a head's values in the segment of
        # ITS KV head, zeros in the others (the mask is made of shapes: a
        # constant the program folds).
        scale = scale if scale is not None else H ** -0.5
        seg = jnp.arange(N) // max(1, N // (k.shape[-2] * f)) % f
        q = jnp.where(seg[:, None, None] == jnp.arange(f)[:, None],
                      q[:, :, :, None, :], 0).reshape(B, Tq, N, f * H)
        H = f * H
    L, P, ps, K, Hk = k.shape
    if not 0 <= layer < L:
        raise ValueError(f"layer {layer} is not in a stack of {L}")
    # Pool rows may be wider than the head: lane-padded with zeros
    # (models/decoder.py::pool_head_dim). q is padded to match below and
    # the output cut back; zeros add nothing to a score or an output.
    if Hk < H or v.shape[:-1] != k.shape[:-1] or K == 0 or N % K != 0 or (
            v.shape[-1] != Hk and not v_dim) or v.shape[-1] < v_dim:
        return declined(
            why, f"paged kernel: q heads {N}x{H} do not group over "
            f"pool heads {K}x{Hk}")
    if page_table.ndim != 2 or page_table.shape[0] != B:
        return declined(why, "paged kernel: page table is not [B, NP]")
    if kv_lengths.shape != (B,):
        return declined(why, "paged kernel: kv_lengths is not [B]")
    if (k_scale is None) != (v_scale is None):
        return declined(why, "paged kernel: one of k_scale/v_scale only")
    if k_scale is not None and (
            k_scale.shape != (P, ps, K) or v_scale.shape != (P, ps, K)):
        return declined(why, "paged kernel: scale planes are not "
                             "[P, ps, K]")
    if not tile_math.lane_aligned_page(ps):
        return declined(
            why, f"paged kernel: page size {ps} is not a 128-lane "
            "multiple")
    tp = 1
    if mesh is not None:
        tp = int(mesh.shape.get(mesh_axis, 1))
        if tp > 1 and (K % tp != 0 or N % tp != 0):
            # Heads replicate under this mesh: gather path.
            return declined(
                why, f"paged kernel: heads {N}/{K} do not divide over "
                f"tp={tp}")
    # Per-shard footprint: each shard owns K/tp kv heads, so the guard
    # budgets the block the kernel will ACTUALLY stream on one core.
    k_local = tile_math.shard_heads(K, tp)
    kb = _pick_heads_block(k_local)
    G, G0 = _group(N // K, kb, k_local, Tq, k_scale, sink, v_dim, tp), N // K
    if tile_math.paged_tile_bytes(
            ps, kb, Hk, k.dtype.itemsize,
            with_scales=k_scale is not None,
            # G is shard-invariant: a shard keeps N/tp query per K/tp kv
            # heads, so each head block still carries Tq*G window rows.
            window=Tq, G=G,
    ) > VMEM_BLOCK_BUDGET_BYTES:
        # Page too fat for VMEM double-buffering: gather path.
        return declined(
            why, f"paged kernel: page tile (ps={ps}, kb={kb}, H={Hk}) "
            "exceeds the VMEM block budget")
    interpret = resolve_interpret(interpret)
    kind = {}
    if v_dim or sink is not None:
        if tp > 1 or k_scale is not None:
            return declined(why, "paged kernel: a sink or a narrower v row "
                                 "under a mesh or over an int8 pool")
        kind = dict(kv_heads=K, v_dim=int(v.shape[-1]),
                    sink=sink is not None)
    if f > 1:
        kind = dict(heads_per_row=f)
    fold = _narrow_fold(k_local, kb, Tq * G, ps, k_scale is not None)
    pages, depth = _walk(fold, ps, kb, Hk, k.dtype.itemsize,
                         k_scale is not None, Tq, G)
    _record_path(kb, Tq * G, ps, Hk, k.dtype, int(sliding),
                 tile_math.window_table_width(
                     int(sliding), Tq, ps, page_table.shape[1]),
                 depth, fold=fold, pages=pages, **kind)
    scale = scale if scale is not None else H ** -0.5
    # Rows ordered (t, g) per kv head: [B, Tq, K, G, H] ->
    # [B, K, Tq*G, H] (Tq == 1 collapses to the historical layout),
    # zero-padded to the pool's row width.
    q_r = _rows(q.reshape(B, Tq, K, G0, H), G).transpose(
        0, 2, 1, 3, 4).reshape(B, K, Tq * G, H
    )
    if Hk > H:
        q_r = jnp.pad(q_r, ((0, 0), (0, 0), (0, 0), (0, Hk - H)))
    operands = (q_r, k, v, page_table.astype(jnp.int32),
                kv_lengths.astype(jnp.int32),
                jnp.full((1,), layer, jnp.int32), k_scale, v_scale)
    static = dict(scale=float(scale), window=int(Tq),
                  interpret=bool(interpret), sliding=int(sliding),
                  pages=pages, depth=depth)
    if tp > 1:
        out = _paged_decode_attention_tp(mesh, mesh_axis, *operands,
                                         **static)
    elif v_dim or sink is not None:
        if sink is not None:
            # a row's sink is its query head's: rows are (kv head, t, g)
            sink = jnp.broadcast_to(
                sink.astype(jnp.float32).reshape(K, 1, G), (K, Tq, G)
            ).reshape(K, Tq * G)
        out = _paged_decode_attention(*operands, sink, **static)
        H = v_dim or H
    else:
        out = _paged_decode_attention(*operands, **static)
    out = _rows(out[..., :H].reshape(B, K, Tq, G, H), G0).transpose(
        0, 2, 1, 3, 4).reshape(B, Tq, N, H)
    if f == 1:
        return out
    # p . v over a whole row gave every segment's values: a head keeps
    # its own KV head's.
    rows = out.reshape(B, Tq, N, f, H // f)
    out = rows[:, :, :, 0]
    for s in range(1, f):
        out = jnp.where(seg[:, None] == s, rows[:, :, :, s], out)
    return out


def _paged_decode_attention_tp(
    mesh, axis: str, q_r, k, v, page_table, kv_lengths, layer, ks, vs,
    *, scale: float, window: int, interpret: bool, sliding: int = 0,
    pages: int, depth: int,
):
    """The TP wrapper: ``shard_map`` the paged kernel over the mesh's
    ``axis`` with q/pools split on the kv-head dim and the page
    table/lengths/layer replicated (page indices are shard-invariant).
    Each shard's call is the ordinary single-device kernel on its head
    slice — numerics are per-head, so the sharded result is exactly the
    unsharded one re-laid-out."""
    from jax.sharding import PartitionSpec as P

    args = [q_r, k, v, page_table, kv_lengths, layer]
    in_specs = [
        P(None, axis, None, None),   # q rows split by kv head
        P(None, None, None, axis, None),  # k stack: heads split
        P(None, None, None, axis, None),
        P(None, None),               # page table: replica-global
        P(None),                     # lengths: replica-global
        P(None),                     # layer index: replica-global
    ]
    has_scales = ks is not None
    if has_scales:
        args += [ks, vs]
        in_specs += [P(None, None, axis), P(None, None, axis)]

    def local(q_l, k_l, v_l, pt, ln, ly, *rest):
        ks_l = rest[0] if has_scales else None
        vs_l = rest[1] if has_scales else None
        return _paged_decode_attention(
            q_l, k_l, v_l, pt, ln, ly, ks_l, vs_l,
            scale=scale, window=window, interpret=interpret,
            sliding=sliding, pages=pages, depth=depth,
        )

    # check_vma=False: pallas_call declares no varying-axes rule, and
    # every operand's layout over ``axis`` is stated in the specs above.
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, axis, None, None),
        check_vma=False,
    )(*args)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_k: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    why: Optional[List[str]] = None,
) -> Optional[jax.Array]:
    """Fused small-window attention; returns None when the shapes aren't
    the decode pattern (caller falls back to flash/XLA, same contract as
    ``flash_attention.flash_attention``), with the reason appended to
    ``why``.

    q [B, Tq, N, H] with Tq <= MAX_WINDOW_FOR_KERNEL; k/v [B, S, K, H]
    with K dividing N; mask None or broadcastable to [B, 1, Tq, S]
    (True = attend). The KV-head grouping matches ``_xla_attention``'s
    ``jnp.repeat`` semantics: query head n reads kv head n // (N // K).

    ``k_scale``/``v_scale`` [B, S, K] enable the int8-cache path: k/v
    hold codes, the kernel reads 1-byte tiles and applies the per-row
    scales inside the dots (``KVCache`` docstring) — the decode scan's
    bandwidth win.
    """
    if q.ndim != 4 or k.ndim != 4:
        return declined(why, "decode kernel: q/k are not rank 4")
    B, Tq, N, H = q.shape
    _, S, K, _ = k.shape
    if not (1 <= Tq <= MAX_WINDOW_FOR_KERNEL):
        return declined(
            why, f"decode kernel: window Tq={Tq} > "
            f"{MAX_WINDOW_FOR_KERNEL} is prefill-shaped")
    if K == 0 or N % K != 0 or v.shape != k.shape:
        return declined(
            why, f"decode kernel: q heads {N} do not group over kv "
            f"heads {K}")
    if (k_scale is None) != (v_scale is None):
        return declined(why, "decode kernel: one of k_scale/v_scale only")
    if k_scale is not None and (
            k_scale.shape != (B, S, K) or v_scale.shape != (B, S, K)):
        return declined(why, "decode kernel: scale planes are not "
                             "[B, S, K]")
    G = N // K
    if mask is not None:
        if mask.shape[-1] != S:
            return declined(why, "decode kernel: mask does not span S")
        try:
            mask = jnp.broadcast_to(
                mask, (B, 1, Tq, S)
            ).reshape(B, Tq, S).astype(jnp.int8)
        except (TypeError, ValueError):
            # e.g. a per-head [B, N, Tq, S] mask: not this kernel's
            # pattern — decline so the caller falls back to XLA, which
            # handles arbitrary masks.
            return declined(
                why, f"decode kernel: mask {mask.shape} is not "
                "head-invariant")
    interpret = resolve_interpret(interpret)
    # KV tile: must divide S (a ragged tile's block would clamp and
    # re-read shifted rows), be mask-tile-legal, and fit VMEM
    # double-buffered. 0 = no legal tile (pathological S) -> XLA.
    sb = _pick_sb(S, _pick_heads_block(K), H, k.dtype.itemsize,
                  mask is not None, target=block_k,
                  with_scales=k_scale is not None, rows=Tq * G)
    if sb == 0:
        return declined(
            why, f"decode kernel: no KV tile of S={S} is a 128-multiple "
            "divisor that fits the VMEM block budget")
    scale = scale if scale is not None else H ** -0.5
    # Rows ordered (t, g) per kv head: [B, Tq, K, G, H] -> [B, K, Tq*G, H].
    q_r = q.reshape(B, Tq, K, G, H).transpose(0, 2, 1, 3, 4).reshape(
        B, K, Tq * G, H
    )
    out = _decode_attention(
        q_r, k, v, mask, k_scale, v_scale,
        scale=float(scale), sb=int(sb), window=int(Tq),
        interpret=bool(interpret),
    )
    return out.reshape(B, K, Tq, G, H).transpose(0, 2, 1, 3, 4).reshape(
        B, Tq, N, H
    )


def _walk(fold: int, ps: int, kb: int, H: int, itemsize: int,
          has_scales: bool, window: int, G: int):
    """``(pages a fold, ring slots of that many pages)`` of the paged
    kernel's walk at these shapes: what it traces (its static arguments)
    and what ``decode_paths()`` says. ``fold`` is :func:`_narrow_fold`'s:
    only the narrow arm's fold takes several pages
    (``tile_math.paged_fold_pages``)."""
    pages = tile_math.paged_fold_pages(
        ps, kb, H, itemsize, window, G, narrow=fold > 1)
    return pages, tile_math.paged_walk_depth(
        ps, kb, H, itemsize, has_scales, window, G, pages)


def _group(G: int, kb: int, k_local: int, Tq: int, k_scale, sink, v_dim,
           tp: int) -> int:
    """The query rows a KV head that the kernel is GIVEN: ``G``, or ``G``
    padded with rows of zeros where that alone keeps a narrow head block
    (fewer than 8 pool rows a position that are all of K, a float pool) off
    the flat fold (:func:`_narrow_fold` wants ``kb * Tq * G`` rows in whole
    sublane tiles): FIVE query heads a KV head over 4 KV heads (Falcon-H1's
    GQA 20/4) are 20 rows, and the per-head form they would fall to costs
    6.6 us a live page against 0.35 (PERF.md, PRs 41 and 52); as 4 x 6 they
    fold flat. A zero row scores 0 everywhere, costs nothing the copy does
    not hide, and its output is cut. A group that is a power of two (every
    other configuration's, and the tests' small ones, whose few rows of one
    or two heads the per-head form reads as it always did) is returned as
    it is: their programs do not change. Here at the file's END, and called from
    lines that were there: a Mosaic module carries its callers' source
    lines (PERF.md, section 7)."""
    from math import gcd

    if (G & (G - 1) == 0 or kb != k_local or kb >= 8 or tp > 1
            or (kb * Tq * G) % 8 == 0
            or k_scale is not None or sink is not None or v_dim):
        return G
    step = 8 // gcd(kb * Tq, 8)
    return -(-G // step) * step


def _rows(x: jax.Array, G: int, axis: int = 3) -> jax.Array:
    """``x`` ``[B, .., G0, H]`` with ``axis`` padded with zeros up to ``G``
    rows, or cut back to ``G``; ``x`` itself where it already has ``G``."""
    have = x.shape[axis]
    if have == G:
        return x
    if have > G:
        return jax.lax.slice_in_dim(x, 0, G, axis=axis)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, G - have)
    return jnp.pad(x, pad)
