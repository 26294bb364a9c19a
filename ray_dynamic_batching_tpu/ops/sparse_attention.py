"""Attention over a LEARNED selection of the cache (a DeepSeek-Sparse-
Attention style indexer): every cached position keeps ONE index key beside
its k and v, a query's ``n`` index heads score all of them,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) * (Hi * n) ** -0.5

in float32, and the query attends only the ``topk`` positions ``s <= t``
with the largest ``I[t, s]`` (all of them while there are no more; a tie
at the edge goes to the LOWER position), the same set for every head.
The selection is EXACT in every form: nothing here approximates the top-k.

Only a model with an indexer imports this module
(``models/decoder.py::DecoderLayer``, ``ops/attention.py::_paged_attention``
when handed a :class:`Selection`).

Forms of the paged read (``sparse_forms()`` says which each program took):

- the FLOOR, any number of query rows (a chunk, a verify window, decode):
  the slot's index keys are gathered through its page table with its k and
  v, scored, and the selection is ANDed into the staircase mask of the
  gather fallback. Correct everywhere, no kernel of its own; a chunk's 512
  queries share one gathered view.
- a window of several rows over a table wider than ``BLOCK_PAGES`` (a
  chunk of a long prompt): the same arithmetic a block of ``BLOCK_PAGES``
  table columns at a time, scores first and then an online softmax over
  the selected columns, in two loops that STOP at the block of the last
  position a row attends: a chunk at position 4k of an 18k-wide table
  pays for 4k. (The flash kernel keeps a head's whole K and V in VMEM and
  declines 18,432 columns; XLA's einsum over the whole view writes 1.2 GB
  of scores a layer.)
- the MASK form (decode, one query row, wherever its kernel takes the read:
  :func:`_mask_form_declines`): the paged kernel's walk over a slot's
  LIVE pages (``ops/decode_attention.py::_paged_decode_attention``: the ring
  of pages copied out of the pool whole, running on across grid steps) with
  the selection as one more operand, a row of the slot's mask a page, ANDed
  into the length bound before the fold. Reads every live page, gathers
  nothing. A head block narrower than 8 (4 key heads) is read through a
  free view of the pool in which two positions' heads are one (8, 128)
  tile (:func:`_page_fold`), so its page arrives as whole tiles and folds
  in one contraction with no relayout, as a block of 8 heads does; such a
  block folds SEVERAL live pages an online-softmax update
  (:func:`_fold_pages`: the update's serial chain, not the page's copy,
  paced a page a fold).
  A kernel of its own, here, and not an option of the paged
  kernel: a Mosaic module carries its source lines, and a line moved in
  that file compiles every program of every other model again. The fold,
  the softmax and the scratch are that file's own functions.

(A form that gathers the selected rows ONLY is slower below ~30k positions
a slot on a v5e and lives in ``tools/run_kernel_ab.py --sparse``, beside its
reading; ROADMAP.md, M9 (a).)
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_dynamic_batching_tpu.ops import tile_math
from ray_dynamic_batching_tpu.ops.decode_attention import (
    NEG_INF,
    _accumulate_tile,
    _fold_flat,
    _pick_heads_block,
    _scan_begin,
    _scan_end,
    _scratch,
    _softmax_fold,
)
from ray_dynamic_batching_tpu.ops.pallas_common import resolve_interpret
from ray_dynamic_batching_tpu.ops.tile_math import (
    VMEM_BLOCK_BUDGET_BYTES,
    VMEM_LIMIT_BYTES,
)

FORM_FLOOR = "floor"
FORM_MASK = "mask"
# Query rows scored at a time: [B, rows, n, S] float32 head scores exist
# only a block at a time (512 rows x 16 heads x 18k positions would be
# 600 MB).
SCORE_ROWS = 128
# Table columns a block of the blocked window read takes: 16 pages of 128
# are 2,048 positions, [32 heads, 512 rows, 2,048] float32 scores 134 MB.
BLOCK_PAGES = 16


class Selection(NamedTuple):
    """What a selecting layer hands the paged read."""

    q: jax.Array      # [B, T, n, Hi] index queries (rotated)
    w: jax.Array      # [B, T, n] head weights
    pool: jax.Array   # [L, P, ps, Hip] index keys, this call's written
    topk: int         # positions a query keeps


# program -> how its selecting layers' reads ran (trace-time, like
# ``ops/attention.py::attention_paths``).
_FORMS: Dict[str, str] = {}


def sparse_forms() -> List[str]:
    return sorted(f"{prog}: {how}" for prog, how in _FORMS.items())


def _record(how: str) -> None:
    from ray_dynamic_batching_tpu.utils.compile_ledger import current_program

    prog = current_program()
    if prog:
        _FORMS[prog] = how


def index_scores(q_i: jax.Array, w_i: jax.Array, k_i: jax.Array) -> jax.Array:
    """``I`` [B, T, S] float32 from index queries [B, T, n, Hi], head
    weights [B, T, n] and index keys [B, S, Hi] (or the pool's lane-padded
    rows [B, S, Hip]: the queries are then padded to match, zeros adding
    nothing to a dot)."""
    B, T, n, Hi = q_i.shape
    scale = float(Hi * n) ** -0.5
    if k_i.shape[-1] > Hi:
        q_i = jnp.pad(q_i, [(0, 0)] * 3 + [(0, k_i.shape[-1] - Hi)])
        Hi = k_i.shape[-1]

    def block(qw):
        q, w = qw                                  # [B, t, n, Hi], [B, t, n]
        s = jnp.einsum("btnh,bsh->btns", q, k_i,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("btns,btn->bts", jax.nn.relu(s),
                          w.astype(jnp.float32)) * scale

    if T <= SCORE_ROWS or T % SCORE_ROWS:
        return block((q_i, w_i))
    nb = T // SCORE_ROWS
    out = jax.lax.map(block, (
        q_i.reshape(B, nb, SCORE_ROWS, n, Hi).swapaxes(0, 1),
        w_i.reshape(B, nb, SCORE_ROWS, n).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(B, T, -1)


def _order_keys(scores: jax.Array) -> jax.Array:
    """float32 scores as uint32 keys in the same order (no NaN among
    them): the bits, negatives mirrored, the sign bit turned; -0.0 counts
    as +0.0, as it compares."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    return jax.lax.bitcast_convert_type(keys, jnp.uint32) ^ jnp.uint32(
        0x80000000)


def _kth_largest(keys: jax.Array, k: int) -> jax.Array:
    """The k-th largest key of each row [..., 1], exactly and without a
    sort: its 32 bits found from the top down, each by one count of the
    keys at or above the candidate (a row of 18k positions sorts in 1.4
    ms a decode step and 10 ms a chunk on a v5e; 32 counts take a tenth)."""
    def bit(i, found):
        cand = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (keys >= cand).sum(-1, keepdims=True) >= k
        return jnp.where(enough, cand, found)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))


def exact_topk_mask(scores: jax.Array, allowed: jax.Array,
                    topk: int) -> jax.Array:
    """Of each row's ``allowed`` positions the ``topk`` with the largest
    score (all of them where there are no more), as a mask [..., S]. A tie
    at the edge goes to the lower positions, so never more than ``topk``."""
    if topk >= scores.shape[-1]:
        return allowed
    keys = _order_keys(jnp.where(allowed, scores, -jnp.inf))
    kth = _kth_largest(keys, topk)
    # (a row with fewer than topk allowed positions finds -inf's key: its
    # masked positions are no ties)
    above, tie = keys > kth, (keys == kth) & allowed
    room = topk - above.sum(-1, keepdims=True)
    # Only where the edge value repeats beyond the room left does a tie's
    # position matter: the running count is skipped otherwise.
    ties = jax.lax.cond(
        jnp.any(tie.sum(-1, keepdims=True) > room),
        lambda: tie & (jnp.cumsum(tie, axis=-1) <= room),
        lambda: tie)
    return above | ties


def select_mask(q_i: jax.Array, w_i: jax.Array, k_i: jax.Array,
                allowed: jax.Array, topk: int) -> jax.Array:
    """``allowed`` [B, 1, T, S] (the causal or staircase mask) cut to each
    query's selection."""
    with jax.named_scope("sparse_index"):
        scores = index_scores(q_i, w_i, k_i)
    with jax.named_scope("sparse_select"):
        return exact_topk_mask(scores, allowed[:, 0], topk)[:, None]


def _index_keys(select: Selection, layer: int, safe: jax.Array) -> jax.Array:
    """The slots' index keys in logical order [B, NP * ps, Hip] (one
    gather over layer and page), lane padding and all: cutting the rows
    back to the indexer's head made XLA lay a layer of the pool out again
    for the gather, 0.2 ms a layer a substep on a v5e; the queries are
    padded with zeros instead (:func:`index_scores`)."""
    g = select.pool[layer, safe]                        # [B, NP, ps, Hip]
    B, NP, ps, _ = g.shape
    return g.reshape(B, NP * ps, -1)


def paged_select_mask(select: Selection, layer: int, safe: jax.Array,
                      win: jax.Array) -> jax.Array:
    """The floor: the gather fallback's staircase ``win`` [B, 1, T, S] cut
    to each row's selection, scored over the slots' gathered index keys
    (``safe`` [B, NP]: the table, sentinels clamped)."""
    _record(f"{FORM_FLOOR} (gathered view, {win.shape[2]} rows)")
    return select_mask(select.q, select.w, _index_keys(select, layer, safe),
                       win, select.topk)


def paged_decode(q, k, v, page_table, kv_lengths, layer: int,
                 select: Selection, *, scale, k_scale,
                 why: Optional[List[str]] = None) -> Optional[jax.Array]:
    """A selecting layer's paged read in a form of its own — a decode step
    in the mask form, a wider window over a wide table block by block — or
    None (the reason appended to ``why``): the caller's floor takes it."""
    why = [] if why is None else why
    if q.shape[1] != 1:
        NP = page_table.shape[1]
        if k_scale is not None or NP <= BLOCK_PAGES or NP % BLOCK_PAGES:
            why.append(f"sparse window: a table of {NP} columns"
                       + (" of an int8 pool" if k_scale is not None else "")
                       + " is the floor's")
            return None
        _record(f"blocked window ({q.shape[1]} rows, {BLOCK_PAGES} table "
                "columns a block, to the last position attended)")
        return _blocked_window(q, k, v, page_table, kv_lengths, layer,
                               select, scale)
    reason = _mask_form_declines(q, k, page_table, k_scale)
    if reason:
        why.append(reason)
        return None
    from ray_dynamic_batching_tpu.models.decoder import paged_window_mask

    P, ps = k.shape[1], k.shape[2]
    NP = page_table.shape[1]
    safe = jnp.minimum(page_table, P - 1)
    win = paged_window_mask(kv_lengths, NP * ps, 1)[:, 0]      # [B, 1, S]
    with jax.named_scope("sparse_index"):
        scores = index_scores(select.q, select.w,
                              _index_keys(select, layer, safe))
    with jax.named_scope("sparse_select"):
        chosen = exact_topk_mask(scores, win, select.topk)[:, 0]
    with jax.named_scope("sparse_attend"):
        K = k.shape[3]
        kb, G = _pick_heads_block(K), q.shape[2] // K
        fold = _page_fold(kb, G, ps)
        pages, depth = _walk(kb, G, ps, k.shape[4], k.dtype.itemsize, NP)
        page = (f"{K}-head page as {K * fold}-row tiles, "
                if fold > 1 else "")
        _record(f"{FORM_MASK} (live pages, {page}{pages} "
                f"page{'s' if pages > 1 else ''} a fold, a ring of {depth}, "
                "the selection a fold's row)")
        return sparse_paged_decode_attention(
            q, k, v, page_table, kv_lengths, chosen, layer=layer, scale=scale)


def _blocked_window(q, k, v, page_table, kv_lengths, layer: int,
                    select: Selection, scale) -> jax.Array:
    """The floor's arithmetic over ``BLOCK_PAGES`` table columns at a time:
    row t (at position ``kv_lengths + t``) attends its selection of the
    positions up to its own. Both loops run to the block that holds the
    last position any row attends and no further."""
    from ray_dynamic_batching_tpu.models.decoder import paged_window_mask

    B, T, N, H = q.shape
    P, ps, K = k.shape[1], k.shape[2], k.shape[3]
    NP = page_table.shape[1]
    cb = BLOCK_PAGES * ps
    scale = scale if scale is not None else H ** -0.5
    safe = jnp.minimum(page_table, P - 1)
    win = paged_window_mask(kv_lengths, NP * ps, T)[:, 0]       # [B, T, S]
    live = jnp.clip((jnp.max(kv_lengths) + T - 1) // cb + 1, 1,
                    NP // BLOCK_PAGES)

    def view(pool, i):        # block i of every slot: [B, cb, ...]
        pages = jax.lax.dynamic_slice_in_dim(
            safe, i * BLOCK_PAGES, BLOCK_PAGES, axis=1)
        g = pool[layer, pages]
        return g.reshape((B, cb) + g.shape[3:])

    with jax.named_scope("sparse_index"):
        def score(i, scores):
            return jax.lax.dynamic_update_slice_in_dim(
                scores, index_scores(select.q, select.w,
                                     view(select.pool, i)), i * cb, axis=2)

        scores = jax.lax.fori_loop(
            0, live, score, jnp.full((B, T, NP * ps), -jnp.inf, jnp.float32))
    with jax.named_scope("sparse_select"):
        chosen = exact_topk_mask(scores, win, select.topk)
    with jax.named_scope("sparse_attend"):
        q_g = q.reshape(B, T, K, N // K, H)

        def attend(i, state):
            m, l, acc = state
            k_b, v_b = view(k, i)[..., :H], view(v, i)[..., :H]
            keep = jax.lax.dynamic_slice_in_dim(
                chosen, i * cb, cb, axis=2)[:, None, None]  # [B,1,1,T,cb]
            s = jnp.einsum("btkgh,bskh->bkgts", q_g, k_b,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
            return (m_new, l * alpha + p.sum(-1),
                    acc * alpha[..., None] + jnp.einsum(
                        "bkgts,bskh->bkgth", p.astype(q.dtype), v_b,
                        preferred_element_type=jnp.float32))

        rows = (B, K, N // K, T)
        _, l, acc = jax.lax.fori_loop(0, live, attend, (
            jnp.full(rows, NEG_INF, jnp.float32), jnp.zeros(rows, jnp.float32),
            jnp.zeros(rows + (H,), jnp.float32)))
        out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, T, N, H).astype(
            q.dtype)


# --- the mask form's kernel --------------------------------------------------
def _mask_form_declines(q, k, page_table, k_scale) -> str:
    """Why the mask form's kernel cannot take this read ("" where it can):
    the paged kernel's own eligibility, less what this one leaves out."""
    from ray_dynamic_batching_tpu.ops.attention import (
        _use_pallas,
        tensor_parallel_width,
    )

    N, H = q.shape[2:]
    ps, K, Hk = k.shape[2:]
    if not _use_pallas():
        return "sparse kernel: pallas off on this backend"
    if k_scale is not None:
        return "sparse kernel: an int8 pool is the floor's"
    if tensor_parallel_width() > 1:
        return "sparse kernel: no shard_map form"
    if Hk < H or K == 0 or N % K:
        return (f"sparse kernel: q heads {N}x{H} do not group over pool "
                f"heads {K}x{Hk}")
    if not tile_math.lane_aligned_page(ps):
        return f"sparse kernel: page size {ps} is not a 128-lane multiple"
    if K * k.dtype.itemsize < 4:
        # Mosaic: "slice shape along dimension 3 must be aligned to tiling
        # (2)": one bf16 head is half a packed row of the page's copy.
        return f"sparse kernel: {K} key head(s) of {k.dtype} under the " \
               "copy's tiling"
    kb, G = _pick_heads_block(K), N // K
    if tile_math.sparse_tile_bytes(
            ps, kb, Hk, k.dtype.itemsize, G, page_table.shape[1],
            _page_fold(kb, G, ps), _flat(kb, G, ps),
    ) > VMEM_BLOCK_BUDGET_BYTES:
        return (f"sparse kernel: page tile (ps={ps}, kb={kb}, H={Hk}) and "
                "the slot's selection exceed the VMEM block budget")
    return ""


def _flat(kb: int, G: int, ps: int) -> bool:
    """Whether this kernel folds a page with all its heads in one
    contraction: where the paged kernel does (``tile_math.flat_heads``: a
    block of 8 heads), and for a NARROWER block too (4 key heads: the
    per-head fold's strided head slices cost 3.9 us a page on a v5e
    against a 0.32 us copy), by ``decode_attention._fold_flat``."""
    return tile_math.flat_heads(kb, G, ps) or (
        kb < 8 and tile_math.flat_heads(8, G, ps))


def _own_fold(kb: int, G: int, ps: int) -> bool:
    """Whether the flat fold is ``decode_attention._fold_flat`` (a narrow
    block) and not the paged kernel's (``_accumulate_tile``: 8 heads)."""
    return _flat(kb, G, ps) and not tile_math.flat_heads(kb, G, ps)


def _page_fold(kb: int, G: int, ps: int) -> int:
    """``f`` > 1 where the kernel reads the pool through the view
    [L, P, ps // f, kb * f, H] (``tile_math.page_view_fold``: a narrow
    block's page as whole (8, 128) tiles, ``f`` positions' heads a tile),
    which it does wherever ``_fold_flat`` takes the page and there is
    such a view; 1 where it reads [L, P, ps, kb, H] as it is (a block of 8
    heads, whose tiles are whole already; 3 heads; the per-head fold)."""
    return tile_math.page_view_fold(kb, ps) if _own_fold(kb, G, ps) else 1


def _fold_pages(kb: int, G: int, ps: int, H: int, itemsize: int,
                NP: int) -> int:
    """Live pages the kernel folds in one online-softmax update
    (``tile_math.sparse_fold_pages``: 4, 2 or 1 by the table's width and
    the VMEM budget where the fold is :func:`_own_fold`'s; 1 elsewhere)."""
    return tile_math.sparse_fold_pages(
        ps, kb, H, itemsize, G, NP, _page_fold(kb, G, ps),
        _own_fold(kb, G, ps))


def _walk(kb: int, G: int, ps: int, H: int, itemsize: int, NP: int):
    """``(pages a fold, ring slots of that many pages)`` of the kernel's
    walk at these shapes: what it traces and what ``sparse_forms()``
    says."""
    pages = _fold_pages(kb, G, ps, H, itemsize, NP)
    return pages, tile_math.sparse_walk_depth(
        ps, kb, H, itemsize, G, NP, _page_fold(kb, G, ps), _flat(kb, G, ps),
        pages)


def sparse_paged_decode_attention(
    q: jax.Array,            # [B, 1, N, H]
    k: jax.Array,            # [L, P, ps, K, Hk] the stacked pool, whole
    v: jax.Array,
    page_table: jax.Array,   # [B, NP]
    kv_lengths: jax.Array,   # [B]
    chosen: jax.Array,       # [B, NP * ps] bool: the positions each attends
    *,
    layer: int,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One decode row a slot over the positions ``chosen`` names among
    those ``<= kv_lengths``: the paged kernel's walk with a mask. The
    caller has checked :func:`_mask_form_declines`."""
    B, _, N, H = q.shape
    _, _, ps, K, Hk = k.shape
    G = N // K
    kb = _pick_heads_block(K)
    NP = page_table.shape[1]
    scale = scale if scale is not None else H ** -0.5
    q_r = q.reshape(B, K, G, H)                  # rows ordered g per kv head
    if Hk > H:
        q_r = jnp.pad(q_r, ((0, 0), (0, 0), (0, 0), (0, Hk - H)))
    sel = chosen.reshape(B, NP, ps).astype(jnp.int32)
    if _flat(kb, G, ps):
        sel = jnp.repeat(sel, kb, axis=2)
    # a fold's pages lie one after another: their rows of the selection
    # are ONE row of the operand (free: the same bytes)
    pages, depth = _walk(kb, G, ps, Hk, k.dtype.itemsize, NP)
    sel = sel.reshape(B, NP // pages, -1)
    out = _sparse_paged_decode_attention(
        q_r, k, v, page_table.astype(jnp.int32),
        kv_lengths.astype(jnp.int32), jnp.full((1,), layer, jnp.int32), sel,
        fold=_page_fold(kb, G, ps), pages=pages, depth=depth,
        scale=float(scale),
        interpret=bool(resolve_interpret(interpret)))
    return out[..., :H].reshape(B, 1, N, H)


@functools.partial(jax.jit, static_argnames=(
    "fold", "pages", "depth", "scale", "interpret"))
def _sparse_paged_decode_attention(
    q: jax.Array,           # [B, K, G, H]
    k: jax.Array,           # [L, P, ps, K, H]
    v: jax.Array,
    page_table: jax.Array,  # [B, NP] int32, sentinel P
    lengths: jax.Array,     # [B] int32: attends pos <= lengths[b]
    layer: jax.Array,       # [1] int32
    sel: jax.Array,         # [B, NP // pages, pages * cols] int32, != 0
    *,
    fold: int,              # _page_fold: > 1 reads the pool's tile view
    pages: int,             # _walk: live pages an online-softmax update,
    depth: int,             # ... and the ring's slots of that many pages
    scale: float,
    interpret: bool,
) -> jax.Array:
    """``decode_attention._paged_decode_attention`` at one query row a
    slot and a bf16 pool, with ``sel``: the same (slot, head block) grid,
    the same ring copied by the kernel itself and running on across steps,
    the same cursor in SMEM. With ``fold`` > 1 the two pools are read
    through their tile view (a reshape the compiler takes as a bitcast)
    and the ring holds a page as [ps // fold, kb * fold, H]: whole (8, 128)
    tiles.

    The walk's item is a GROUP of ``pages`` live pages, one after another
    in a ring slot, each copied once by its own DMA, ``depth - 1`` groups
    in flight behind the one being folded; a group is ONE fold (one score
    product, one running-max update, one rescale, one value product)
    handed ``pos <= length`` AND the group's row of the slot's selection. A
    slot of ``count`` live pages runs ``ceil(count / pages)`` folds; the
    last group's tail repeats the slot's last live page (finite rows, never
    an unwritten ring slot), at positions past the length."""
    B, K, R, H = q.shape
    L, P, ps = k.shape[:3]
    NP = page_table.shape[1]
    kb = _pick_heads_block(K)
    nj = K // kb
    steps = B * nj
    flat = _flat(kb, R, ps)
    own_fold = _own_fold(kb, R, ps)
    ahead = depth - 1
    page = (ps // fold, kb * fold, H)       # as the ring holds it
    span = pages * ps                       # positions a group covers
    if fold > 1:                            # nj == 1: the block is all K
        k, v = k.reshape(L, P, *page), v.reshape(L, P, *page)

    def bounds(b, len_ref):
        """A slot's live pages and the groups they make."""
        _, count = tile_math.live_pages(len_ref[b], 1, 0, ps, NP)
        return count, (count + (pages - 1)) // pages

    def kernel(pt_ref, len_ref, ly_ref, q_ref, sel_ref, k_hbm, v_hbm,
               o_ref, k_buf, v_buf, sem, cur, m_ref, l_ref, acc_ref):
        s = pl.program_id(0) * nj + pl.program_id(1)

        def copies(t, count, group, slot):
            b, j = (t, 0) if nj == 1 else (t // nj, t % nj)
            heads = (slice(None) if nj == 1
                     else pl.ds(pl.multiple_of(j * kb, kb), kb))
            out = []
            for r in range(pages):
                col = jnp.minimum(group * pages + r, count - 1)
                phys = jnp.minimum(pt_ref[b, col], P - 1)
                out += [pltpu.make_async_copy(
                    hbm.at[ly_ref[0], pl.ds(phys, 1), :, heads, :],
                    buf.at[pl.ds(slot, 1), pl.ds(r * page[0], page[0])],
                    sem.at[n, slot, r])
                    for n, (hbm, buf) in enumerate(
                        ((k_hbm, k_buf), (v_hbm, v_buf)))]
            return out

        def start_next(t, i, slot):
            t_in = jnp.minimum(t, steps - 1)
            count, groups = bounds(t_in if nj == 1 else t_in // nj, len_ref)

            @pl.when(t < steps)
            def _start():
                for c in copies(t_in, count, i, slot):
                    c.start()

            roll = i + 1 >= groups
            return (jnp.where(roll, t + 1, t),
                    jnp.where(roll, 0, i + 1))

        @pl.when(s == 0)
        def _stream_begins():
            cursor = (jnp.int32(0), jnp.int32(0))
            for n in range(ahead):
                cursor = start_next(*cursor, n)
            cur[0] = 0
            cur[1], cur[2] = cursor

        b = pl.program_id(0)
        count, groups = bounds(b, len_ref)
        base = cur[0]
        _scan_begin(m_ref, l_ref, acc_ref)

        def fold_group(i, cursor):
            cursor = start_next(*cursor, (base + i + ahead) % depth)
            slot = (base + i) % depth
            for c in copies(s, count, i, slot):
                c.wait()
            shape = (kb * R, span * kb) if flat else (R, span)
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            pos = i * span + (col // kb if flat else col)
            # the group's row of the selection, broadcast over the rows
            picked = sel_ref[0, pl.ds(i, 1), :] != 0
            valid = (pos <= len_ref[b]) & picked
            tiles = k_buf.at[pl.ds(slot, 1)], v_buf.at[pl.ds(slot, 1)]
            if own_fold:
                _fold_flat(q_ref, *tiles, m_ref, l_ref, acc_ref, ps=span,
                           kb=kb, valid=valid, scale=scale)
            else:
                _accumulate_tile(q_ref, *tiles, None, None, m_ref, l_ref,
                                 acc_ref, valid=valid, scale=scale)
            return cursor

        cur[1], cur[2] = jax.lax.fori_loop(
            0, groups, fold_group, (cur[1], cur[2]))
        cur[0] = (base + groups) % depth
        _scan_end(o_ref, m_ref, l_ref, acc_ref)

    rows_spec = pl.BlockSpec(
        (1, kb * R, H), lambda b, j, pt, ln, ly: (b, j, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nj),
        in_specs=[
            rows_spec,
            pl.BlockSpec((1,) + tuple(sel.shape[1:]),
                         lambda b, j, pt, ln, ly: (b, 0, 0)),
            in_hbm, in_hbm],
        out_specs=rows_spec,
        scratch_shapes=[pltpu.VMEM(
            (depth, pages * page[0]) + page[1:], k.dtype)] * 2 + [
            pltpu.SemaphoreType.DMA((2, depth, pages)),
            pltpu.SMEM((3,), jnp.int32),
        ] + _scratch(kb, R, H, flat),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K * R, H), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(page_table, lengths, layer, q.reshape(B, K * R, H), sel, k, v
      ).reshape(B, K, R, H)
