"""Attention over a LATENT pool: one row a position a layer, ``[c_kv | k_r |
0]`` (the normed low-rank latent, the ONE rotary key all heads share,
zeros up to the 128 lanes), which is key and value at once. Loaded only by a
model that has such a layer (``models/latent.py`` imports it where one is
met).

The pool is ``[L, P, page_size, Wp]``: no head axis, no k/v pair. ``Wp`` is
the row AS HELD, ``tile_math.pad_lane(rank + rope)`` (576 -> 640): a bf16
row's minor axis lies in 128-lane tiles on the device whatever the shape
says, so a 576-wide shape would be held as 640 anyway and hide it from
``on_device_size_in_bytes``' reader; said in the shape, a page is whole (16,
128) tiles for the kernel's copy and its two dots, with no lane mask. (512 +
a 64-wide plane packed two positions a lane row would save the tenth, 64 of
640 lanes, at the price of a second copy a page and a lane-offset write a
token; not built.) The zeros are inert: the query's lanes there are zero and
the value is the row's first ``rank`` lanes.

Three reads, one rule of validity (row t of a slot attends positions <=
``lengths + t``; a page-table column past that is never attended), and under
it, where the layer SELECTS (``chosen``, ``[B, T, NP * ps]`` bool from
``ops/sparse_latent_attention.py``; None for a layer that attends its whole
prefix, whose programs hold nothing of it), only the positions chosen:

- :func:`decode`: the ABSORBED decode step, ``q' = [q_n W_uk | q_r | 0]``
  against the rows themselves. On the TPU (or under the strict ``"pallas"``
  backend) the kernel :func:`_latent_paged_decode_attention`: the pool stays
  in HBM whole, the layer is a prefetched scalar, a slot's live pages
  (``tile_math.live_pages``) are copied ``[page_size, Wp]`` at a time into a
  ring of VMEM slots, ``FOLD_PAGES`` to a slot, and a slot is folded at
  once under an online softmax: scores over all ``Wp`` lanes, values the
  first ``rank`` of the SAME tiles, so a page is read once. A selection
  is one more operand, a fold's row of the slot's mask ANDed into the
  length bound (the MASK form: every live page is read, nothing is
  gathered). Elsewhere :func:`absorbed`, the same arithmetic in XLA.
- :func:`absorbed`: any number of rows a slot, a block of pages at a time in
  XLA (the kernel's fallback, and the form a chunk could take).
- :func:`expanded`: a chunk's rows. A block of pages' keys and values are
  EXPANDED from the latent (``[k_n | v] = c_kv W_ukv``) and attended as
  ordinary heads. At a 512-row chunk of Xing's 32 heads the expansion (2 x
  512 x 8,192 flop a position) costs less than the absorbed form's wider
  dots (32 x 512 x 2 x (576 + 512) against 32 x 512 x 2 x (192 + 128) a
  position): PERF.md has the chip's reading of both.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import tile_math
from ray_dynamic_batching_tpu.ops.pallas_common import resolve_interpret

NEG_INF = -1e30
# Pages a block of the XLA walks: 4 x 128 positions, a chunk's own width.
BLOCK_PAGES = 4
# Pages the decode kernel folds at a time, and the VMEM slots of its ring
# of such folds (folds in flight: one fewer).
FOLD_PAGES = 4
RING_DEPTH = 3


def row_width(rank: int, rope: int) -> int:
    """A pool row as held: ``rank + rope`` up to whole lane tiles."""
    return tile_math.pad_lane(rank + rope)


def _walk(page_table, lengths, T: int, P: int, ps: int, fold, state,
          chosen=None):
    """Fold the blocks of a slot's table that hold a position some row
    attends: ``fold(state, pages [B, bp], valid [B, T, bp * ps])``, the
    block's columns of ``chosen`` ANDed into ``valid`` where given."""
    B, NP = page_table.shape
    bp = min(BLOCK_PAGES, NP)
    blocks = -(-NP // bp)
    if chosen is not None:
        chosen = jnp.pad(
            chosen, ((0, 0), (0, 0), (0, (blocks * bp - NP) * ps)))
    # columns past the table's end repeat its last: their positions are
    # past the capacity, where nothing is attended
    table = jnp.minimum(jnp.pad(
        page_table, ((0, 0), (0, blocks * bp - NP)), mode="edge"), P - 1)
    bound = lengths.astype(jnp.int32)[:, None] + jnp.arange(
        T, dtype=jnp.int32)[None, :]
    S = bp * ps

    def body(j, state):
        pages = jax.lax.dynamic_slice_in_dim(table, j * bp, bp, axis=1)
        pos = j * S + jnp.arange(S, dtype=jnp.int32)
        valid = (pos[None, None, :] <= bound[:, :, None]) & (
            pos < NP * ps)[None, None, :]
        if chosen is not None:
            valid &= jax.lax.dynamic_slice_in_dim(chosen, j * S, S, axis=2)
        return fold(state, pages, valid)

    last = jnp.minimum(jnp.max(bound[:, -1]), NP * ps - 1)
    live = jnp.clip(last // S + 1, 1, blocks)
    return jax.lax.fori_loop(0, live, body, state)


def _begin(B: int, N: int, T: int, Hv: int):
    return (jnp.zeros((B, N, T, Hv), jnp.float32),
            jnp.full((B, N, T), NEG_INF, jnp.float32),
            jnp.zeros((B, N, T), jnp.float32))


def _softmax_fold(state, s, valid, values):
    """One online-softmax update: scores ``s`` [B, N, T, S], ``valid`` [B,
    T, S]; ``values(p)`` contracts the block's probabilities with its
    values -> [B, N, T, Hv]."""
    acc, m, l = state
    see = valid[:, None]
    m_new = jnp.maximum(m, jnp.max(jnp.where(see, s, NEG_INF), axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(see, jnp.exp(s - m_new[..., None]), 0.0)
    return (acc * alpha[..., None] + values(p), m_new,
            l * alpha + p.sum(-1))


def _finish(state, dtype):
    acc, _, l = state
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(0, 2, 1, 3).astype(dtype)      # [B, T, N, Hv]


def _rows(pool, layer: int, pages):
    """A block of pages' rows ``[B, bp * ps, Wp]``. The barrier keeps the
    block as the gather gives it (``ops/kind_attention.py::paged``: without
    one XLA may lay the whole pool out anew for the contraction)."""
    rows = jax.lax.optimization_barrier(pool[layer, pages])
    return rows.reshape(rows.shape[0], -1, rows.shape[-1])


def absorbed(q: jax.Array, pool: jax.Array, page_table: jax.Array,
             lengths: jax.Array, layer: int, *, rank: int, scale: float,
             chosen: Optional[jax.Array] = None) -> jax.Array:
    """q ``[B, T, N, Wp]`` (``[q_n W_uk | q_r | 0]``) against the rows of
    ``pool`` ``[L, P, ps, Wp]`` through ``page_table`` ``[B, NP]``; row t
    sits at position ``lengths + t``. -> ``[B, T, N, rank]``: the
    probabilities' sum of latents, for the caller's ``W_uv``."""
    B, T, N, _ = q.shape
    P, ps = pool.shape[1], pool.shape[2]

    def fold(state, pages, valid):
        rows = _rows(pool, layer, pages)
        s = jnp.einsum("btnw,bsw->bnts", q, rows,
                       preferred_element_type=jnp.float32) * scale
        return _softmax_fold(state, s, valid, lambda p: jnp.einsum(
            "bnts,bsr->bntr", p.astype(rows.dtype), rows[..., :rank],
            preferred_element_type=jnp.float32))

    return _finish(_walk(page_table, lengths, T, P, ps, fold,
                         _begin(B, N, T, rank), chosen), q.dtype)


def expanded(q_n: jax.Array, q_r: jax.Array, pool: jax.Array,
             w_ukv: jax.Array, page_table: jax.Array, lengths: jax.Array,
             layer: int, *, scale: float,
             chosen: Optional[jax.Array] = None) -> jax.Array:
    """q_n ``[B, T, N, nope]``, q_r ``[B, T, N, rope]`` against keys and
    values expanded a block of pages at a time from the pool's latents by
    ``w_ukv`` ``[rank, N, nope + Hv]``. -> ``[B, T, N, Hv]``."""
    B, T, N, nope = q_n.shape
    rope = q_r.shape[-1]
    rank = w_ukv.shape[0]
    P, ps = pool.shape[1], pool.shape[2]
    w = w_ukv.astype(pool.dtype)
    # Keys and values expanded by their own halves of the up-projection,
    # each where it is used (expanded as ONE [.., nope + Hv] product, XLA
    # rematerialised it for the values), and a block's keys made WHOLE
    # heads, [k_n | the one rotary key], so that a block's scores are ONE
    # product over the head's nope + rope values and not two float32
    # [N, T, S] arrays added (my chip run, PR 57: each such array is 67 MB
    # written and read a block at 64 heads, what paced the walk).
    w_k, w_v = w[..., :nope], w[..., nope:]
    q = jnp.concatenate([q_n, q_r], axis=-1)

    def fold(state, pages, valid):
        with jax.named_scope("latent_chunk_expand"):
            rows = _rows(pool, layer, pages)
            latents = rows[..., :rank]
            k_r = rows[..., None, rank:rank + rope]
            keys = jnp.concatenate([
                jnp.einsum("bsr,rnh->bsnh", latents, w_k),
                jnp.broadcast_to(k_r, k_r.shape[:2] + (N, rope))], axis=-1)
        with jax.named_scope("latent_chunk_attend"):
            s = jnp.einsum("btnh,bsnh->bnts", q, keys,
                           preferred_element_type=jnp.float32) * scale
            return _softmax_fold(state, s, valid, lambda p: jnp.einsum(
                "bnts,bsnh->bnth", p.astype(rows.dtype),
                jnp.einsum("bsr,rnh->bsnh", latents, w_v),
                preferred_element_type=jnp.float32))

    return _finish(_walk(page_table, lengths, T, P, ps, fold,
                         _begin(B, N, T, w.shape[-1] - nope), chosen),
                   q_n.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def _latent_paged_decode_attention(
    q: jax.Array,           # [B, N, Wp]
    pool: jax.Array,        # [L, P, ps, Wp] the STACKED pool, whole
    page_table: jax.Array,  # [B, NP] int32, sentinel P
    lengths: jax.Array,     # [B] int32: attends pos <= lengths[b]
    layer: jax.Array,       # [1] int32
    sel: Optional[jax.Array] = None,  # [B, folds, bp * ps] int32, != 0:
    *,                                # attended (a selecting layer's)
    rank: int,
    scale: float,
    interpret: bool,
) -> jax.Array:
    B, N, W = q.shape
    P, ps = pool.shape[1], pool.shape[2]
    NP = page_table.shape[1]
    depth, bp = RING_DEPTH, min(FOLD_PAGES, NP)
    selected = [] if sel is None else [sel]

    # The grid is the slots; a slot's live pages are a loop inside the
    # step, over the table columns ``tile_math.live_pages`` names from the
    # prefetched length, so a column past the length is never read. The
    # loop folds ``bp`` pages at a time: each is copied once, into its
    # place in a ring slot of ``[bp * ps, Wp]``, and serves both dots; one
    # running-max update and one rescale of the accumulator a fold, whose
    # chain (dot, max, exp, dot) is what a page a fold would wait on. Under
    # a selection a fold is handed ``pos <= length`` AND its row of the
    # slot's ``sel``; one that keeps nothing leaves the running maximum
    # where it was and adds nothing.
    def kernel(pt_ref, len_ref, ly_ref, q_ref, *refs):
        sel_ref = None if sel is None else refs[0]
        pool_hbm, o_ref, buf, sem = refs[-4:]
        b = pl.program_id(0)
        _, count = tile_math.live_pages(len_ref[b], 1, 0, ps, NP)
        folds = (count + (bp - 1)) // bp

        def copies(j, slot):
            # the last fold's tail repeats the last live page: rows past
            # the length, masked below, but finite (a ring slot never
            # written could hold anything)
            out = []
            for r in range(bp):
                col = jnp.minimum(j * bp + r, count - 1)
                phys = jnp.minimum(pt_ref[b, col], P - 1)
                out.append(pltpu.make_async_copy(
                    pool_hbm.at[ly_ref[0], phys],
                    buf.at[slot, pl.ds(r * ps, ps)], sem.at[slot, r]))
            return out

        for n in range(depth - 1):
            @pl.when(n < folds)
            def _prime(n=n):
                for c in copies(n, n):
                    c.start()

        q_rows = q_ref[0]                                     # [N, Wp]
        # the last position attended: the length, inside the table
        last = jnp.minimum(len_ref[b], NP * ps - 1)

        def fold(j, state):
            m, l, acc = state
            ahead = j + (depth - 1)

            @pl.when(ahead < folds)
            def _next():
                for c in copies(ahead, ahead % depth):
                    c.start()

            slot = j % depth
            for c in copies(j, slot):
                c.wait()
            rows = buf[slot]                                  # [bp ps, Wp]
            s = jax.lax.dot_general(
                q_rows, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [N, bp ps]
            pos = j * (bp * ps) + jax.lax.broadcasted_iota(
                jnp.int32, (N, bp * ps), 1)
            keep = pos <= last
            if sel_ref is not None:
                # the fold's row of the selection, broadcast over the heads
                keep &= sel_ref[0, pl.ds(j, 1), :] != 0
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            if sel_ref is not None:
                p = jnp.where(keep, p, 0.0)
            acc = acc * alpha + jnp.dot(
                p.astype(rows.dtype), rows[:, :rank],
                preferred_element_type=jnp.float32)
            return m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True), acc

        _, l, acc = jax.lax.fori_loop(0, folds, fold, (
            jnp.full((N, 1), NEG_INF, jnp.float32),
            jnp.zeros((N, 1), jnp.float32),
            jnp.zeros((N, rank), jnp.float32)))
        o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    a_slot = lambda b, pt, ln, ly: (b, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, N, W), a_slot)] + [
            pl.BlockSpec((1,) + x.shape[1:], a_slot) for x in selected] + [
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, N, rank), a_slot),
        scratch_shapes=[pltpu.VMEM((depth, bp * ps, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((depth, bp))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=tile_math.VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(page_table, lengths, layer, q, *selected, pool)


def decode(q: jax.Array, pool: jax.Array, page_table: jax.Array,
           lengths: jax.Array, layer: int, *, rank: int, scale: float,
           chosen: Optional[jax.Array] = None,
           why: Optional[List[str]] = None) -> jax.Array:
    """The absorbed decode step: q ``[B, T, N, Wp]`` -> ``[B, T, N,
    rank]``, by the kernel where Pallas is on and ``T`` is 1, else
    :func:`absorbed`."""
    B, T, N, W = q.shape
    ps, NP = pool.shape[2], page_table.shape[1]
    declines = [] if why is None else why
    with jax.named_scope("latent_decode"):
        if T != 1:
            declines.append(f"{T} rows a slot: the kernel folds one")
        elif ps % 16 or W % 128 or rank % 128:
            declines.append(
                f"page of {ps} x {W}, rank {rank}: not whole (16, 128) "
                "tiles")
        elif attn_ops.tensor_parallel_width() > 1:
            declines.append("a latent row has no head axis to shard")
        elif attn_ops._use_pallas():
            sel = None
            if chosen is not None:
                # a fold's pages lie one after another: their columns of
                # the selection are ONE row of the operand
                bp = min(FOLD_PAGES, NP)
                sel = jnp.pad(chosen[:, 0], ((0, 0), (0, -NP % bp * ps))
                              ).reshape(B, -1, bp * ps).astype(jnp.int32)
            out = _latent_paged_decode_attention(
                q[:, 0], pool, page_table.astype(jnp.int32),
                lengths.astype(jnp.int32), jnp.full((1,), layer, jnp.int32),
                sel, rank=rank, scale=float(scale),
                interpret=bool(resolve_interpret(None)))
            attn_ops._record(attn_ops.PATH_PAGED_KERNEL, q, pool, declines,
                             stacked=True, v_dim=rank)
            return out[:, None]
        if attn_ops._BACKEND == "pallas":
            raise attn_ops.AttentionDeclined(
                "latent decode kernel declined: " + "; ".join(declines))
        attn_ops._record(attn_ops.PATH_BLOCKED, q, pool, declines,
                         v_dim=rank)
        return absorbed(q, pool, page_table, lengths, layer, rank=rank,
                        scale=scale, chosen=chosen)
