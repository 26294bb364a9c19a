"""The SELECTION of a selecting latent layer (GLM-5's: DeepSeek-V3.2's
indexer over DeepSeek-V3's latent attention): the rows of
``ops/latent_attention.py`` (``[c_kv | k_r | 0]``, one a position a layer)
with ONE index key a position in a plane beside them
(``PagedKVCache.index_k``, the same page and offset), of which a query
attends the ``topk`` positions its index heads score highest
(``ops/sparse_attention.py``: ``index_scores``, ``exact_topk_mask``; the
same set for every head, exact, a tie to the lower position). Loaded only
by a model whose latent layers select (``models/latent.py`` imports it
where one is met).

The reads are ``ops/latent_attention.py``'s own, handed :func:`_chosen` (the
staircase of the length bound cut to each row's best; rows with no more
than ``topk`` positions keep them all and cost no top-k):

- :func:`whole_mask`: no cache, the causal mask of a whole sequence cut.
- :func:`chunk`: a chunk's rows, ``latent_attention.expanded``'s walk with
  the block's columns of the rows' selection ANDed into the length bound.
  The index scores are taken ``BLOCK_PAGES`` table columns at a time to the
  block of the chunk's last position, before the walk.
- :func:`decode`: the ABSORBED decode step in the MASK form: the latent
  kernel's walk over a slot's live pages with the selection as one more
  operand (Keye's mask form on Xing's kernel; every live page is read,
  nothing is gathered). Elsewhere (a CPU, several rows a slot)
  ``latent_attention.absorbed`` under the same selection.

(A form that gathers the ``topk`` selected rows and scores those alone reads
2.4 MB a slot a layer where this one walks up to 21 MB: not built; PERF.md
section 7, PR 57.)
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import latent_attention as lat
from ray_dynamic_batching_tpu.ops.sparse_attention import (
    BLOCK_PAGES,
    FORM_FLOOR,
    FORM_MASK,
    Selection,
    _index_keys,
    _record,
    exact_topk_mask,
    index_scores,
)

__all__ = ["Selection", "whole_mask", "chunk", "decode"]


def whole_mask(q_i: jax.Array, w_i: jax.Array, k_i: jax.Array,
               allowed: jax.Array, topk: int) -> jax.Array:
    """``allowed`` [B, 1, T, T] (the causal, valid-token mask of a whole
    sequence) cut to each query's selection over the index keys ``k_i``
    [B, T, Hi]."""
    with jax.named_scope("sparse_latent_select"):
        return exact_topk_mask(
            index_scores(q_i, w_i, k_i), allowed[:, 0], topk)[:, None]


def _scores(select: Selection, layer: int, safe: jax.Array,
            last: jax.Array) -> jax.Array:
    """``I`` [B, T, NP * ps] over the slots' index keys through their
    tables (``safe``: sentinels clamped). Several rows a slot over a wide
    table: ``BLOCK_PAGES`` columns at a time up to the block that holds
    position ``last``, ``-inf`` beyond (nothing there is attended)."""
    B, NP = safe.shape
    T = select.q.shape[1]
    if T == 1 or NP <= BLOCK_PAGES or NP % BLOCK_PAGES:
        return index_scores(select.q, select.w,
                            _index_keys(select, layer, safe))
    cb = BLOCK_PAGES * select.pool.shape[2]

    def score(i, scores):
        pages = jax.lax.dynamic_slice_in_dim(
            safe, i * BLOCK_PAGES, BLOCK_PAGES, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            scores, index_scores(select.q, select.w,
                                 _index_keys(select, layer, pages)),
            i * cb, axis=2)

    live = jnp.clip(last // cb + 1, 1, NP // BLOCK_PAGES)
    return jax.lax.fori_loop(0, live, score, jnp.full(
        (B, T, NP * select.pool.shape[2]), -jnp.inf, jnp.float32))


def _chosen(select: Selection, layer: int, page_table: jax.Array,
            lengths: jax.Array, T: int) -> jax.Array:
    """[B, T, NP * ps] bool: row t of a slot (at position ``lengths + t``)
    attends these positions: of those up to its own, its ``topk`` best."""
    from ray_dynamic_batching_tpu.models.decoder import paged_window_mask

    P, ps = select.pool.shape[1], select.pool.shape[2]
    NP = page_table.shape[1]
    win = paged_window_mask(lengths, NP * ps, T)[:, 0]
    if select.topk >= NP * ps:
        return win
    last = jnp.max(lengths) + (T - 1)

    def pick():
        scores = _scores(select, layer, jnp.minimum(page_table, P - 1), last)
        return exact_topk_mask(scores, win, select.topk)

    with jax.named_scope("sparse_latent_select"):
        # no row has more than topk positions: all are kept, nothing scored
        return jax.lax.cond(last >= select.topk, pick, lambda: win)


def chunk(q_n: jax.Array, q_r: jax.Array, pool: jax.Array,
          w_ukv: jax.Array, page_table: jax.Array, lengths: jax.Array,
          layer: int, select: Selection, *, scale: float) -> jax.Array:
    """A chunk's rows: ``latent_attention.expanded`` under each row's
    selection of its prefix. -> ``[B, T, N, Hv]``."""
    T = q_n.shape[1]
    _record(f"expanded chunk walk ({T} rows, "
            f"{min(lat.BLOCK_PAGES, page_table.shape[1])} pages a block, "
            "the rows' selection a block's mask)")
    chosen = _chosen(select, layer, page_table, lengths, T)
    with jax.named_scope("sparse_latent_chunk"):
        return lat.expanded(q_n, q_r, pool, w_ukv, page_table, lengths,
                            layer, scale=scale, chosen=chosen)


def decode(q: jax.Array, pool: jax.Array, page_table: jax.Array,
           lengths: jax.Array, layer: int, select: Selection, *, rank: int,
           scale: float, why: Optional[List[str]] = None) -> jax.Array:
    """The absorbed decode step under a selection:
    ``latent_attention.decode`` (q ``[B, T, N, Wp]`` -> ``[B, T, N,
    rank]``), and the form it took for ``sparse_forms()``."""
    T, NP = q.shape[1], page_table.shape[1]
    declines: List[str] = []
    chosen = _chosen(select, layer, page_table, lengths, T)
    with jax.named_scope("sparse_latent_decode"):
        out = lat.decode(q, pool, page_table, lengths, layer, rank=rank,
                         scale=scale, chosen=chosen, why=declines)
    if why is not None:
        why.extend(declines)
    if attn_ops._use_pallas() and not declines:
        _record(f"{FORM_MASK} (latent rows, live pages, "
                f"{min(lat.FOLD_PAGES, NP)} pages a fold, a ring of "
                f"{lat.RING_DEPTH}, the selection a fold's row)")
    else:
        _record(f"{FORM_FLOOR} (latent rows, {T} row"
                f"{'s' if T > 1 else ''} a slot, "
                f"{min(lat.BLOCK_PAGES, NP)} pages a block in XLA)")
    return out
