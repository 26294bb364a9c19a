"""Attention for a layer the common kernels do not take: a learned SINK in
the softmax (one scalar a query head: it takes probability mass and adds no
value), a value head narrower than the key's. Plain XLA, and loaded only by
a model that has such a layer (``models/decoder.py``, ``ops/attention.py``
import it where one is met).

:func:`paged` is the paged read wherever the paged decode kernel is not: a
chunk's rows on the chip, every read on the CPU. It walks the slot's table a
BLOCK of pages at a time under an online softmax, so that the scores of a
512-row chunk against an 18k-position prefix are never one array (64 heads
x 512 x 18,432 x 4 B = 2.4 GB): a block's are 512 x 512 a head. A sliding
layer walks the columns of its rows' windows only (``window_table``: of a
ring, 6 pages), a full layer up to the column of its last row's position.
One rule of validity with the kernel: row t attends positions <= lengths +
t, a sliding layer the last ``sliding`` of them
(``models/decoder.py::paged_window_mask``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
# Pages a block: 4 x 128 positions, a chunk's own width.
BLOCK_PAGES = 4


def _finish(acc, m, l, sink, dtype):
    """acc [B, K, G, T, Hv], m and l [B, K, G, T] -> [B, T, N, Hv]; the
    sink [N] adds ``exp(sink - m)`` to the sum and nothing to ``acc``."""
    B, K, G, T, Hv = acc.shape
    if sink is not None:
        s = sink.astype(jnp.float32).reshape(1, K, G, 1)
        l = l + jnp.where(l > 0, jnp.exp(s - m), 0.0)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, K * G, Hv).astype(dtype)


def _fold(state, q, k, v, valid):
    """One online-softmax update: q [B, T, K, G, H] (scaled), k [B, S, K,
    H], v [B, S, K, Hv], valid [B, T, S]."""
    acc, m, l = state
    s = jnp.einsum("btkgh,bskh->bkgts", q, k,
                   preferred_element_type=jnp.float32)
    see = valid[:, None, None]
    m_new = jnp.maximum(m, jnp.max(jnp.where(see, s, NEG_INF), axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(see, jnp.exp(s - m_new[..., None]), 0.0)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bkgts,bskh->bkgth", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return acc, m_new, l * alpha + p.sum(-1)


def _begin(B, K, G, T, Hv):
    return (jnp.zeros((B, K, G, T, Hv), jnp.float32),
            jnp.full((B, K, G, T), NEG_INF, jnp.float32),
            jnp.zeros((B, K, G, T), jnp.float32))


def dense(q: jax.Array, k: jax.Array, v: jax.Array, allowed: jax.Array,
          sink: Optional[jax.Array], scale: Optional[float] = None,
          ) -> jax.Array:
    """q [B, T, N, H], k [B, S, K, H], v [B, S, K, Hv], ``allowed``
    broadcastable to [B, 1, T, S] (True = attend) -> [B, T, N, Hv]."""
    B, T, N, H = q.shape
    S, K = k.shape[1], k.shape[2]
    scale = H ** -0.5 if scale is None else scale
    qg = (q * scale).reshape(B, T, K, N // K, H)
    valid = jnp.broadcast_to(allowed, (B, 1, T, S))[:, 0]
    return _finish(*_fold(_begin(B, K, N // K, T, v.shape[-1]),
                          qg, k, v, valid), sink, q.dtype)


def paged(q: jax.Array, k: jax.Array, v: jax.Array, page_table: jax.Array,
          lengths: jax.Array, layer: int, *, sliding: int = 0,
          sink: Optional[jax.Array] = None, scale: Optional[float] = None,
          ) -> jax.Array:
    """q [B, Tq, N, H] against the stacked pools k [L, P, ps, K, Hk >= H]
    and v [L, P, ps, K, Hvp] (rows lane-padded) through ``page_table`` [B,
    NP]; row t sits at position ``lengths + t``. -> [B, Tq, N, Hvp]: the
    caller cuts the value head back."""
    from ray_dynamic_batching_tpu.ops.decode_attention import window_table

    B, T, N, H = q.shape
    P, ps, K = k.shape[1], k.shape[2], k.shape[3]
    capacity = page_table.shape[1] * ps
    lengths = lengths.astype(jnp.int32)
    base = jnp.zeros((B,), jnp.int32)
    if sliding:
        page_table, first = window_table(page_table, lengths, sliding, T, ps)
        base = first * ps
    W = page_table.shape[1]
    bp = min(BLOCK_PAGES, W)
    blocks = -(-W // bp)
    # columns past the view's end repeat its last: their positions are
    # past ``reach`` (below), where nothing is attended
    table = jnp.minimum(jnp.pad(
        page_table, ((0, 0), (0, blocks * bp - W)), mode="edge"), P - 1)
    scale = H ** -0.5 if scale is None else scale
    qg = (q * scale).reshape(B, T, K, N // K, H)
    bound = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    reach = jnp.minimum(base + W * ps, capacity)     # [B] the view's end
    S = bp * ps

    def fold(j, state):
        pages = jax.lax.dynamic_slice_in_dim(table, j * bp, bp, axis=1)
        # The barrier keeps the block as the gather gives it: without it
        # XLA lays the WHOLE pool out anew, positions under heads, for the
        # contraction below (seen at group width 2: a 1.5 GB copy a full
        # layer a chunk program, and the cell's programs no longer fit).
        k_b, v_b = jax.lax.optimization_barrier(
            (k[layer, pages], v[layer, pages]))
        k_b = k_b.reshape(B, S, K, -1)[..., :H]
        v_b = v_b.reshape(B, S, K, -1)
        pos = base[:, None] + j * S + jnp.arange(S, dtype=jnp.int32)[None, :]
        valid = (pos[:, None, :] <= bound[:, :, None]) & (
            pos < reach[:, None])[:, None, :]
        if sliding:
            valid = valid & (pos[:, None, :] > bound[:, :, None] - sliding)
        return _fold(state, qg, k_b, v_b, valid)

    # only the blocks that hold a position some row attends
    last = jnp.minimum(jnp.max(bound[:, -1] - base), W * ps - 1)
    live = jnp.clip(last // S + 1, 1, blocks)
    state = jax.lax.fori_loop(
        0, live, fold, _begin(B, K, N // K, T, v.shape[-1]))
    return _finish(*state, sink, q.dtype)
