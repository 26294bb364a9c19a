"""The latent-attention sublayer (MLA, DeepSeek-V2/V3's) of
``models/decoder.py::DecoderLayer``: low-rank q and kv paths, a head split
into ``qk_nope_head_dim`` values without positions and ``rope_dim`` rotary
ones, ONE rotary key a position shared by all heads, YaRN on the rotary
frequencies. Loaded only by a model with ``kv_lora_rank`` > 0.

    c_q = RMSNorm_g(x W_dq);  q = c_q W_uq            (heads of nope + rope)
    [c_kv | k_r] = x W_dkv;   c_kv <- RMSNorm_g(c_kv)
    rotary on q's last ``rope`` and on k_r, pairs (2i, 2i+1), YaRN
    [k_n | v]_h = c_kv W_ukv
    a_tj = (q_n . k_n + q_r . k_r) x (nope + rope)^-1/2 x m^2

The paged cache holds ``[c_kv | k_r]`` alone (``ops/latent_attention.py``).
A chunk's rows expand a block of pages' keys and values from it; a decode
step is ABSORBED: ``q'_h = q_n W_uk,h^T`` scores the latent itself, and
``o_h = (sum_j p_j c_kv,j) W_uv,h``.

A SELECTING latent layer (``index_topk``; DeepSeek-V3.2's indexer, GLM-5)
attends only the positions its :class:`Indexer` keeps a query: the index
queries come from the q latent ``c_q``, ONE index key a position lies in a
plane of its own beside the rows (``PagedKVCache.index_k``, the same page
and offset), and the selection (``ops/sparse_latent_attention.py``, loaded
by such a layer alone) is ANDed into all three reads, which are
``ops/latent_attention.py``'s own.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """[dim / 2] rotary frequencies: ``theta^(-2i/dim)`` where a dimension
    turns more than ``beta_fast`` times over the ``original`` context, that
    over ``factor`` where fewer than ``beta_slow``, the linear ramp between
    the two correction dimensions elsewhere. ``factor`` <= 1: unscaled."""
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return base.astype(np.float32)

    def correction(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (base / factor * ramp + base * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def rope_pairs(x: jax.Array, positions: jax.Array, inv_freq: np.ndarray,
               gain: float = 1.0) -> jax.Array:
    """x ``[B, T, ..., d]``, positions ``[B, T]``: the pair ``(x[2i],
    x[2i+1])`` turns by ``position x inv_freq[i]``. The result holds the
    pairs' first halves, then their second halves: q and k alike, so a
    score does not see the order."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = gain * jnp.cos(ang), gain * jnp.sin(ang)
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


class Kernel(nn.Module):
    """A bare ``kernel`` parameter: an up-projection the absorbed form
    reads in slices."""

    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape, jnp.float32)


class Indexer:
    """A selecting latent layer's indexer, DeepSeek-V3.2's form (the one a
    latent layer with ``index_topk`` has; a second published form would be
    a key of ``DecoderConfig``): what ``DecoderLayer._latent_attention``
    hands :func:`attention`, which calls it with the q latent. ``qI = c_q
    W_qI`` as ``index_heads`` heads, from the q LATENT and not from the
    layer's input; ``kI = LayerNorm(x W_kI)`` (scale and bias, eps 1e-6),
    ONE key a position; ``w = x W_w``; rotary at the layer's theta, pairs
    (2i, 2i+1), on the first ``rope_dim`` values of qI and kI, the rest
    unrotated."""

    def __init__(self, cfg: Any, dtype: Any, topk: int):
        self.cfg, self.dtype, self.topk = cfg, dtype, topk

    def __call__(self, c_q: jax.Array, y: jax.Array, positions: jax.Array):
        """-> (qI ``[B, T, n, Hi]``, w ``[B, T, n]`` float32, kI ``[B, T,
        Hi]``), in the layer's module scope (the caller's)."""
        cfg, f32 = self.cfg, jnp.float32
        n, Hi, rd = cfg.index_heads, cfg.index_head_dim, cfg.rope_dim
        project = lambda x, name, shape, form: jnp.einsum(  # noqa: E731
            form, x, Kernel(shape, name=name)().astype(self.dtype),
            preferred_element_type=f32)
        with jax.named_scope("sparse_index"):
            q_i = project(c_q, "index_q", (c_q.shape[-1], n, Hi),
                          "btr,rnh->btnh")
            k_i = nn.LayerNorm(
                epsilon=1e-6, dtype=f32, param_dtype=f32,
                name="index_k_norm")(
                    project(y, "index_k", (y.shape[-1], Hi), "btd,dh->bth"))
            w_i = project(y, "index_w", (y.shape[-1], n), "btd,dn->btn")
            inv = yarn_inv_freq(rd, cfg.rope_theta, 1.0, 0, 0.0, 0.0)
            turn = lambda x: jnp.concatenate(  # noqa: E731
                [rope_pairs(x[..., :rd], positions, inv), x[..., rd:]],
                axis=-1)
            return (turn(q_i).astype(self.dtype), w_i,
                    turn(k_i).astype(self.dtype))


def softmax_scale(cfg: Any) -> float:
    """``head^-1/2 x m^2``, ``m`` YaRN's ``mscale_all_dim`` gain."""
    m = yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale_all_dim)
    return cfg.head_dim ** -0.5 * m * m


def attention(
    cfg: Any, dtype: Any, norm: Callable[[str], nn.Module],
    y: jax.Array,                    # [B, T, D] the sublayer's normed input
    positions: jax.Array,            # [B, T]
    *,
    pool: Optional[jax.Array] = None,        # [L, P, ps, Wp] latent rows
    layer: int = 0,
    page_table: Optional[jax.Array] = None,  # [B, NP]
    kv_lengths: Optional[jax.Array] = None,  # [B]
    allowed: Optional[jax.Array] = None,     # [B, 1, T, T]: no-cache mask
    indexer: Optional[Indexer] = None,       # a selecting layer's
    index_pool: Optional[jax.Array] = None,  # [L, P, ps, Hip] its keys
) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """-> (the heads' outputs ``[B, T, N, v_head_dim]`` for the caller's
    ``o``, the pool with this chunk's or step's rows written, the index
    keys' plane likewise)."""
    from ray_dynamic_batching_tpu.ops import latent_attention as ops

    N, rank, rope = cfg.num_heads, cfg.kv_lora_rank, cfg.rope_dim
    nope, Hv = cfg.head_dim - rope, cfg.v_head_dim
    B, T = positions.shape
    # The low-rank paths keep float32 between their matmuls (the MXU
    # accumulates in it anyway): a latent is rounded to the model's type
    # ONCE, after its norm, where the next matmul (or the cache) takes it,
    # and a rotary part once, after its rotation. Rounded after every step
    # as a plain layer's activations are, q and k would each carry three
    # roundings where a k/v pair's carry one, and YaRN's sharper softmax
    # (m^2 = 2) doubles what a score's error does to the probabilities.
    f32 = jnp.float32
    project = lambda x, name, shape, form: jnp.einsum(  # noqa: E731
        form, x, Kernel(shape, name=name)().astype(dtype),
        preferred_element_type=f32)
    D = y.shape[-1]
    c_q = norm("q_norm")(project(
        y, "q_down", (D, cfg.q_lora_rank), "btd,dr->btr")).astype(dtype)
    q = project(c_q, "q_up", (cfg.q_lora_rank, N, cfg.head_dim),
                "btr,rnh->btnh")
    ckv = project(y, "kv_down", (D, rank + rope), "btd,dr->btr")
    c_kv = norm("kv_norm")(ckv[..., :rank]).astype(dtype)
    w_ukv = Kernel((rank, N, nope + Hv), name="kv_up")().astype(dtype)
    inv = yarn_inv_freq(
        rope, cfg.rope_theta, cfg.rope_yarn_factor,
        cfg.rope_yarn_original, cfg.rope_yarn_beta_fast,
        cfg.rope_yarn_beta_slow)
    gain = (yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale)
            / yarn_mscale(cfg.rope_yarn_factor,
                          cfg.rope_yarn_mscale_all_dim))
    q_n = q[..., :nope].astype(dtype)
    q_r = rope_pairs(q[..., nope:], positions, inv, gain).astype(dtype)
    # ONE rotary key a position, for all heads
    k_r = rope_pairs(ckv[..., rank:], positions, inv, gain).astype(dtype)
    scale = softmax_scale(cfg)
    if indexer is not None:
        # (imported here: a latent model without an indexer never loads it)
        from ray_dynamic_batching_tpu.ops import sparse_latent_attention
        q_i, w_i, k_i = indexer(c_q, y, positions)

    if pool is None:
        if indexer is not None:
            allowed = sparse_latent_attention.whole_mask(
                q_i, w_i, k_i, jnp.broadcast_to(allowed, (B, 1, T, T)),
                indexer.topk)
        # Whole-sequence attention, keys and values expanded: plain XLA.
        kv = jnp.einsum("bsr,rnh->bsnh", c_kv, w_ukv)
        s = (jnp.einsum("btnh,bsnh->bnts", q_n, kv[..., :nope],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("btnh,bsh->bnts", q_r, k_r,
                          preferred_element_type=jnp.float32)) * scale
        p = jax.nn.softmax(jnp.where(allowed, s, ops.NEG_INF), axis=-1)
        return jnp.einsum("bnts,bsnh->btnh", p.astype(dtype),
                          kv[..., nope:]), None, None

    # The rows go through the slot's page table to (page, offset), as a
    # k/v pair's do (``DecoderLayer``); a position past the table's end
    # or on an unallocated column steers to the sentinel and drops.
    P, ps, Wp = pool.shape[1:]
    n_entries = page_table.shape[1]
    row = jnp.concatenate([c_kv, k_r], axis=-1)
    row = jnp.pad(row, ((0, 0), (0, 0), (0, Wp - row.shape[-1])))
    pidx = jnp.minimum(positions // ps, n_entries - 1)
    pid = jnp.where(positions < n_entries * ps,
                    page_table[jnp.arange(B)[:, None], pidx], P)
    pool = pool.at[layer, pid, positions % ps].set(
        row.astype(pool.dtype), mode="drop")
    select = None
    if indexer is not None:
        # the index key beside the row, through the same (page, offset),
        # written before it is scored as the row is
        index_pool = index_pool.at[layer, pid, positions % ps].set(
            jnp.pad(k_i, ((0, 0), (0, 0),
                          (0, index_pool.shape[-1] - k_i.shape[-1]))
                    ).astype(index_pool.dtype), mode="drop")
        select = sparse_latent_attention.Selection(
            q_i, w_i, index_pool, indexer.topk)
    if T > 1 and select is not None:
        return sparse_latent_attention.chunk(
            q_n, q_r, pool, w_ukv, page_table, kv_lengths, layer, select,
            scale=scale), pool, index_pool
    if T > 1:
        return ops.expanded(q_n, q_r, pool, w_ukv, page_table, kv_lengths,
                            layer, scale=scale), pool, None
    q_abs = jnp.einsum("btnh,rnh->btnr", q_n, w_ukv[..., :nope])
    q_abs = jnp.concatenate([q_abs, q_r], axis=-1)
    q_abs = jnp.pad(q_abs, ((0, 0),) * 3 + ((0, Wp - q_abs.shape[-1]),))
    if select is not None:
        latent = sparse_latent_attention.decode(
            q_abs, pool, page_table, kv_lengths, layer, select, rank=rank,
            scale=scale)
    else:
        latent = ops.decode(q_abs, pool, page_table, kv_lengths, layer,
                            rank=rank, scale=scale)
    return (jnp.einsum("btnr,rnh->btnh", latent, w_ukv[..., nope:]), pool,
            index_pool)
