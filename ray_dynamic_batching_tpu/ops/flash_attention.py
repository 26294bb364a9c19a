"""Pallas TPU flash attention — the framework's hot prefill kernel.

The reference serves its models through eager torch forwards
(``293-project/src/scheduler.py:435-452``); its attention FLOPs live inside
torchvision/HF modules. On TPU the prefill attention is the one op worth a
hand kernel: a fused tiled online-softmax keeps the [Tq, Tk] score matrix out
of HBM entirely (it never materializes), so the op stays MXU-bound instead of
HBM-bound. Decode steps (Tq == 1) stay on the XLA path — they are
bandwidth-bound KV scans where a custom kernel buys nothing.

Design (FlashAttention-2 style, one pass over KV):
- grid (B, N, ceil(Tq/block_q)); each program owns one query tile of one head.
- K/V for the head are resident in VMEM (seq buckets cap Tk, so at 8k seq,
  bf16, H=128 the pair costs 4 MB — comfortably under the block budget of
  ``ops/tile_math.py``, which the wrapper enforces).
- inner ``fori_loop`` over KV tiles carries (m, l, acc) in registers/VMEM:
  m/l rescaling per tile, scores and accumulator in f32 (bf16 inputs go
  through the MXU with f32 accumulation via ``preferred_element_type``).
- causal masking is computed from iota (no mask tensor traffic); an explicit
  mask (padding / decode windows) streams per-tile as int8.
- GQA: query head n reads kv head n // (N // K) via the BlockSpec index map —
  no ``jnp.repeat`` materialization (the XLA fallback pays that copy).
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_dynamic_batching_tpu.ops import tile_math
from ray_dynamic_batching_tpu.ops.pallas_common import (
    declined,
    resolve_interpret,
)

NEG_INF = -1e30

# Query tiles below this aren't worth a kernel launch (decode steps).
MIN_QUERY_FOR_PALLAS = 16


def _attn_kernel(
    q_ref,      # [1, 1, block_q, H]   (B N T H layout: T, H are the tiled dims)
    k_ref,      # [1, 1, Tk, H]
    v_ref,      # [1, 1, Tk, H]
    mask_ref,   # [1, block_q, Tk] int8, or None
    o_ref,      # [1, 1, block_q, H]
    *,
    scale: float,
    causal: bool,
    block_k: int,
    q_len: int,
    kv_len: int,
):
    iq = pl.program_id(2)
    block_q = q_ref.shape[2]
    H = q_ref.shape[3]
    Tk = k_ref.shape[2]
    num_kb = pl.cdiv(Tk, block_k)

    # Keep matmul operands in input dtype (bf16 runs the MXU at full rate;
    # f32 would quarter it) and accumulate in f32 via preferred_element_type.
    q = q_ref[0, 0, :, :]  # [block_q, H]
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    if causal:
        # Query row r may attend keys <= r + (kv_len - q_len); KV tiles fully
        # beyond the last valid diagonal contribute nothing — stop early.
        last_key = (iq + 1) * block_q - 1 + (kv_len - q_len)
        kb_hi = jnp.minimum(num_kb, pl.cdiv(last_key + 1, block_k))
    else:
        kb_hi = num_kb

    def body(jk, carry):
        m_prev, l_prev, acc_prev = carry
        k_tile = k_ref[0, 0, pl.ds(jk * block_k, block_k), :]  # [block_k, H]
        v_tile = v_ref[0, 0, pl.ds(jk * block_k, block_k), :]
        s = jax.lax.dot_general(
            q,
            k_tile,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k] f32

        k_pos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = k_pos < kv_len  # tail tile past Tk
        if causal:
            valid = jnp.logical_and(valid, k_pos <= q_pos + (kv_len - q_len))
        if mask_ref is not None:
            # The streamed mask folds in ARITHMETICALLY (f32 multiply-add),
            # not via boolean ops: an i1 vector derived from a VMEM-streamed
            # tile trips a Mosaic relayout bug ("non-singleton logical
            # dimension is replicated in destination but not in source") on
            # v5 hardware; iota-derived booleans are fine.
            m_tile = mask_ref[0, :, pl.ds(jk * block_k, block_k)]
            mf = m_tile.astype(jnp.float32)                  # 1 keep, 0 drop
            s = s + (mf - 1.0) * (-NEG_INF)
        s = jnp.where(valid, s, NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)          # [block_q, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # A fully-masked row has s == m_new == NEG_INF, where exp(s - m_new)
        # would be 1 — zero those probs explicitly via the validity mask
        # (and the f32 mask for rows masked only by mask_ref).
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)        # [block_q, block_k]
        if mask_ref is not None:
            p = p * mf
        corr = jnp.exp(m_prev - m_new)                       # [block_q, 1]
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_tile.dtype),
            v_tile,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, H] f32
        acc_new = acc_prev * corr + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, H), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, kb_hi, body, (m0, l0, acc0))

    # Fully-masked rows (padding) have l == 0 — emit 0, not NaN.
    out = acc / jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


def _pick_block(t: int, target: int) -> int:
    """Largest divisor of ``t`` at most ``target``, preferring
    sublane-aligned (8-multiple) divisors: a non-dividing block's ds()
    would clamp its start like dynamic_slice and silently re-read
    shifted rows that the validity iota then mislabels, so blocks must
    divide — and unaligned tiles both waste sublanes and trip Mosaic's
    bf16 mixed-type broadcast bug."""
    if t <= target:
        return t
    for cand in range(target, 0, -1):
        if t % cand == 0 and cand % 8 == 0:
            return cand
    for cand in range(target, 0, -1):
        if t % cand == 0:
            return cand
    return t


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def _flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array],
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    B, Tq, N, H = q.shape
    _, Tk, K, _ = k.shape
    group = N // K
    grid = (B, N, pl.cdiv(Tq, block_q))

    # B N T H layout so the tiled dims (T, H) are the trailing two — the TPU
    # lowering requires (8, 128)-aligned trailing block dims. XLA fuses these
    # transposes into the surrounding projections.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    in_specs = [
        pl.BlockSpec(
            (1, 1, block_q, H), lambda b, n, i: (b, n, i, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (1, 1, Tk, H), lambda b, n, i: (b, n // group, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (1, 1, Tk, H), lambda b, n, i: (b, n // group, 0, 0),
            memory_space=pltpu.VMEM,
        ),
    ]
    args = [qt, kt, vt]
    if mask is not None:
        in_specs.append(
            pl.BlockSpec(
                (1, block_q, Tk), lambda b, n, i: (b, i, 0),
                memory_space=pltpu.VMEM,
            )
        )
        args.append(mask)
    else:
        in_specs.append(None)
        args.append(None)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        block_k=block_k,
        q_len=Tq,
        kv_len=Tk,
    )
    if mask is None:
        def kernel_nomask(q_ref, k_ref, v_ref, o_ref):
            return kernel(q_ref, k_ref, v_ref, None, o_ref)

        call_kernel = kernel_nomask
        in_specs = in_specs[:3]
        args = args[:3]
    else:
        call_kernel = kernel

    flops = 4 * B * N * Tq * Tk * H  # qk^T + pv
    bytes_accessed = (
        q.size * q.dtype.itemsize
        + k.size * k.dtype.itemsize
        + v.size * v.dtype.itemsize
        + q.size * q.dtype.itemsize
    )
    out = pl.pallas_call(
        call_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, block_q, H), lambda b, n, i: (b, n, i, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=flops, bytes_accessed=bytes_accessed, transcendentals=B * N * Tq * Tk
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tile_math.VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*args)
    return out.transpose(0, 2, 1, 3)  # back to [B, Tq, N, H]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    why: Optional[List[str]] = None,
) -> Optional[jax.Array]:
    """Fused attention; returns None when the shape isn't worth a kernel
    (tiny decode queries, GQA head counts that don't divide) so the
    dispatcher (:mod:`ray_dynamic_batching_tpu.ops.attention`) falls back
    to XLA, with the reason appended to ``why``.

    Shapes: q [B, Tq, N, H], k/v [B, Tk, K, H], mask broadcastable to
    [B, 1, Tq, Tk] (True = attend).
    """
    B, Tq, N, H = q.shape
    _, Tk, K, _ = k.shape
    if Tq < MIN_QUERY_FOR_PALLAS:
        return declined(
            why, f"flash kernel: Tq={Tq} < {MIN_QUERY_FOR_PALLAS} query "
            "rows is not worth a query-tiled launch")
    if K == 0 or N % K != 0:
        return declined(
            why, f"flash kernel: q heads {N} do not group over kv "
            f"heads {K}")
    scale = scale if scale is not None else H ** -0.5
    block_q = _pick_block(Tq, block_q)
    block_k = _pick_block(Tk, block_k)
    # Sub-32-bit inputs with a sublane-unaligned query tile trip a
    # Mosaic verifier bug (bf16 [197, H] dot under preferred f32 emits a
    # mixed-type vector.broadcast — ViT's CLS+14x14=197 sequence found
    # it); f32 lowers fine at any alignment, so only narrow shapes
    # decline to XLA (pinned in tests/test_tpu_lowering.py).
    if q.dtype.itemsize < 4 and block_q % 8 != 0:
        return declined(
            why, f"flash kernel: {q.dtype} query tile of {block_q} rows "
            "is not sublane-aligned (Mosaic mixed-type broadcast bug)")
    # Degenerate tiling (prime-ish sequence lengths -> width-<8 tiles at
    # <=1/128 MXU utilization, e.g. ViT-G/14's 257) is not worth a
    # kernel: XLA's fused attention handles these shapes well.
    if block_q < 8 or block_k < 8:
        return declined(
            why, f"flash kernel: Tq={Tq}/Tk={Tk} only tile at "
            f"{block_q}x{block_k}, under one sublane group")
    # Per-grid-step VMEM guard sharing the runtime/static footprint model
    # (ops/tile_math.py): the resident K/V pair, the q/out tiles, and the
    # streamed int8 mask tile, all padded and double-buffered, must fit
    # the block budget — the docstring's "K/V comfortably resident"
    # assumption, now enforced instead of assumed. Over-budget shapes
    # (e.g. masked multi-k seq where the [block_q, Tk] mask tile alone
    # costs Tq*Tk bytes) decline to XLA like every other fallback.
    blocks = (
        2 * tile_math.padded_block_bytes((1, 1, Tk, H), k.dtype.itemsize)
        + 2 * tile_math.padded_block_bytes((1, 1, block_q, H),
                                           q.dtype.itemsize)
    )
    if mask is not None:
        blocks += tile_math.padded_block_bytes((1, block_q, Tk), 1)
    if tile_math.DOUBLE_BUFFER * blocks > tile_math.VMEM_BLOCK_BUDGET_BYTES:
        return declined(
            why, f"flash kernel: resident K/V [Tk={Tk}, H={H}]"
            + (" plus the mask tile" if mask is not None else "")
            + " exceeds the VMEM block budget")
    interpret = resolve_interpret(interpret)

    mask_i8 = None
    if mask is not None:
        # [B, 1, Tq, Tk] (or broadcastable) -> dense [B, Tq, Tk] int8 tiles.
        m4 = jnp.broadcast_to(mask, (B, 1, Tq, Tk)) if mask.ndim == 4 else mask
        mask_i8 = jnp.broadcast_to(
            m4.reshape(B, Tq, Tk) if m4.ndim == 4 else m4, (B, Tq, Tk)
        ).astype(jnp.int8)
    return _flash_attention(
        q, k, v, mask_i8,
        causal=causal, scale=float(scale),
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
