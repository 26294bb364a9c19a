"""Attention ops with backend dispatch (XLA reference, Pallas on TPU).

The reference framework has no attention of its own (it serves fixed-shape
vision models through torch); attention enters via the north-star LLM configs.
This module is the single place models get attention from, so the engine can
swap the XLA einsum reference for the fused Pallas kernels
(:mod:`ray_dynamic_batching_tpu.ops.decode_attention`,
:mod:`ray_dynamic_batching_tpu.ops.flash_attention`) on TPU without touching
model code.

Which path a call took is never a guess: every dispatch appends one
:class:`AttentionPath` to a bounded trace-time record — the path, the
program being traced (the compile ledger's frame), and the reason each
kernel tried before it declined. ``chip_smoke.py`` prints it as the
*paths* table.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_dynamic_batching_tpu.models.kv_state import (
    dequantize_kv,
    from_pool_rows,
)
from ray_dynamic_batching_tpu.ops.pallas_common import resolve_interpret
from ray_dynamic_batching_tpu.utils.compile_ledger import current_program

# "auto": Pallas on a TPU backend, XLA elsewhere. "xla": the reference
# everywhere. "pallas": the kernels everywhere (interpret mode off-TPU)
# and STRICT — a call every kernel declines raises AttentionDeclined
# with the reasons instead of quietly running the reference.
_BACKEND = "auto"

PATH_PAGED_KERNEL = "paged_kernel"
PATH_SLAB_KERNEL = "slab_kernel"
PATH_FLASH = "flash"
PATH_XLA = "xla"
PATH_BLOCKED = "blocked"


class AttentionDeclined(ValueError):
    """Every Pallas kernel declined a call made under the strict
    ``"pallas"`` backend."""


@dataclasses.dataclass(frozen=True)
class AttentionPath:
    """One dispatch, recorded while its program traced."""

    program: str              # compile-ledger program ("" outside one)
    path: str                 # PATH_*; gathered paged reads say so below
    gathered: bool            # paged pool gathered to a slab view first
    stacked: bool             # paged kernel read the [L, ...] stack whole
    tp: int                   # shard_map width of the kernel call (1 = none)
    interpret: bool           # kernel ran interpreted (never on a TPU)
    q_shape: Tuple[int, ...]
    kv_shape: Tuple[int, ...]
    kv_dtype: str
    declines: Tuple[str, ...]  # why each kernel tried first said no
    sliding: int = 0          # a sliding layer's window on a paged read
    v_dim: int = 0            # a value row's width where not the key's
    sink: bool = False        # a learned sink in the softmax
    heads_per_row: int = 1    # KV heads side by side in a pool row

    def describe(self) -> str:
        name = {
            PATH_PAGED_KERNEL: "paged kernel",
            PATH_SLAB_KERNEL: "slab kernel",
            PATH_FLASH: "flash kernel",
            PATH_XLA: "XLA einsum",
            PATH_BLOCKED: "XLA online softmax over blocks of pages",
        }[self.path]
        how = (["stacked pool"] if self.stacked else []) + (
            [f"shard_map tp={self.tp}"] if self.tp > 1 else []) + (
            [f"window {self.sliding}"] if self.sliding else []) + (
            [f"v rows {self.v_dim}"] if self.v_dim else []) + (
            ["sink"] if self.sink else []) + (
            [f"{self.heads_per_row} heads a row"]
            if self.heads_per_row > 1 else [])
        if how:
            name += f" ({', '.join(how)})"
        return ("gather-then-" if self.gathered else "") + name


# Bounded: one entry per traced attention call (a 24-layer program
# leaves 24), oldest dropped first. Trace-time only — a cached dispatch
# never reaches this module.
_PATHS: collections.deque = collections.deque(maxlen=4096)


def attention_paths() -> List[AttentionPath]:
    """The recorded dispatches, oldest first."""
    return list(_PATHS)


def clear_attention_paths() -> None:
    _PATHS.clear()


def _record(path: str, q, k, declines: List[str], *, gathered: bool = False,
            stacked: bool = False, tp: int = 1, sliding: int = 0,
            v_dim: int = 0, sink: bool = False,
            heads_per_row: int = 1) -> None:
    kernel = path in (PATH_PAGED_KERNEL, PATH_SLAB_KERNEL, PATH_FLASH)
    _PATHS.append(AttentionPath(
        program=current_program(), path=path, gathered=gathered,
        stacked=stacked, tp=tp,
        interpret=kernel and resolve_interpret(None),
        q_shape=tuple(q.shape), kv_shape=tuple(k.shape),
        kv_dtype=str(k.dtype), declines=tuple(declines), sliding=sliding,
        v_dim=v_dim, sink=sink, heads_per_row=heads_per_row,
    ))


# (mesh, axis) when sequence parallelism is active. ContextVar, not a module
# global: concurrent jit traces (e.g. a serve replica warming up while a
# train step traces) must not observe each other's mesh.
_SP_CTX: contextvars.ContextVar[Optional[Tuple]] = contextvars.ContextVar(
    "sequence_parallel_ctx", default=None
)
# (mesh, axis) when a TP serving slice is active (ROADMAP item 2): the
# paged decode kernel must run per-shard under shard_map — GSPMD cannot
# partition a pallas_call on its own — so the engine names its slice
# here and the dispatcher threads it into the kernel wrapper. Same
# ContextVar discipline (and the same enter-inside-the-traced-function
# contract) as the sequence-parallel context above.
_TP_CTX: contextvars.ContextVar[Optional[Tuple]] = contextvars.ContextVar(
    "tensor_parallel_ctx", default=None
)


def set_attention_backend(backend: str) -> None:
    global _BACKEND
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown attention backend {backend!r}")
    _BACKEND = backend


@contextlib.contextmanager
def sequence_parallel(mesh, axis: str = "sp"):
    """While active (including during jit tracing), :func:`self_attention`
    routes through the ring-attention kernel over the mesh's ``axis`` when
    that axis has more than one device. The trace-time context is baked into
    the compiled program, so enter it inside the jitted step function."""
    token = _SP_CTX.set((mesh, axis))
    try:
        yield
    finally:
        _SP_CTX.reset(token)


@contextlib.contextmanager
def tensor_parallel(mesh, axis: str = "tp"):
    """While active (including during jit tracing), every Pallas kernel
    runs per head shard under ``shard_map`` over the mesh's ``axis`` —
    GSPMD cannot partition a ``pallas_call`` (on a TPU the lowering
    refuses: "Mosaic kernels cannot be automatically partitioned"). The
    paged kernel has its own wrapper (``paged_decode_attention``'s
    ``mesh`` parameter: q and the page pools split on the kv-head dim,
    page table and lengths replicated — page indices are
    shard-invariant); the slab-layout kernels go through
    :func:`_dense_kernel`. The XLA paths need no context: plain jnp,
    which GSPMD partitions from the operands' shardings. A TP engine
    holds it around everything it traces
    (``DecodeEngine._device_ctx``)."""
    token = _TP_CTX.set((mesh, axis))
    try:
        yield
    finally:
        _TP_CTX.reset(token)


def _tp_slice() -> Tuple[Optional[object], str, int]:
    """(mesh, axis, width) of the active :func:`tensor_parallel` slice;
    width 1 (mesh None) when there is none."""
    ctx = _TP_CTX.get()
    if ctx is None:
        return None, "", 1
    mesh, axis = ctx
    return mesh, axis, int(mesh.shape.get(axis, 1))


def tensor_parallel_width() -> int:
    """Width of the active :func:`tensor_parallel` slice (1: none)."""
    return _tp_slice()[2]


def self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    token_mask: Optional[jax.Array] = None,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Self-attention over a full (un-cached) sequence; q/k/v [B, T, *, H],
    token_mask [B, T] True = valid. Under an active :func:`sequence_parallel`
    context with sp > 1 this dispatches to ring attention (sequence sharded
    over the ``sp`` mesh axis); otherwise dense attention with the causal +
    padding mask built here."""
    ctx = _SP_CTX.get()
    if ctx is not None:
        mesh, axis = ctx
        if mesh.shape.get(axis, 1) > 1:
            from ray_dynamic_batching_tpu.ops.ring_attention import (
                ring_self_attention,
            )

            return ring_self_attention(
                mesh, q, k, v, token_mask, causal=causal, scale=scale,
                axis=axis,
            )
    mask = None
    if token_mask is not None:
        mask = token_mask[:, None, None, :].astype(bool)
    return dot_product_attention(q, k, v, causal=causal, mask=mask, scale=scale)


def _use_pallas() -> bool:
    if _BACKEND == "xla":
        return False
    if _BACKEND == "pallas":
        return True
    return jax.default_backend() == "tpu"


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,
    kv_lengths: Optional[jax.Array] = None,
    layer: int = 0,
    sliding: int = 0,
    select: Optional[tuple] = None,
    sink: Optional[jax.Array] = None,
    v_dim: int = 0,
    heads_per_row: int = 1,
) -> jax.Array:
    """Multi-head attention.

    Shapes: q [B, Tq, N, H], k/v [B, Tk, K, H] with K == N or K dividing N
    (grouped-query attention: each group of N//K query heads shares a kv head).
    mask: broadcastable to [B, 1, Tq, Tk], True = attend.

    ``k_scale``/``v_scale`` [B, Tk, K]: k/v are int8 KV-cache codes
    (models/kv_state.py::KVCache). The decode kernel consumes the codes
    directly (1-byte scan, scales applied inside the dots); every other
    path dequantizes first and proceeds as usual.

    ``page_table`` [B, NP] + ``kv_lengths`` [B] switch to the PAGED
    decode read: k/v are the STACKED page pools [L, P, ps, K, H], passed
    whole, and ``layer`` names the layer to read — an index, never a
    slice, so no program makes an array the size of a layer of the pool
    (a single layer's [P, ps, K, H] pool is taken as a one-layer stack);
    the scales are that layer's [P, ps, K] planes. Each slot's logical
    KV run is the table-ordered gather of its pages. The Pallas paged
    kernel fuses that gather into the KV scan (no logical-view
    materialization in HBM); everywhere else ONE explicit gather over
    (layer, page) rebuilds the slab view and re-enters this function —
    one mask/dequant rule, so paged and slab reads are token-exact
    against each other. ``sliding`` > 0 (paged reads only; elsewhere the
    window rides the caller's mask) is a sliding-window layer: each row
    attends its last ``sliding`` positions, by the one rule of
    ``models/decoder.py::paged_window_mask``. ``select`` (paged reads
    only; ``ops/sparse_attention.py::Selection``) is a layer with an
    indexer: each row attends only its best-scored positions of those.
    ``v_dim`` > 0 (paged reads only; the value head's width) marks a layer
    of a model with state by layer kind: its v rows may be narrower than
    its k rows and its softmax may carry a learned ``sink`` ([N] float32,
    or None): the paged kernel takes both, and ``ops/kind_attention.py``
    every other read. ``heads_per_row`` > 1 (paged reads only): the pools
    are ``[L, P, ps, K // f, f * H]``, ``f`` KV heads side by side in a
    row (``models/kv_state.py::pool_heads_per_row``): the paged kernel
    reads them as they lie, the gather reshapes its pages' rows back to
    ``[.., K, H]``.
    """
    if page_table is not None:
        return _paged_attention(
            q, k, v, page_table, kv_lengths, layer, mask=mask,
            scale=scale, k_scale=k_scale, v_scale=v_scale, sliding=sliding,
            select=select, sink=sink, v_dim=v_dim,
            heads_per_row=heads_per_row,
        )
    if heads_per_row != 1:
        raise ValueError("heads_per_row is the paged read's")
    if v_dim or sink is not None:
        raise ValueError("sink and v_dim are the paged read's")
    if select is not None:
        raise ValueError("select is the paged read's; elsewhere a "
                         "selection rides the mask")
    if sliding:
        raise ValueError("sliding is the paged read's; elsewhere a window "
                         "rides the mask")
    return _dense_attention(
        q, k, v, causal=causal, mask=mask, scale=scale,
        k_scale=k_scale, v_scale=v_scale, declines=[], gathered=False,
    )


class _ShardDeclined(Exception):
    """A kernel wrapper declined inside a shard_map body (a traced
    function cannot return None); :func:`_dense_kernel` turns it back
    into the wrapper's None."""


def _dense_kernel(
    kernel: Callable[..., Optional[jax.Array]],
    q: jax.Array, k: jax.Array, v: jax.Array,
    mask: Optional[jax.Array],
    k_scale: Optional[jax.Array], v_scale: Optional[jax.Array],
    declines: List[str],
) -> Tuple[Optional[jax.Array], int]:
    """Call a slab-layout kernel wrapper ``kernel(q, k, v, mask, k_scale,
    v_scale)`` — directly, or per head shard under an active
    :func:`tensor_parallel` slice: q/k/v (and the int8 scale planes)
    split on their head axis, the head-invariant mask replicated, so
    each shard's call is the ordinary single-device kernel on its head
    slice and decides its own eligibility from the shapes it will
    actually stream. Returns (output or None, shard_map width)."""
    from jax.sharding import PartitionSpec as P

    mesh, axis, tp = _tp_slice()
    if tp == 1:
        return kernel(q, k, v, mask, k_scale, v_scale), 1
    N, K = q.shape[2], k.shape[2]
    if N % tp or K % tp:
        declines.append(
            f"tp={tp}: heads {N}/{K} do not divide over the slice, and "
            "GSPMD cannot partition a Pallas call")
        return None, tp
    heads = P(None, None, axis, None)
    operands = {"q": (q, heads), "k": (k, heads), "v": (v, heads)}
    if mask is not None:
        operands["mask"] = (mask, P())
    if k_scale is not None:
        operands["k_scale"] = (k_scale, P(None, None, axis))
        operands["v_scale"] = (v_scale, P(None, None, axis))
    names = list(operands)

    def local(*args):
        got = dict(zip(names, args))
        out = kernel(got["q"], got["k"], got["v"], got.get("mask"),
                     got.get("k_scale"), got.get("v_scale"))
        if out is None:
            raise _ShardDeclined
        return out

    try:
        # check_vma=False: pallas_call declares no varying-axes rule,
        # and every operand's layout over ``axis`` is stated above.
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=tuple(operands[n][1] for n in names),
            out_specs=heads, check_vma=False,
        )(*(operands[n][0] for n in names)), tp
    except _ShardDeclined:
        return None, tp


def _dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    mask: Optional[jax.Array],
    scale: Optional[float],
    k_scale: Optional[jax.Array],
    v_scale: Optional[jax.Array],
    declines: List[str],
    gathered: bool,
    sliding: int = 0,   # for the record only: the window is in ``mask``
) -> jax.Array:
    """Slab-layout attention: decode kernel, else flash kernel, else the
    XLA reference — each decline's reason lands in ``declines`` (seeded
    by the paged path when it gathered its way here)."""
    if _use_pallas():
        if not causal:
            # Small query windows — plain decode (Tq == 1), speculative
            # verify (Tq == k+1), small prefill buckets: the fused
            # KV-scan kernel — GQA via layout (no jnp.repeat of the
            # cache read), online softmax in VMEM
            # (ops/decode_attention.py). Window semantics ride the
            # explicit mask, so only non-causal calls qualify; the
            # kernel itself owns the eligibility band and declines
            # wider windows.
            from ray_dynamic_batching_tpu.ops import decode_attention

            out, tp = _dense_kernel(
                lambda q, k, v, mask, ks, vs:
                decode_attention.decode_attention(
                    q, k, v, mask=mask, scale=scale,
                    k_scale=ks, v_scale=vs, why=declines,
                ),
                q, k, v, mask, k_scale, v_scale, declines,
            )
            if out is not None:
                _record(PATH_SLAB_KERNEL, q, k, declines,
                        gathered=gathered, tp=tp, sliding=sliding)
                return out
        else:
            declines.append("decode kernel: causal=True call (its "
                            "windows ride an explicit mask)")
        if k_scale is not None:
            k, v = dequantize_kv(k, k_scale, q.dtype), dequantize_kv(
                v, v_scale, q.dtype)
            k_scale = v_scale = None
        from ray_dynamic_batching_tpu.ops import flash_attention

        out, tp = _dense_kernel(
            lambda q, k, v, mask, ks, vs: flash_attention.flash_attention(
                q, k, v, causal=causal, mask=mask, scale=scale,
                why=declines,
            ),
            q, k, v, mask, None, None, declines,
        )
        if out is not None:
            _record(PATH_FLASH, q, k, declines, gathered=gathered, tp=tp,
                    sliding=sliding)
            return out
        if _BACKEND == "pallas":
            raise AttentionDeclined(
                f"attention backend 'pallas' is strict and every kernel "
                f"declined q{tuple(q.shape)} kv{tuple(k.shape)}: "
                + "; ".join(declines)
            )
    else:
        declines.append(
            f"pallas off: backend {_BACKEND!r} on "
            f"{jax.default_backend()}")
    if k_scale is not None:
        k, v = dequantize_kv(k, k_scale, q.dtype), dequantize_kv(
            v, v_scale, q.dtype)
    _record(PATH_XLA, q, k, declines, gathered=gathered, sliding=sliding)
    return _xla_attention(q, k, v, causal=causal, mask=mask, scale=scale)


def _paged_attention(
    q: jax.Array,
    k: jax.Array,              # [L, P, ps, K, H] stacked page pool
    v: jax.Array,
    page_table: jax.Array,     # [B, NP] int32, sentinel P = unallocated
    kv_lengths: jax.Array,     # [B] valid logical prefix (attend <= len)
    layer: int,                # which layer of the stack to read
    *,
    mask: Optional[jax.Array],
    scale: Optional[float],
    k_scale: Optional[jax.Array],   # [P, ps, K] or None
    v_scale: Optional[jax.Array],
    sliding: int = 0,
    select: Optional[tuple] = None,
    sink: Optional[jax.Array] = None,
    v_dim: int = 0,
    heads_per_row: int = 1,
) -> jax.Array:
    """Paged decode read: fused page-table KV scan on the Pallas path,
    explicit gather back to the slab view otherwise (the token-exact
    fallback — identical values land in identical logical positions, and
    the shared ``decode_mask`` rule bounds what is attended). A layer with
    an indexer (``select``) never takes the paged kernel, which attends a
    contiguous prefix: ``ops/sparse_attention.py`` reads it, in its decode
    form or through the gather fallback below with the selection in the
    mask."""
    if mask is not None:
        raise ValueError(
            "paged attention derives its window from kv_lengths; an "
            "explicit mask on this path means a caller mixed the slab "
            "and paged conventions"
        )
    stacked = k.ndim == 5
    if not stacked:
        # One layer's pool: a one-layer stack (a free reshape).
        k, v, layer = k[None], v[None], 0
    declines: List[str] = []
    # what only a layer of a model with state by layer kind passes on
    kind = {"sink": sink, "v_dim": v_dim} if v_dim else {}
    kind_record = ({"v_dim": int(v.shape[-1]), "sink": sink is not None}
                   if v_dim else {})
    if heads_per_row > 1:
        if kind or select is not None or k_scale is not None:
            raise ValueError(
                "a pool with several heads a row has no sink, no narrower "
                "v row, no indexer and no scale planes "
                "(models/kv_state.py::pool_heads_per_row)")
        kind = kind_record = {"heads_per_row": heads_per_row}
    if select is not None:
        from ray_dynamic_batching_tpu.ops import sparse_attention

        if sliding:
            raise ValueError("a selecting layer has no sliding window")
        out = sparse_attention.paged_decode(
            q, k, v, page_table, kv_lengths, layer, select, scale=scale,
            k_scale=k_scale, why=declines)
        if out is not None:
            return out
        declines.append("paged kernel: a selecting layer attends a "
                        "learned subset, not a prefix")
    elif _use_pallas():
        from ray_dynamic_batching_tpu.ops import decode_attention

        tp_mesh, tp_axis, tp = _tp_slice()
        mesh_kwargs = {}
        if tp > 1:
            mesh_kwargs = {"mesh": tp_mesh, "mesh_axis": tp_axis}
        out = decode_attention.paged_decode_attention(
            q, k, v, page_table, kv_lengths, layer=layer, scale=scale,
            k_scale=k_scale, v_scale=v_scale, why=declines,
            sliding=sliding, **mesh_kwargs, **kind,
        )
        if out is not None:
            _record(PATH_PAGED_KERNEL, q, k, declines, stacked=stacked,
                    tp=tp, sliding=sliding, **kind_record)
            return out
    if v_dim:
        # A sink, a narrower value row: no gather-then-kernel form takes
        # them. The table is walked in blocks of pages in plain XLA (a
        # chunk's rows on the chip; every read where Pallas is off).
        from ray_dynamic_batching_tpu.ops import kind_attention

        if k_scale is not None:
            raise ValueError("a layer with a sink has no int8 pool")
        if _BACKEND == "pallas" and q.shape[1] <= 8:
            raise AttentionDeclined(
                "attention backend 'pallas' is strict and the paged kernel "
                f"declined q{tuple(q.shape)}: " + "; ".join(declines))
        _record(PATH_BLOCKED, q, k, declines, stacked=stacked,
                sliding=sliding, **kind_record)
        with jax.named_scope("chunk_attention_window" if sliding
                             else "chunk_attention_full"):
            return kind_attention.paged(
                q, k, v, page_table, kv_lengths, layer, sliding=sliding,
                scale=scale, sink=sink)[..., :v_dim]
    # Gather fallback: rebuild each slot's logical KV run [B, S, K, H]
    # (S = NP * ps) and re-enter the slab path. Sentinel/garbage pages
    # clamp to a real page, then the length mask voids their positions —
    # the same never-attended-garbage invariant the slab cache relies on.
    # Tq > 1 is the speculative-verify window: the STAIRCASE mask (row t
    # attends <= lengths + t, paged_window_mask — the same rule the
    # kernel computes in-VMEM from the prefetched lengths).
    # The layer rides the SAME gather as the page ids (pool[layer, safe]
    # is one gather over two collapsed axes), so no layer of the pool is
    # materialised on the way.
    from ray_dynamic_batching_tpu.models.decoder import paged_window_mask

    P, ps = k.shape[1], k.shape[2]
    base = real = None
    if sliding:
        # A sliding layer's view is the table columns its rows' windows
        # cover (the kernel's own sub-table), not the slot's whole run: a
        # 512-token chunk reads 6 pages of a 32-page table.
        from ray_dynamic_batching_tpu.ops.decode_attention import (
            window_table,
        )

        capacity = page_table.shape[1] * ps
        page_table, first = window_table(
            page_table, kv_lengths, sliding, q.shape[1], ps)
        base = first * ps
        # columns past the table's end repeat its last entry: no position
        real = (base[:, None] + jnp.arange(page_table.shape[1] * ps)
                < capacity)[:, None, None, :]
    safe = jnp.minimum(page_table, P - 1)
    B, NP = page_table.shape

    def logical(g):  # [B, NP, ps, ...] -> [B, NP * ps, ...]
        return g.reshape((B, NP * ps) + g.shape[3:])

    # Pool rows are lane-padded (models/kv_state.py::pool_head_dim): the
    # slab view is cut back to the head AFTER the gather. Gathering the
    # head's lanes only (pool[layer, safe, :, :, :H]) reads half the
    # bytes on paper, but XLA then re-lays the whole pool out for that
    # gather: four pool-sized copies in the chunk program
    # (tools/pool_traffic.py). Rows that hold several heads side by side
    # hold no padding: the gathered pages are the slab view's own bytes.
    H = q.shape[-1]
    K = k.shape[3] * heads_per_row
    k_g = from_pool_rows(logical(_pages(k, layer, safe, K, select)), K, H)
    v_g = from_pool_rows(logical(_pages(v, layer, safe, K, select)), K, H)
    ks_g = vs_g = None
    if k_scale is not None:
        ks_g, vs_g = logical(k_scale[safe]), logical(v_scale[safe])
    win = paged_window_mask(kv_lengths, NP * ps, q.shape[1], sliding, base)
    if real is not None:
        win = win & real
    if select is not None:
        win = sparse_attention.paged_select_mask(select, layer, safe, win)
    return _dense_attention(
        q, k_g, v_g, causal=False, mask=win, scale=scale,
        k_scale=ks_g, v_scale=vs_g, declines=declines, gathered=True,
        sliding=sliding,
    )


def _xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    mask: Optional[jax.Array],
    scale: Optional[float],
) -> jax.Array:
    B, Tq, N, H = q.shape
    _, Tk, K, _ = k.shape
    if K != N:
        assert N % K == 0, f"query heads {N} not divisible by kv heads {K}"
        k = jnp.repeat(k, N // K, axis=2)
        v = jnp.repeat(v, N // K, axis=2)
    scale = scale if scale is not None else H ** -0.5
    # [B, N, Tq, Tk] logits in f32 for numerical stability on bf16 inputs.
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool), k=Tk - Tq)
        logits = jnp.where(causal_mask[None, None, :, :], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknh->bqnh", probs, v)


# NOTE: decode-path masking lives in models/decoder.py (decode_mask) — the
# single owner of the KV-cache attention-window convention.


def _pages(pool: jax.Array, layer: int, safe: jax.Array,
           kv_heads: int, select: Optional[object] = None) -> jax.Array:
    """``pool[layer, safe]``: the pages ``safe`` ``[B, NP]`` of one layer of
    a stacked pool ``[L, P, ps, K_pool, Hp]``. Where a position is FEWER
    rows than a sublane tile (8 KV heads of 64 side by side: 4 rows of 128;
    4 KV heads of 128: 4 rows), the pages
    are gathered through the pool's view ``[ps * rows //
    8, 8, Hp]`` (the same bytes: a bitcast where a row is one lane tile)
    and viewed back: for a gather of 4-row positions XLA lays the WHOLE
    pool out anew, positions under rows — two pool-sized copies an
    attention layer a chunk program — and for one of 8-row tiles,
    gpt2-medium's, it does not (``tools/pool_traffic.py``). A selecting
    layer's gather (``select``: Keye's 4 heads of 128) keeps
    ``pool[layer, safe]`` until its own cell has measured the view
    (ROADMAP S8). Here at the
    file's END, and called from lines that were there: a Mosaic module
    carries its callers' source lines (PERF.md, section 7), and nothing
    above a kernel's call may move."""
    L, P, ps, rows, width = pool.shape
    narrow = rows < kv_heads or (rows < 8 and select is None)
    if (L > 1 and rows < 8 and narrow and 8 % rows == 0 and width == 128
            and pool.dtype.itemsize == 2 and (ps * rows) % 8 == 0):
        view = pool.reshape(L, P, ps * rows // 8, 8, width)
        return view[layer, safe].reshape(safe.shape + pool.shape[2:])
    return pool[layer, safe]
