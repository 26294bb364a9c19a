"""Expert compute with backend dispatch (grouped Pallas kernel on TPU,
``jax.lax.ragged_dot`` elsewhere): the one door ``models/moe.py`` gets its
expert products from, in the shape of :mod:`ops.attention`.

The caller has already routed: ``xs`` [M, D] holds the M = tokens x top_k
routed rows SORTED BY EXPERT and ``group_sizes`` [E] says how many rows
each expert drew (a group may be empty; they sum to M, or to less where
the caller holds some of the experts only: the rows behind the last group
are no expert's, cost no work item, and their results are undefined). Both
paths compute,
for a row r of expert e,

    ys[r] = act(xs[r] @ wi[e]) @ wo[e]          act = gelu
    ys[r] = (silu(xs[r] @ wg[e]) * (xs[r] @ wi[e])) @ wo[e]    (gated)

on the same sorted rows and group sizes, so the CPU tests run the sort,
the grouping and the combine that the chip runs. The kernel is a grouped
matmul over RAGGED groups: its work items are the (expert, row tile) pairs
that overlap, found outside the kernel from ``group_sizes`` and handed in
as scalar-prefetch tables that drive the block maps. An expert with no row
owns no work item, so its weights are never read; an expert whose rows
straddle a tile boundary keeps its weight block across the two items (the
block index does not change, so nothing is fetched twice).

Which path a call took is recorded at trace time (:func:`moe_paths`), as
``attention_paths()`` records its own.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_dynamic_batching_tpu.ops.pallas_common import (
    declined,
    resolve_interpret,
)
from ray_dynamic_batching_tpu.ops.tile_math import (
    VMEM_LIMIT_BYTES,
    moe_tile_cols,
)
from ray_dynamic_batching_tpu.utils.compile_ledger import current_program

# "auto": the kernel on a TPU backend, ragged_dot elsewhere. "xla":
# ragged_dot everywhere. "pallas": the kernel everywhere (interpret mode
# off-TPU) and STRICT, as ops/attention.py's.
_BACKEND = "auto"

PATH_KERNEL = "grouped_kernel"
PATH_XLA = "ragged_dot"

# The kernel's name in a device trace (``benchmark/trace_reduce.py``'s
# ``stable_name``): readers find it by this.
KERNEL_NAME = "moe_grouped_matmul"


class MoEDeclined(ValueError):
    """The grouped kernel declined a call made under the strict
    ``"pallas"`` backend."""


@dataclasses.dataclass(frozen=True)
class MoEPath:
    """One expert-layer dispatch, recorded while its program traced."""

    program: str               # compile-ledger program ("" outside one)
    path: str                  # PATH_*
    interpret: bool            # kernel ran interpreted (never on a TPU)
    rows: int                  # M routed rows
    experts: int
    d_model: int
    mlp_dim: int
    tile_rows: int             # the kernel's row tile (0 on the XLA path)
    declines: Tuple[str, ...]  # why the kernel said no

    def describe(self) -> str:
        if self.path == PATH_KERNEL:
            return (f"grouped Pallas kernel ({self.tile_rows}-row tiles"
                    + (", interpreted)" if self.interpret else ")"))
        return "XLA ragged_dot"


_PATHS: collections.deque = collections.deque(maxlen=4096)


def moe_paths() -> List[MoEPath]:
    """The recorded dispatches, oldest first."""
    return list(_PATHS)


def clear_moe_paths() -> None:
    _PATHS.clear()


def set_moe_backend(backend: str) -> None:
    global _BACKEND
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown MoE backend {backend!r}")
    _BACKEND = backend


def _under_mesh() -> bool:
    """A mesh is in context (``with mesh:``, ``jax.set_mesh`` or a TP
    engine's :func:`~ops.attention.tensor_parallel` slice): operands may
    be sharded, and GSPMD cannot partition a ``pallas_call``."""
    from jax.interpreters import pxla

    from ray_dynamic_batching_tpu.ops.attention import tensor_parallel_width

    return (not pxla.thread_resources.env.physical_mesh.empty
            or not jax.sharding.get_abstract_mesh().empty
            or tensor_parallel_width() > 1)


def expert_mlp(xs: jax.Array, group_sizes: jax.Array, wi: jax.Array,
               wo: jax.Array, wg: Optional[jax.Array] = None) -> jax.Array:
    """The experts' MLPs on rows sorted by expert. xs [M, D];
    group_sizes [E] int32 summing to at most M; wi (and wg, for gated experts)
    [E, D, F]; wo [E, F, D]. Returns [M, D] in ``xs.dtype`` (accumulation
    in float32 on both paths)."""
    M, D = xs.shape
    E, _, F = wi.shape
    declines: List[str] = []
    use_kernel = _BACKEND == "pallas" or (
        _BACKEND == "auto" and jax.default_backend() == "tpu")
    tm = 0
    if use_kernel:
        tm = _kernel_tile_rows(xs, wi, declines) or 0
    if _BACKEND == "pallas" and not tm:
        raise MoEDeclined("; ".join(declines))
    _PATHS.append(MoEPath(
        program=current_program(), path=PATH_KERNEL if tm else PATH_XLA,
        interpret=bool(tm) and resolve_interpret(None), rows=M, experts=E,
        d_model=D, mlp_dim=F, tile_rows=tm, declines=tuple(declines)))
    if tm:
        return _kernel_mlp(xs, group_sizes, wi, wo, wg, tm)
    return _xla_mlp(xs, group_sizes, wi, wo, wg)


# --- the XLA form -----------------------------------------------------------
def _xla_mlp(xs, group_sizes, wi, wo, wg):
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                            preferred_element_type=jnp.float32)
    h = dot(xs, wi)
    h = jax.nn.silu(dot(xs, wg)) * h if wg is not None else jax.nn.gelu(h)
    return dot(h.astype(xs.dtype), wo).astype(xs.dtype)


# --- the grouped kernel -------------------------------------------------------
def _kernel_tile_rows(xs, wi, why: List[str]) -> Optional[int]:
    """The kernel's row tile for this call, or None (reason on ``why``)
    for shapes it is not built for."""
    M, D = xs.shape
    E, _, F = wi.shape
    if _under_mesh():
        return declined(why, "a mesh is in context and GSPMD cannot "
                             "partition a Pallas call")
    if xs.dtype not in (jnp.bfloat16, jnp.float32) or wi.dtype != xs.dtype:
        return declined(why, f"dtypes {xs.dtype}/{wi.dtype}: rows and "
                             "weights must both be bfloat16 or float32")
    if D % 128 or F % 128:
        return declined(why, f"widths D={D} F={F} are not multiples of "
                             "the 128 lanes")
    # As wide as the rows allow, up to 128. A decode substep (32 slots x 8
    # = 256 rows over 64 experts) gives an expert ~4 rows: its cost is its
    # weight block read and pushed through the MXU, which a wider row tile
    # does not raise, while every tile boundary an expert's rows straddle
    # is a further matmul on the same weights. Measured on a v5e, one
    # layer's experts (PERF.md, PR 27): at 256 rows 1.14-1.15 ms with tiles
    # of 32-128 against 1.20 at 16 and 1.24 at 256; at 4,096 rows (a
    # prefill chunk: ~64 an expert) 1.54 at 128 against 1.66 at 64, 2.13 at
    # 16 and 2.48 at 512.
    return int(min(128, max(16, pl.next_power_of_2(-(-M // 16) * 16))))


def _work_items(group_sizes: jax.Array, tiles: int, tm: int):
    """The (expert, row tile) pairs that overlap, in row order, as tables
    of static length ``tiles + E - 1`` (the most there can be): expert and
    tile of each item, each expert's first row and end, and how many items
    are real. Items past the last real one repeat it, so their block maps
    fetch nothing new."""
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    item_end = jnp.cumsum(n_tiles)
    num = item_end[-1]
    # (no row at all, where this rank's experts drew none: one unreal
    # item, on an expert and a tile that exist)
    item = jnp.maximum(
        jnp.minimum(jnp.arange(tiles + E - 1, dtype=jnp.int32), num - 1), 0)
    # the first expert whose items end past this one
    group = jnp.minimum(
        (item[:, None] >= item_end[None, :]).sum(-1), E - 1
    ).astype(jnp.int32)
    tile = first[group] + item - (item_end[group] - n_tiles[group])
    return group, tile.astype(jnp.int32), starts, ends, num[None]


def _grouped_matmul(xs, tables, w, w_gate, act: Optional[str], tm: int,
                    interpret: bool):
    """out[r] = act(xs[r] @ w[e(r)]) (``act`` None: the plain product;
    with ``w_gate``: silu(xs[r] @ w_gate[e]) * (xs[r] @ w[e])) for rows
    sorted by expert; xs [Mp, K] with Mp a multiple of ``tm``."""
    Mp, K = xs.shape
    E, _, N = w.shape
    tn = moe_tile_cols(K, N, 1 if w_gate is None else 2, w.dtype.itemsize)
    n_items = Mp // tm + E - 1

    def moe_grouped_matmul(group_ref, tile_ref, start_ref, end_ref, num_ref,
                           x_ref, *refs):
        w_ref, o_ref = refs[0], refs[-1]
        i = pl.program_id(1)

        @pl.when(i < num_ref[0])
        def _():
            g, t = group_ref[i], tile_ref[i]

            # A row tile's first item zeroes it; the experts that share
            # the tile then each write their own rows.
            @pl.when((i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t))
            def _():
                o_ref[...] = jnp.zeros_like(o_ref)

            x = x_ref[...]
            h = jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)
            if w_gate is not None:
                h = jax.nn.silu(jnp.dot(
                    x, refs[1][0], preferred_element_type=jnp.float32)) * h
            elif act == "gelu":
                h = jax.nn.gelu(h)
            row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
            mine = (row >= start_ref[g]) & (row < end_ref[g])
            o_ref[...] = jnp.where(mine, h.astype(o_ref.dtype), o_ref[...])

    w_spec = pl.BlockSpec(
        (1, K, tn), lambda n, i, group, tile, *_: (group[i], 0, n))
    weights = [w] if w_gate is None else [w, w_gate]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // tn, n_items),
        in_specs=[pl.BlockSpec(
            (tm, K), lambda n, i, group, tile, *_: (tile[i], 0))]
        + [w_spec] * len(weights),
        out_specs=pl.BlockSpec(
            (tm, tn), lambda n, i, group, tile, *_: (tile[i], n)),
    )
    return pl.pallas_call(
        moe_grouped_matmul,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*tables, xs, *weights)


def _kernel_forward(xs, group_sizes, wi, wo, wg, tm: int):
    M = xs.shape[0]
    Mp = -(-M // tm) * tm
    interpret = resolve_interpret(None)
    tables = _work_items(group_sizes, Mp // tm, tm)
    # pad rows lie past every group: no item's mask takes them
    xp = jnp.pad(xs, ((0, Mp - M), (0, 0)))
    h = _grouped_matmul(xp, tables, wi, wg,
                        None if wg is not None else "gelu", tm, interpret)
    return _grouped_matmul(h, tables, wo, None, None, tm, interpret)[:M]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_mlp(xs, group_sizes, wi, wo, wg, tm):
    return _kernel_forward(xs, group_sizes, wi, wo, wg, tm)


def _kernel_mlp_fwd(xs, group_sizes, wi, wo, wg, tm):
    return (_kernel_forward(xs, group_sizes, wi, wo, wg, tm),
            (xs, group_sizes, wi, wo, wg))


def _kernel_mlp_bwd(tm, saved, ct):
    # The kernel has no transpose of its own: gradients take the XLA
    # form's, which computes the same function of the same operands.
    xs, group_sizes, wi, wo, wg = saved
    _, vjp = jax.vjp(
        lambda xs, wi, wo, wg: _xla_mlp(xs, group_sizes, wi, wo, wg),
        xs, wi, wo, wg)
    d_xs, d_wi, d_wo, d_wg = vjp(ct)
    return (d_xs, np.zeros(group_sizes.shape, jax.dtypes.float0),
            d_wi, d_wo, d_wg)


_kernel_mlp.defvjp(_kernel_mlp_fwd, _kernel_mlp_bwd)
