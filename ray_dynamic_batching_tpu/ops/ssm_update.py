"""A state-space mixer's decode row as ONE kernel on the state plane in
place: each (slot, head block) tile of one layer of ``PagedKVCache.ssm_state``
``f32[L, B, H, P, N]`` is copied into VMEM, moved on by one row of the
recurrence, read out, and written back where it lay,

    S[h] <- exp(dt[h] A[h]) S[h] + (dt[h] x[h]) (x) B[g(h)]      where advance[b]
    y[h]  = S[h] C[g(h)]

so the state is read once and written once a substep. (In XLA,
``models/ssm.py::decode_update``, the update is one fusion and the read-out
another that reads the state AGAIN: three passes.) The plane is an operand
aliased onto the kernel's FIRST result: nothing of the other layers is read
or written, and no second plane is allocated. Loaded only by a model that has
such a mixer (``models/ssm.py`` imports it).

The tile is ``[hb, P, N]`` as it lies: ``N`` on the lanes, ``P`` on the
sublanes, ``hb`` heads of ONE group (they share a row of ``B`` and of ``C``;
``tile_math.ssm_update_heads``, from shapes). Every product and the
read-out's sum are float32 on the VPU / XLU. ``dt x`` comes in dense, ``[hb,
P]`` with ``P`` on the lanes, and is relaid in VMEM (a transposition) to
stand on the sublanes beside a head's rows; ``y`` goes back the same way. A
slot that does not advance has its tiles copied through bit for bit and its
``y`` read from them.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import tile_math
from ray_dynamic_batching_tpu.ops.pallas_common import (
    declined,
    resolve_interpret,
)

F32 = jnp.float32


def _heads_block(H: int, G: int, P: int, N: int) -> int:
    """Heads a tile (the tests and ``tools/run_kernel_ab.py --ssm`` patch
    this to time another block)."""
    return tile_math.ssm_update_heads(H // G, P, N)


def _kernel(ly_ref, adv_ref, decay_ref, s_ref, dx_ref, b_ref, c_ref,
            o_ref, y_ref, *, H: int, Hg: int):
    del ly_ref                                   # the index maps' alone
    hb, P, N = s_ref.shape[2:]
    b, j = pl.program_id(0), pl.program_id(1)
    g = (j * hb) // Hg
    b_row = b_ref[0, pl.ds(g, 1), :]                       # [1, N]
    c_row = c_ref[0, pl.ds(g, 1), :]
    dx_cols = dx_ref[0, 0].T                               # [P, hb]
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, hb), 1)

    def tile(advancing: bool) -> None:
        y_cols = jnp.zeros((P, hb), F32)
        for h in range(hb):                                # static unroll
            S = s_ref[0, 0, h]                             # [P, N]
            if advancing:
                S = (decay_ref[b * H + j * hb + h] * S
                     + dx_cols[:, h:h + 1] * b_row)
            o_ref[0, 0, h] = S
            # the read-out: the lane tiles of N added first, then ONE
            # cross-lane reduce a row tile
            sc = S * c_row
            folded = sc[:, :tile_math.LANE]
            for k in range(1, N // tile_math.LANE):
                folded = folded + sc[:, k * tile_math.LANE:
                                     (k + 1) * tile_math.LANE]
            y_col = jnp.sum(folded, axis=-1, keepdims=True)  # [P, 1]
            y_cols = jnp.where(lane == h, y_col, y_cols)
        y_ref[0, 0] = y_cols.T

    advance = adv_ref[b] != 0
    pl.when(advance)(lambda: tile(True))
    pl.when(jnp.logical_not(advance))(lambda: tile(False))


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _ssm_state_update(
    plane: jax.Array,     # [L, B, H, P, N] float32: the plane, whole
    decay: jax.Array,     # [B * H] float32: exp(dt A)
    dx: jax.Array,        # [B, H, P] float32: dt x
    Bm: jax.Array,        # [B, G, N] float32
    Cm: jax.Array,        # [B, G, N] float32
    advance: jax.Array,   # [B] int32
    layer: jax.Array,     # [1] int32
    *,
    hb: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    L, B, H, P, N = plane.shape
    G = Bm.shape[1]
    nh = H // hb
    a_tile = lambda b, j, ly, adv, dc: (ly[0], b, j, 0, 0)  # noqa: E731
    a_block = lambda b, j, ly, adv, dc: (b, j, 0, 0)        # noqa: E731
    a_slot = lambda b, j, ly, adv, dc: (b, 0, 0)            # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nh),
        in_specs=[pl.BlockSpec((1, 1, hb, P, N), a_tile),
                  pl.BlockSpec((1, 1, hb, P), a_block),
                  pl.BlockSpec((1, G, N), a_slot),
                  pl.BlockSpec((1, G, N), a_slot)],
        out_specs=[pl.BlockSpec((1, 1, hb, P, N), a_tile),
                   pl.BlockSpec((1, 1, hb, P), a_block)],
    )
    new_plane, y = pl.pallas_call(
        functools.partial(_kernel, H=H, Hg=H // G),
        grid_spec=grid_spec,
        # the plane FIRST: the benchmark's reader finds the state's
        # operations by the shape of an operation's first result
        out_shape=(jax.ShapeDtypeStruct(plane.shape, plane.dtype),
                   jax.ShapeDtypeStruct((B, nh, hb, P), F32)),
        # operand 3 (after the three prefetched scalars) IS result 0
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=tile_math.VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(layer, advance, decay, plane, dx.reshape(B, nh, hb, P), Bm, Cm)
    return new_plane, y.reshape(B, H, P)


def state_update(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                 Cm: jax.Array, plane: jax.Array, layer: int,
                 advance: jax.Array, why: Optional[List[str]] = None,
                 ) -> Optional[Tuple[jax.Array, jax.Array]]:
    """``decode_update``'s row on layer ``layer`` of the WHOLE plane ``[L,
    B, H, P, N]``: (``y`` ``[B, H, P]``, the plane with that layer moved on
    where ``advance[b]``), or None with the reason on ``why``. The caller
    hands the plane over (it is not to read the old one again: the kernel
    writes where it lies)."""
    _, _, H, P, N = plane.shape
    G = Bm.shape[1]
    if not attn_ops._use_pallas():
        return declined(why, f"pallas off: backend {attn_ops._BACKEND!r} "
                             f"on {jax.default_backend()}")
    if plane.dtype != F32:
        return declined(why, f"a {plane.dtype} state: the kernel's "
                             "arithmetic is float32 on a float32 plane")
    if P % 8 or N % tile_math.LANE:
        return declined(why, f"a head's state [{P}, {N}] is no whole "
                             "(8, 128) tiles")
    if H % G:
        return declined(why, f"{H} heads in {G} groups: a tile is heads "
                             "of one group")
    decay = jnp.exp(dt * A).reshape(-1)
    new_plane, y = _ssm_state_update(
        plane, decay, dt[..., None] * x, Bm, Cm, advance.astype(jnp.int32),
        jnp.full((1,), layer, jnp.int32), hb=_heads_block(H, G, P, N),
        interpret=bool(resolve_interpret(None)))
    return y, new_plane
