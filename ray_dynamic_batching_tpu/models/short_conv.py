"""A gated short convolution as a layer's sequence mixer (LFM2's ``conv``
layers; ``DecoderConfig.conv_kernel``, letter "C" of ``layer_pattern``).
Imported by a model with such layers alone.

    [B | C | x] = u W_in            (D -> 3D, no bias)
    z_t = B_t * x_t
    c_t = sum_j w_j * z_{t-(K-1)+j}  (depthwise, causal, K taps, one weight
                                      a channel a tap; z before the
                                      sequence's start is 0)
    m_t = (C_t * c_t) W_out         (D -> D)

What such a layer keeps of a sequence is the taps' last ``K - 1`` inputs,
``(z_{t-K+1}, .., z_{t-1})``: a FIXED size a slot, no pages
(``PagedKVCache.conv_state`` ``[L_conv, B, K - 1, D]``). A chunk of a
prompt starts from the state its slot holds (zeros where the prompt
starts) and leaves the state at its TRUE end: a padded tail's rows never
enter it. A decode row moves the state on by one, or not at all for a slot
that does not advance. Plain XLA: ``K`` multiply-adds between two products.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_dynamic_batching_tpu.ops import attention as attn_ops

PATH_SHORT_CONV = "short_conv"


@dataclasses.dataclass(frozen=True)
class ConvPath(attn_ops.AttentionPath):
    """A conv layer's dispatch among ``ops.attention.attention_paths()``:
    ``q_shape`` the rows mixed ``[B, T, D]``, ``kv_shape`` the states they
    start from and leave (``[L_conv, B, K - 1, D]``; ``()`` without a
    cache)."""

    def describe(self) -> str:
        taps = (self.kv_shape[2] + 1) if self.kv_shape else "K"
        return (f"short convolution, {taps} taps in XLA, a state a slot "
                "(no pages)" if self.kv_shape
                else "short convolution in XLA from a zero state")


def _record(z: jax.Array, states: Optional[jax.Array]) -> None:
    attn_ops._PATHS.append(ConvPath(
        program=attn_ops.current_program(), path=PATH_SHORT_CONV,
        gathered=False, stacked=states is not None, tp=1, interpret=False,
        q_shape=tuple(z.shape),
        kv_shape=() if states is None else tuple(states.shape),
        kv_dtype=str(z.dtype if states is None else states.dtype),
        declines=()))


def taps(z_ext: jax.Array, w: jax.Array) -> jax.Array:
    """The convolution both forms share. ``z_ext`` ``[B, T + K - 1, D]``:
    the ``K - 1`` rows before the sequence's rows, then the rows; ``w``
    ``[K, D]``. Returns ``c`` ``[B, T, D]`` float32: row ``t`` is ``sum_j
    w[j] * z_ext[t + j]``."""
    K = w.shape[0]
    T = z_ext.shape[1] - (K - 1)
    w = w.astype(jnp.float32)
    z_ext = z_ext.astype(jnp.float32)
    return sum(w[j] * z_ext[:, j:j + T] for j in range(K))


def chunk(z: jax.Array, state: jax.Array, lens: jax.Array,
          w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A chunk's rows ``z`` ``[g, T, D]`` behind the incoming ``state``
    ``[g, K - 1, D]``, of which row b's first ``lens[b]`` are real (the rest
    a bucket's padding). Returns (``c`` ``[g, T, D]``, the state at each
    row's TRUE end: the last ``K - 1`` of ``state | z[:lens]``, so a row
    with fewer than ``K - 1`` real tokens keeps the tail of what came in,
    and one with none all of it)."""
    z_ext = jnp.concatenate([state.astype(z.dtype), z], axis=1)
    idx = lens[:, None] + jnp.arange(state.shape[1])[None, :]
    new = jnp.take_along_axis(z_ext, idx[:, :, None], axis=1)
    return taps(z_ext, w), new.astype(state.dtype)


def decode_row(z: jax.Array, state: jax.Array, advance: jax.Array,
               w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One row a slot: ``z`` ``[B, 1, D]`` behind ``state`` ``[B, K - 1,
    D]``. Returns (``c`` ``[B, 1, D]``, the state moved on by the row where
    ``advance[b]``, as it came in where not)."""
    z_ext = jnp.concatenate([state.astype(z.dtype), z], axis=1)
    moved = jnp.where(advance.astype(bool)[:, None, None],
                      z_ext[:, 1:].astype(state.dtype), state)
    return taps(z_ext, w), moved


def mixer(layer: nn.Module, dense: Any, kind: Any, u: jax.Array,
          cache_kv: Optional[Any], state_lens: Optional[jax.Array],
          ) -> Tuple[jax.Array, Optional[Any]]:
    """The mixer of ``layer`` (a ``DecoderLayer`` inside its compact call;
    ``dense`` its kernel factory) on the normed rows ``u`` ``[B, T, D]``:
    (``m``, the layer's state updated or None without a cache).
    ``cache_kv.conv_state`` is the rows' states, ``[L_conv, B, K - 1, D]``
    (this layer's is ``kind.pool_layer``); ``state_lens`` ``[B]`` the real
    tokens of each row (a decode row's 1 or 0). Without a cache the
    sequence starts at its first row: the state is zeros and nothing is
    kept."""
    cfg = layer.cfg
    K, D = cfg.conv_kernel, cfg.d_model
    B, T, _ = u.shape
    w = layer.param("conv_taps", nn.initializers.normal(K ** -0.5),
                    (K, D), jnp.float32)
    with jax.named_scope("short_conv"):
        gate_in, gate_out, x = jnp.split(dense(3 * D, "conv_in")(u), 3, -1)
        z = gate_in * x
        _record(z, None if cache_kv is None else cache_kv.conv_state)
        if cache_kv is None:
            c = taps(jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0))), w)
            new_cache = None
        else:
            states = cache_kv.conv_state
            li = kind.pool_layer
            if state_lens is None:
                raise ValueError(
                    "a conv layer over a cache needs its rows' real "
                    "lengths (state_lens)")
            if T == 1:
                c, new = decode_row(z, states[li], state_lens, w)
            else:
                c, new = chunk(z, states[li], state_lens, w)
            new_cache = cache_kv._replace(
                conv_state=states.at[li].set(new))
        m = gate_out * c.astype(z.dtype)
    return dense(D, "conv_out")(m), new_cache
