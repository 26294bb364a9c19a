"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): a
residual path of ``n`` streams. A token's state is ``X`` in R^{n x D}; every
sublayer ``F`` reads ONE mix of the streams and writes back into all of them
through three maps computed from ``X`` itself, in float32:

    x~      = RMSNorm(vec(X))                     (all n D values, no gain)
    H_pre   = sigmoid(a_pre  (x~ Phi_pre)  + b_pre)            in R^n
    H_post  = 2 sigmoid(a_post (x~ Phi_post) + b_post)         in R^n
    H_res   = SK(clip(a_res mat(x~ Phi_res) + B_res))          in R^{n x n}
    u = H_pre X;   y = F(norm(u));   X <- H_res X + H_post^T y

``SK`` is ``exp`` and then ``iters`` Sinkhorn rounds (columns, then rows,
each over its sum + ``eps``): ALL of them, a fixed count, so that the
program has one shape. Loaded only by a model with ``hc_mult`` > 1
(``models/decoder.py`` imports it where one is met).

On the device the maps are PLANES over the tokens: ``H_res`` is 16 arrays
``[1, B T]`` (the tokens on the lanes), a Sinkhorn round is adds, multiplies
and a reciprocal of whole planes (no reduction over a 4-wide axis), and the
two mixes are 4 and 20 scaled adds of ``[B, T, D]`` streams, not ``4 x 4``
matmuls. Left to XLA the rounds are some 36 small kernels a sublayer (a
plane has 16 consumers a round, and the fuser will not grow a fusion of 16
outputs through 20 of them) and 16,000 instructions a program; where Pallas
is on they are ONE kernel a sublayer (:func:`_sinkhorn_kernel`: the 16
planes in VMEM, the rounds a loop over vector registers).
"""

from __future__ import annotations

import functools
from typing import Any, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops.pallas_common import resolve_interpret

Planes = List[jax.Array]          # n arrays [1, B T] float32
Maps = Tuple[Planes, List[Planes]]  # H_post [n], H_res [n][n]


def sinkhorn(m: List[Planes], iters: int, eps: float) -> List[Planes]:
    """``m[i][j]`` positive planes -> ``iters`` rounds: every entry over its
    COLUMN's sum + ``eps``, then over its ROW's."""
    n = len(m)
    for _ in range(iters):
        cols = [1.0 / (sum(m[i][j] for i in range(n)) + eps)
                for j in range(n)]
        m = [[m[i][j] * cols[j] for j in range(n)] for i in range(n)]
        rows = [1.0 / (sum(m[i]) + eps) for i in range(n)]
        m = [[m[i][j] * rows[i] for j in range(n)] for i in range(n)]
    return m


def _sinkhorn_kernel(m_ref, o_ref, *, n: int, iters: int, eps: float):
    """m_ref, o_ref ``[n n, S, 128]``: plane ``i n + j`` is entry (i, j) of
    every token's matrix, a token a lane."""
    def round_(_, flat):
        m = [list(flat[i * n:(i + 1) * n]) for i in range(n)]
        return tuple(x for row in sinkhorn(m, 1, eps) for x in row)

    flat = jax.lax.fori_loop(
        0, iters, round_, tuple(m_ref[k] for k in range(n * n)))
    for k in range(n * n):
        o_ref[k] = flat[k]


@functools.partial(jax.jit, static_argnames=("iters", "eps", "interpret"))
def _hc_sinkhorn(m: jax.Array, *, iters: int, eps: float,
                 interpret: bool) -> jax.Array:
    """m ``[n n, R]`` positive -> the same after ``iters`` rounds."""
    nn_, R = m.shape
    S = -(-R // 128)
    # lanes past the tokens hold ones: computed, finite, never read
    padded = jnp.pad(m, ((0, 0), (0, S * 128 - R)), constant_values=1.0)
    out = pl.pallas_call(
        functools.partial(_sinkhorn_kernel, n=int(round(nn_ ** 0.5)),
                          iters=iters, eps=eps),
        out_shape=jax.ShapeDtypeStruct((nn_, S, 128), m.dtype),
        interpret=interpret,
    )(padded.reshape(nn_, S, 128))
    return out.reshape(nn_, S * 128)[:, :R]


class HyperConnection(nn.Module):
    """One sublayer's maps. ``__call__(X [B, T, n, D])`` -> ``(u [B, T, D],
    maps)``; :func:`mix` writes the sublayer's output back."""

    n: int
    iters: int
    eps: float                   # the Sinkhorn denominators'
    rms_eps: float
    clamp: Tuple[float, float]   # on H~res, before the exponent
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, X: jax.Array) -> Tuple[jax.Array, Maps]:
        B, T, n, D = X.shape
        f32 = jnp.float32
        phi = self.param("kernel", nn.initializers.lecun_normal(),
                         (n * D, 2 * n + n * n), f32)
        a = self.param("a", nn.initializers.ones, (3,), f32)
        b_pre = self.param("b_pre", nn.initializers.zeros, (n,), f32)
        b_post = self.param("b_post", nn.initializers.zeros, (n,), f32)
        b_res = self.param("b_res", nn.initializers.zeros, (n, n), f32)
        with jax.named_scope("hc_maps"):
            x = X.astype(f32).reshape(B * T, n * D)
            x = x * jax.lax.rsqrt(
                jnp.mean(x * x, axis=-1, keepdims=True) + self.rms_eps)
            # [24, 1, B T]: a plane is ONE lane row over the tokens
            raw = jnp.einsum("rk,km->mr", x, phi.astype(f32),
                             precision=jax.lax.Precision.HIGHEST)[:, None]
            a, b_pre, b_post, b_res = (
                v.astype(f32) for v in (a, b_pre, b_post, b_res))
            pre = [jax.nn.sigmoid(a[0] * raw[i] + b_pre[i])
                   for i in range(n)]
            post = [2.0 * jax.nn.sigmoid(a[1] * raw[n + i] + b_post[i])
                    for i in range(n)]
            m = jnp.exp(jnp.clip(
                a[2] * raw[2 * n:, 0] + b_res.reshape(n * n, 1),
                *self.clamp))                               # [n n, B T]
            if attn_ops._use_pallas():
                m = _hc_sinkhorn(m, iters=self.iters, eps=self.eps,
                                 interpret=bool(resolve_interpret(None)))
                res = [[m[i * n + j][None] for j in range(n)]
                       for i in range(n)]
            else:
                res = sinkhorn([[m[i * n + j][None] for j in range(n)]
                                for i in range(n)], self.iters, self.eps)
        with jax.named_scope("hc_mix"):
            u = sum(_over(pre[i], X) * X[:, :, i].astype(f32)
                    for i in range(n))
        return u.astype(self.dtype), (post, res)


def _over(plane: jax.Array, X: jax.Array) -> jax.Array:
    """A plane ``[1, B T]`` as a factor of a stream ``[B, T, D]``."""
    return plane.reshape(X.shape[0], X.shape[1], 1)


def mix(X: jax.Array, y: jax.Array, maps: Maps) -> jax.Array:
    """``X <- H_res X + H_post^T y``: X ``[B, T, n, D]``, y ``[B, T, D]``."""
    post, res = maps
    n = X.shape[2]
    with jax.named_scope("hc_mix"):
        streams = [X[:, :, j].astype(jnp.float32) for j in range(n)]
        yf = y.astype(jnp.float32)
        return jnp.stack(
            [sum(_over(res[i][j], X) * streams[j] for j in range(n))
             + _over(post[i], X) * yf for i in range(n)],
            axis=2).astype(X.dtype)
