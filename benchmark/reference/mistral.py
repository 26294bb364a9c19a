"""Mistral-7B block, as published (Jiang et al. 2023, arXiv:2310.06825;
mistralai/Mistral-7B-v0.3 ``config.json``): pre-RMSNorm (eps 1e-5), rotary
positions (theta 1e6, rotate-half layout) on queries and keys, grouped-query
causal attention (32 query heads over 8 key/value heads of 128), no biases,
SwiGLU MLP, final RMSNorm, untied output head. v0.3 has no sliding window.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, N, H]; position t rotates the pair (x[i], x[i + H/2]) by
    t * theta^(-2i/H)."""
    T, _, H = x.shape
    half = H // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(
    jax.jit, static_argnames=("n_head", "n_kv", "eps", "theta"))
def _layer(x, w, n_head: int, n_kv: int, eps: float, theta: float):
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    # projections may arrive as [D, heads, head] / [heads, head, D]
    for k in ("wq", "wk", "wv"):
        w[k] = w[k].reshape(D, -1)
    w["wo"] = w["wo"].reshape(-1, D)
    H = w["wq"].shape[1] // n_head
    h = _rms(x, w["ln1_g"], eps)
    q = _rope((h @ w["wq"]).reshape(T, n_head, H), theta)
    k = _rope((h @ w["wk"]).reshape(T, n_kv, H), theta)
    v = (h @ w["wv"]).reshape(T, n_kv, H)
    group = n_head // n_kv  # query head n reads key/value head n // group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("tnh,snh->nts", q, k) / math.sqrt(H)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("nts,snh->tnh", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(T, n_head * H) @ w["wo"]
    h = _rms(x, w["ln2_g"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, lm_head, eps: float):
    return _rms(x, g.astype(F32), eps) @ lm_head.astype(F32)


def logits(weights, tokens, sizes):
    """[T, V] float32 next-token logits at every position of ``tokens``."""
    eps = float(sizes["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32)
        for w in weights["layers"]:
            x = _layer(x, w, n_head=int(sizes["num_attention_heads"]),
                       n_kv=int(sizes["num_key_value_heads"]), eps=eps,
                       theta=float(sizes["rope_theta"]))
        return _head(x, weights["lnf_g"], weights["lm_head"], eps=eps)
