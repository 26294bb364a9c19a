"""OLMoE-1B-7B block, as published (Muennighoff et al. 2024,
arXiv:2409.02060; allenai/OLMoE-1B-7B-0125-Instruct ``config.json`` and its
modelling code): pre-RMSNorm (eps 1e-5), multi-head causal attention (16
heads of 128) with RMSNorm on the query and key PROJECTIONS — over the whole
2,048-wide projection, one learned scale of that width, before the split
into heads and before the rotary positions (theta 1e4, rotate-half layout)
— no biases, ``clip_qkv`` null; then a mixture of 64 SwiGLU experts of width
1,024: ``p = softmax(h @ W_r)`` in float32 over all experts, the 8 largest
``p`` used AS THEY ARE (``norm_topk_prob`` false: they sum to about a half),
``y = sum_e p_e * W_down,e(silu(h W_gate,e) * (h W_up,e))``. Every token
reaches all 8 of its experts: no capacity, nothing dropped, no shared
expert. Final RMSNorm, untied output head.

Experts run one at a time (a scan over the stacks): a whole layer's expert
stacks cast to float32 would be 1.6 GB beside a served model that fills the
chip. ``norm_topk_prob`` true (another model of the family) renormalises.
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


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "eps", "theta"))
def _attention(x, w, n_head: int, n_kv: int, eps: float, theta: float):
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    # projections may arrive as [D, heads, head] / [heads, head, D]
    for k in ("wq", "wk", "wv"):
        w[k] = w[k].reshape(D, -1)
    w["wo"] = w["wo"].reshape(-1, D)
    H = w["wq"].shape[1] // n_head
    h = _rms(x, w["ln1_g"], eps)
    q = _rms(h @ w["wq"], w["q_norm_g"], eps)     # over the projection
    k = _rms(h @ w["wk"], w["k_norm_g"], eps)
    q = _rope(q.reshape(T, n_head, H), theta)
    k = _rope(k.reshape(T, n_kv, H), theta)
    v = (h @ w["wv"]).reshape(T, n_kv, H)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    s = jnp.einsum("tnh,snh->nts", q, k) / math.sqrt(H)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    a = jnp.einsum("nts,snh->tnh", jax.nn.softmax(s, axis=-1), v)
    return x + a.reshape(T, n_head * H) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("top_k", "renorm", "eps"))
def _experts(x, ln2_g, w_router, we_up, we_gate, we_down, top_k: int,
             renorm: bool, eps: float):
    """x + the mixture; also the chosen experts [T, top_k] (for a caller
    that counts routing)."""
    h = _rms(x, ln2_g.astype(F32), eps)
    p = jax.nn.softmax(h @ w_router.astype(F32), axis=-1)         # [T, E]
    top, idx = jax.lax.top_k(p, top_k)
    if renorm:
        top = top / top.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(idx, p.shape[-1], dtype=F32)
              * top[..., None]).sum(1)                            # [T, E]

    def one(acc, e):
        up, gate, down, w_e = e
        y = (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
             ) @ down.astype(F32)
        return acc + w_e[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (we_up, we_gate, we_down, weight.T))
    return x + y, idx


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, lm_head, eps: float):
    return _rms(x, g.astype(F32), eps) @ lm_head.astype(F32)


def logits(weights, tokens, sizes, routing=None):
    """[T, V] float32 next-token logits at every position of ``tokens``.
    ``routing``: a list that receives each layer's chosen experts
    [T, top_k]."""
    eps = float(sizes["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32)
        for w in weights["layers"]:
            x = _attention(
                x, {k: w[k] for k in ("ln1_g", "wq", "wk", "wv", "wo",
                                      "q_norm_g", "k_norm_g")},
                n_head=int(sizes["num_attention_heads"]),
                n_kv=int(sizes["num_key_value_heads"]), eps=eps,
                theta=float(sizes["rope_theta"]))
            x, idx = _experts(
                x, w["ln2_g"], w["w_router"], w["we_up"], w["we_gate"],
                w["we_down"], top_k=int(sizes["num_experts_per_tok"]),
                renorm=bool(sizes["norm_topk_prob"]), eps=eps)
            if routing is not None:
                routing.append(idx)
        return _head(x, weights["lnf_g"], weights["lm_head"], eps=eps)
