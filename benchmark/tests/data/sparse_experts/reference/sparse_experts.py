"""Tests only: a Llama-shaped block whose MLP is a DROPLESS top-k mixture of
SwiGLU experts: pre-RMSNorm, rotary grouped-query causal attention, then
``softmax(h @ router)`` over the experts, the k largest gates renormalised
to sum to one (``norm_topk_prob``, as ``models/moe.py:61-64`` does), and the
gate-weighted sum of those experts' outputs. Every token reaches its k
experts: the program's capacity routing agrees only where nothing drops."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    T, _, H = x.shape
    half = H // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(
    jax.jit, static_argnames=("n_head", "n_kv", "top_k", "eps", "theta"))
def _layer(x, w, n_head: int, n_kv: int, top_k: int, eps: float,
           theta: float):
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    for k in ("wq", "wk", "wv"):
        w[k] = w[k].reshape(D, -1)
    w["wo"] = w["wo"].reshape(-1, D)
    H = w["wq"].shape[1] // n_head
    h = _rms(x, w["ln1_g"], eps)
    q = _rope((h @ w["wq"]).reshape(T, n_head, H), theta)
    k = _rope((h @ w["wk"]).reshape(T, n_kv, H), theta)
    v = (h @ w["wv"]).reshape(T, n_kv, H)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    s = jnp.einsum("tnh,snh->nts", q, k) / math.sqrt(H)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    a = jnp.einsum("nts,snh->tnh", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(T, n_head * H) @ w["wo"]
    h = _rms(x, w["ln2_g"], eps)
    gates = jax.nn.softmax(h @ w["w_router"], axis=-1)           # [T, E]
    top, idx = jax.lax.top_k(gates, top_k)
    top = top / top.sum(-1, keepdims=True)
    # every expert on every token, then the chosen k: plain, not fast
    up = jnp.einsum("td,edf->etf", h, w["we_up"])
    gate = jnp.einsum("td,edf->etf", h, w["we_gate"])
    out = jnp.einsum("etf,efd->etd", jax.nn.silu(gate) * up, w["we_down"])
    weight = (jax.nn.one_hot(idx, gates.shape[-1]) * top[..., None]).sum(1)
    return x + jnp.einsum("te,etd->td", weight, out)


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
                       n_kv=int(sizes["num_key_value_heads"]),
                       top_k=int(sizes["num_experts_per_tok"]), eps=eps,
                       theta=float(sizes["rope_theta"]))
        return _head(x, weights["lnf_g"], weights["lm_head"], eps=eps)
