"""GPT-2 block, as published (Radford et al. 2019; openai-community/gpt2-*
``config.json``): learned positions, pre-LayerNorm (eps 1e-5), multi-head
causal attention with biases, GELU (tanh form, ``gelu_new``) MLP with
biases, final LayerNorm, output head tied to the token embedding.

Departure of the PROGRAM from this, not of the reference: ``models/
decoder.py`` builds ``flax.linen.LayerNorm`` with its default eps 1e-6.
On unit-variance activations the two differ by ~5e-6 relative, far inside
the tolerance of the check.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _flat(w, D):
    """float32 copies with projections as 2-D matrices: [D, heads, head]
    -> [D, heads*head], [heads, head, D] -> [heads*head, D], biases flat."""
    w = {k: a.astype(F32) for k, a in w.items()}
    for k in ("wq", "wk", "wv"):
        w[k] = w[k].reshape(D, -1)
    w["wo"] = w["wo"].reshape(-1, D)
    for k in ("bq", "bk", "bv"):
        if k in w:
            w[k] = w[k].reshape(-1)
    return w


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _layer(x, w, n_head: int, eps: float):
    T, D = x.shape
    H = D // n_head
    w = _flat(w, D)
    h = _ln(x, w["ln1_g"], w["ln1_b"], eps)
    q = (h @ w["wq"] + w["bq"]).reshape(T, n_head, H)
    k = (h @ w["wk"] + w["bk"]).reshape(T, n_head, H)
    v = (h @ w["wv"] + w["bv"]).reshape(T, n_head, H)
    s = jnp.einsum("tnh,snh->nts", q, k) / math.sqrt(H)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("nts,snh->tnh", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(T, D) @ w["wo"] + w["bo"]
    h = _ln(x, w["ln2_g"], w["ln2_b"], eps)
    return x + _gelu_new(h @ w["w_up"] + w["b_up"]) @ w["w_down"] + w["b_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, b, wte, eps: float):
    x = _ln(x, g.astype(F32), b.astype(F32), eps)
    return x @ wte.astype(F32).T


def logits(weights, tokens, sizes):
    """[T, V] float32 next-token logits at every position of ``tokens``."""
    eps = float(sizes["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = (weights["wte"][tokens].astype(F32)
             + weights["wpe"][: tokens.shape[0]].astype(F32))
        for w in weights["layers"]:
            x = _layer(x, w, n_head=int(sizes["n_head"]), eps=eps)
        return _head(x, weights["lnf_g"], weights["lnf_b"], weights["wte"],
                     eps=eps)
