"""The language model of Keye-VL-2.0-30B-A3B, one block as its ``config.json``
gives it (Kwai-Keye/Keye-VL-2.0-30B-A3B; no modelling code was on the machine,
so what the config does not say is listed under ``assumed`` in the
configuration file): pre-RMSNorm (eps 1e-6); grouped-query attention, 32
query heads over 4 key/value heads of 128, RMSNorm over each HEAD's values
with one learned scale of that width shared by the heads, before rotate-half
rotary positions over the whole head (theta 1e7; text tokens only, for which
the three M-RoPE components are one index); no biases.

A learned indexer (``sa_config``; the DeepSeek-Sparse-Attention form) picks
what each query attends: ``qI = h W_qI`` [16 heads of 64], ``kI = h W_kI``
[64] (ONE key a position), ``w = h W_w`` [16], qI and kI rotated like q and
k; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(64 x 16)`` for
``s <= t``; query t attends the ``topk`` (2,048) positions with the largest
``I[t, s]``, all of them while ``t < topk``; of equal scores the LOWER position
first. One selection for all heads. The selection here is literal: a stable
sort of every row, the first ``topk`` of it.

Then a mixture of 128 SwiGLU experts of 768: ``p = softmax(h W_r)`` in float32
over all experts, the 8 largest renormalised to sum to one (``norm_topk_prob``
true), no shared expert, nothing dropped. ``expert_parallel`` in the
configuration file: this rank holds experts ``[first_expert, first_expert +
held)`` (the stacks it is given) and what the absent ones would add is left
out. Final RMSNorm, untied output head.

Query rows run a block at a time: 32 heads x 5,016 x 5,016 float32 scores
would be 3.2 GB beside a served model that fills the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 256      # query rows scored at a time


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


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "n_index", "topk", "eps", "theta"))
def _attention(x, w, n_head: int, n_kv: int, n_index: int, topk: int,
               eps: float, theta: float):
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    # projections may arrive as [D, heads, head] / [heads, head, D]
    for k in ("wq", "wk", "wv", "wq_index", "wk_index"):
        w[k] = w[k].reshape(D, -1)
    w["wo"] = w["wo"].reshape(-1, D)
    H = w["wq"].shape[1] // n_head
    Hi = w["wq_index"].shape[1] // n_index
    h = _rms(x, w["ln1_g"], eps)
    q = _rms((h @ w["wq"]).reshape(T, n_head, H), w["q_norm_g"], eps)
    k = _rms((h @ w["wk"]).reshape(T, n_kv, H), w["k_norm_g"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    v = (h @ w["wv"]).reshape(T, n_kv, H)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    q_i = _rope((h @ w["wq_index"]).reshape(T, n_index, Hi), theta)
    k_i = _rope((h @ w["wk_index"]).reshape(T, 1, Hi), theta)[:, 0]
    w_i = h @ w["ww_index"]                                     # [T, n]

    def rows(block):
        t, q_b, qi_b, wi_b = block          # [R], [R,N,H], [R,n,Hi], [R,n]
        causal = jnp.arange(T)[None, :] <= t[:, None]           # [R, T]
        index = (jax.nn.relu(jnp.einsum("rjh,sh->rjs", qi_b, k_i))
                 * wi_b[:, :, None]).sum(1) / math.sqrt(Hi * n_index)
        index = jnp.where(causal, index, -jnp.inf)
        best = jnp.argsort(-index, axis=-1, stable=True)[:, :topk]
        chosen = jnp.zeros(causal.shape, bool).at[
            jnp.arange(len(t))[:, None], best].set(True) & causal
        s = jnp.einsum("rnh,snh->nrs", q_b, k) / math.sqrt(H)
        s = jnp.where(chosen[None], s, -jnp.inf)
        return jnp.einsum("nrs,snh->rnh", jax.nn.softmax(s, axis=-1), v)

    pad = -T % ROWS
    blocks = tuple(
        jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
            (-1, ROWS) + a.shape[1:])
        for a in (jnp.arange(T), q, q_i, w_i))
    a = jax.lax.map(rows, blocks).reshape((-1, n_head * H))[:T]
    return x + a @ w["wo"]


@functools.partial(jax.jit, static_argnames=(
    "top_k", "renorm", "first", "eps"))
def _experts(x, ln2_g, w_router, we_up, we_gate, we_down, top_k: int,
             renorm: bool, first: int, eps: float):
    """x + the held experts' part of the mixture; also the chosen experts
    [T, top_k] (ids among all the router's)."""
    h = _rms(x, ln2_g.astype(F32), eps)
    p = jax.nn.softmax(h @ w_router.astype(F32), axis=-1)         # [T, E]
    top, idx = jax.lax.top_k(p, top_k)
    if renorm:
        top = top / top.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(idx, p.shape[-1], dtype=F32)
              * top[..., None]).sum(1)                            # [T, E]
    held = weight[:, first:first + we_up.shape[0]]

    def one(acc, e):
        up, gate, down, w_e = e
        y = (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
             ) @ down.astype(F32)
        return acc + w_e[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (we_up, we_gate, we_down, held.T))
    return x + y, idx


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, lm_head, eps: float):
    return _rms(x, g.astype(F32), eps) @ lm_head.astype(F32)


ATTENTION_KEYS = ("ln1_g", "wq", "wk", "wv", "wo", "q_norm_g", "k_norm_g",
                  "wq_index", "wk_index", "ww_index")


def logits(weights, tokens, sizes, routing=None):
    """[T, V] float32 next-token logits at every position of ``tokens``.
    ``routing``: a list that receives each layer's chosen experts
    [T, top_k]."""
    eps = float(sizes["rms_norm_eps"])
    sa = sizes["sa_config"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32)
        for w in weights["layers"]:
            x = _attention(
                x, {k: w[k] for k in ATTENTION_KEYS},
                n_head=int(sizes["num_attention_heads"]),
                n_kv=int(sizes["num_key_value_heads"]),
                n_index=int(sa["indexer_num_heads"]), topk=int(sa["topk"]),
                eps=eps, theta=float(sizes["rope_theta"]))
            x, idx = _experts(
                x, w["ln2_g"], w["w_router"], w["we_up"], w["we_gate"],
                w["we_down"], top_k=int(sizes["num_experts_per_tok"]),
                renorm=bool(sizes["norm_topk_prob"]),
                first=int(sizes["expert_parallel"]["first_expert"]), eps=eps)
            if routing is not None:
                routing.append(idx)
        return _head(x, weights["lnf_g"], weights["lm_head"], eps=eps)
