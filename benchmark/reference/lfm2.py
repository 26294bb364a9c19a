"""LFM2-24B-A2B block (LiquidAI/LFM2-24B-A2B ``config.json``, ``model_type``
``lfm2_moe``), one RANK'S SHARE of it: for one sequence, ``h`` the residual
stream, nothing with a bias term of its own, RMSNorm eps ``norm_eps`` with
gain. For layer i:

1. ``r = h; u = RMSNorm_op(h)``.
2. A conv layer (``layer_types[i] == "conv"``): ``[B | C | x] = u W_in``
   (2048 -> 3 x 2048); ``z_t = B_t * x_t``; ``c_t = w_0 * z_{t-2} + w_1 *
   z_{t-1} + w_2 * z_t`` (depthwise, causal, ``conv_L_cache`` 3 taps, one
   weight a channel a tap, ``z`` before the sequence's start 0); ``m_t =
   (C_t * c_t) W_out``. No activation between the gates.
3. An attention layer: q as 32 heads of 64, k and v as 8; RMSNorm over each
   head's 64 values on q and on k, with gain, before the rotary positions
   (rotate-half over the whole head, theta 1e6); causal softmax in float32
   at scale 64^-1/2, 4 query heads a key head; ``m = concat(o) W_o``.
4. ``h = r + m; v = RMSNorm_ffn(h)``. A dense layer (i < ``num_dense_layers``):
   SwiGLU of ``intermediate_size``. Else ``s = sigmoid(v W_r)`` over ALL the
   router's experts; the chosen set the ``num_experts_per_tok`` largest of
   ``s + b`` (``b`` the expert bias, for the choice only); ``w_e = s_e /
   (sum of the chosen s + 1e-6)`` times ``routed_scaling_factor``; ``y = sum
   over chosen e HELD HERE of w_e FFN_e(v)``, each a SwiGLU of
   ``moe_intermediate_size``. No shared expert. What the absent experts
   would add is left out (they live on the deployment's other ranks).
5. After the last layer a final RMSNorm, then logits over the whole
   vocabulary through the input embedding (tied).

Depth, widths, the router's width and how many experts are held come from
the arrays given; which layers are conv layers (``layer_types``), which
experts are held (``expert_parallel.first_expert``), top-k, the scaling
factor and eps from the configuration file. With every expert held and
``first_expert`` 0 this is the uncut layer. Imports nothing from the program.

Long prompts run in BLOCKS of rows: the conv's taps reach two rows back and
the attention of a block reads the keys before it, so a block of queries
needs no ``[T, T]`` score matrix of the whole prompt, and the dense MLP runs
in column blocks and the experts one at a time: a layer's weights cast to
float32 whole would not fit beside a served model that fills the chip.

Where the choice of step 4 is NOT DECIDED at the precision the configuration
states, this reference says so instead of naming a token, by the rule of
``benchmark/reference/kexaone.py``: each sparse layer reports, per position,
how far the nearest expert HELD HERE lies from the edge of the chosen set,
and where ``reference_check.undecided_score_gap`` is set, ``logits`` returns
a FLAT row (all zeros: any token passes a comparison of margins) at the
positions where some layer's distance is under it. Every other position is
held to what is computed here. The configuration file says what gap it
states and from which two readings (PERF.md, PR 50).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_DENSE_BLOCK = 2048     # columns of a dense MLP's width at a time
_ROW_BLOCK = 512        # query rows of an attention layer at a time
_NORM_SUM_EPS = 1e-6    # in the denominator of the renormalised gates


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta, start=0):
    """x [T, N, H], row t at position ``start + t``: rotates the pair
    (x[i], x[i + H/2]) by position * theta^(-2i/H)."""
    T, _, H = x.shape
    half = H // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = (start + jnp.arange(T, dtype=F32))[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def conv_mixer(x, w, eps: float):
    """x + the gated short convolution of its normed rows. ``w["taps"]``
    ``[K, D]``: tap j weighs the row ``K - 1 - j`` back."""
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    u = _rms(x, w["op_norm_g"], eps) @ w["w_in"].reshape(D, 3 * D)
    gate_in, gate_out, val = u[:, :D], u[:, D:2 * D], u[:, 2 * D:]
    z = gate_in * val
    K = w["taps"].shape[0]
    z_ext = jnp.concatenate([jnp.zeros((K - 1, D), F32), z], axis=0)
    c = sum(w["taps"][j] * z_ext[j:j + T] for j in range(K))
    return x + (gate_out * c) @ w["w_out"].reshape(D, D)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "eps", "theta"))
def _attention(x, w, n_head: int, n_kv: int, eps: float, theta: float):
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    # projections may arrive as [D, heads, head] / [heads, head, D]
    for k in ("wq", "wk", "wv"):
        w[k] = w[k].reshape(D, -1)
    w["wo"] = w["wo"].reshape(-1, D)
    H = w["q_norm_g"].shape[0]
    h = _rms(x, w["op_norm_g"], eps)
    q = _rope(_rms((h @ w["wq"]).reshape(T, n_head, H), w["q_norm_g"], eps),
              theta)
    k = _rope(_rms((h @ w["wk"]).reshape(T, n_kv, H), w["k_norm_g"], eps),
              theta)
    v = (h @ w["wv"]).reshape(T, n_kv, H)
    G = n_head // n_kv
    outs = []
    for a in range(0, T, _ROW_BLOCK):       # a block of queries, keys 0..b
        b = min(a + _ROW_BLOCK, T)
        qb = q[a:b].reshape(b - a, n_kv, G, H)
        s = jnp.einsum("tkgh,skh->kgts", qb, k[:b]) / math.sqrt(H)
        see = jnp.arange(b)[None, :] <= jnp.arange(a, b)[:, None]
        p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgts,skh->tkgh", p, v[:b]).reshape(
            b - a, n_head * H))
    return x + jnp.concatenate(outs, axis=0) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, ffn_norm_g, gate, up, down, eps: float):
    h = _rms(x, ffn_norm_g.astype(F32), eps)
    y = jnp.zeros_like(x)
    for a in range(0, gate.shape[1], _DENSE_BLOCK):   # columns of the width
        b = a + _DENSE_BLOCK
        y = y + _swiglu(h, gate[:, a:b], up[:, a:b], down[a:b])
    return x + y


def route(h, w_router, bias, top_k: int, scale: float):
    """``h`` [T, D] (normed) -> the chosen experts [T, top_k], every
    expert's weight in the sum [T, E] (0 where not chosen), and every
    expert's distance from the edge of the chosen set [T, E]: for a chosen
    expert its selection score less the best one left out, for the others
    the worst one chosen less theirs."""
    s = jax.nn.sigmoid(h @ w_router.astype(F32))                  # [T, E]
    select = s + bias.astype(F32)
    best, idx = jax.lax.top_k(select, top_k + 1)
    idx = idx[:, :top_k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    gates = scale * chosen / (chosen.sum(-1, keepdims=True) + _NORM_SUM_EPS)
    picked = jax.nn.one_hot(idx, s.shape[-1], dtype=F32)          # [T, k, E]
    weight = (picked * gates[..., None]).sum(1)
    edge = jnp.where(picked.sum(1) > 0, select - best[:, top_k:],
                     best[:, top_k - 1:top_k] - select)
    return idx, weight, edge


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "first", "eps"))
def experts(x, w, top_k: int, scale: float, first: int, eps: float):
    """x + the held experts' part of the mixture; also the chosen experts
    [T, top_k], ids among all the router's, and the held experts' least
    distance from the chosen set's edge [T]."""
    h = _rms(x, w["ffn_norm_g"].astype(F32), eps)
    idx, weight, edge = route(
        h, w["w_router"], w["router_bias"], top_k, scale)
    held = w["we_up"].shape[0]

    def one(acc, e):
        up, gate, down, w_e = e
        return acc + w_e[:, None] * _swiglu(h, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["we_up"], w["we_gate"], w["we_down"],
         weight[:, first:first + held].T))
    return x + y, idx, edge[:, first:first + held].min(-1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, wte, eps: float):
    return _rms(x, g.astype(F32), eps) @ wte.astype(F32).T


_CONV = ("op_norm_g", "w_in", "taps", "w_out")
_ATTN = ("op_norm_g", "wq", "wk", "wv", "wo", "q_norm_g", "k_norm_g")
_SPARSE = ("ffn_norm_g", "w_router", "router_bias", "we_up", "we_gate",
           "we_down")


def logits(weights, tokens, sizes, routing=None, edges=None):
    """[T, V] float32 next-token logits at every position of ``tokens``; a
    flat row where the choice of experts is not decided (see the top).
    ``routing``: a list that receives each SPARSE layer's chosen experts
    [T, top_k]. ``edges``: a list that receives each sparse layer's [T]
    distances of the held experts from the chosen set's edge; the caller
    then does its own excusing and every row comes back as computed."""
    eps = float(sizes["norm_eps"])
    check = sizes.get("reference_check", {}) if edges is None else {}
    undecided = float(check.get("undecided_score_gap", 0.0))
    # (an array: a depth cut may leave the dense layers alone)
    nearest = jnp.full((len(tokens),), jnp.inf, F32)
    first = int(sizes.get("expert_parallel", {}).get("first_expert", 0))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32)
        for l, w in enumerate(weights["layers"]):
            if sizes["layer_types"][l] == "conv":
                x = conv_mixer(x, {k: w[k] for k in _CONV}, eps=eps)
            else:
                x = _attention(
                    x, {k: w[k] for k in _ATTN},
                    n_head=int(sizes["num_attention_heads"]),
                    n_kv=int(sizes["num_key_value_heads"]), eps=eps,
                    theta=float(sizes["rope_parameters"]["rope_theta"]))
            if "w_router" in w:
                x, idx, edge = experts(
                    x, {k: w[k] for k in _SPARSE},
                    top_k=int(sizes["num_experts_per_tok"]),
                    scale=float(sizes["routed_scaling_factor"]),
                    first=first, eps=eps)
                nearest = jnp.minimum(nearest, edge)
                if routing is not None:
                    routing.append(idx)
                if edges is not None:
                    edges.append(edge)
            else:
                x = _dense(x, w["ffn_norm_g"], w["w_gate"], w["w_up"],
                           w["w_down"], eps=eps)
        out = _head(x, weights["lnf_g"], weights["wte"], eps=eps)
        # (a gap of 0 excuses nothing)
        return jnp.where((nearest < undecided)[:, None], 0.0, out)
