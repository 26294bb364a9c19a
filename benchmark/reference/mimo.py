"""MiMo-V2-Flash block (XiaomiMiMo/MiMo-V2-Flash ``config.json``,
``model_type`` ``mimo_v2_flash``), one RANK'S SHARE of it: the eight steps
of the configuration file's ``assumed``, for one sequence, ``x`` the residual
stream, nothing with a bias term. Layer ``l`` is FULL where
``hybrid_layer_pattern[l]`` is 0 and slides over a WINDOW where it is 1; the
two kinds differ in their KV head count, their rotary base, and a sink:

1. ``h = RMSNorm(x)`` (eps ``layernorm_epsilon``). No norm on q or k.
2. ``q = h Wq`` as 64 heads of 192; ``k = h Wk`` as ``K`` heads of 192 and
   ``v = h Wv`` as ``K`` heads of 128: ``K`` is ``num_key_value_heads`` (4)
   in a full layer, ``swa_num_key_value_heads`` (8) in a window layer.
3. Rotary positions, rotate-half, over the FIRST ``int(192 x
   partial_rotary_factor)`` = 64 values of each q and k head, base
   ``rope_theta`` (5e6) in a full layer and ``swa_rope_theta`` (1e4) in a
   window layer; values 64..191 pass unrotated.
4. Query ``t`` attends key ``j`` iff ``j <= t`` and, in a window layer,
   ``t - j < sliding_window`` (128 positions, itself included); scores over
   ``sqrt(192)``; query head ``n`` reads KV head ``n // (64 / K)``.
5. Full: ``p = softmax(a)``. Window (``add_swa_attention_sink_bias``):
   ``p_j = exp(a_j) / (sum_j exp(a_j) + exp(s_n))``, ``s_n`` one learned
   scalar a query head a layer: the sink takes mass and adds no value.
6. ``o_t = attention_value_scale x sum_j p_j v_j`` (0.707); ``x += o Wo``.
7. ``h2 = RMSNorm(x)``. A dense layer (the first): SwiGLU of 16,384. An
   expert layer: ``s = sigmoid(h2 Wr)`` over ALL the router's experts; the
   chosen set the 8 largest of ``s + b`` (the selection bias, for the choice
   only; one group); ``w_e = s_e / sum of the chosen s``
   (``routed_scaling_factor`` null = 1); ``y = sum over chosen e HELD HERE of
   w_e FFN_e(h2)``, each a SwiGLU of 2,048. No shared expert. What the
   absent experts would add is left out (the deployment's other ranks).
8. Final RMSNorm, untied head over this rank's slice of the vocabulary.

Depth, widths, both head counts, the router's width and how many experts are
held come from the arrays given; which layers slide, the window, both bases,
the rotated share, the value scale, top-k and eps from the configuration
file. With every expert held and ``first_expert`` 0 this is the uncut layer.

Attention runs a block of queries at a time (64 heads x 5,032 x 5,032
scores are 6.5 GB; a block of 256 queries' are 0.33), experts one at a time
and the dense MLP in column blocks: this runs beside a served model that
fills the chip.

Where the choice of step 7 is NOT DECIDED at the precision the configuration
states, this reference says so instead of naming a token, exactly as
``benchmark/reference/kexaone.py`` does and for its reason: each expert
layer reports, per position, how far the nearest expert HELD HERE lies from
the edge of the chosen set, and where ``reference_check.undecided_score_gap``
is set ``logits`` returns a FLAT row at the positions where some layer's
distance is under it (and prints how many rows it excused, of how many). A
flat row passes any comparison of margins.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_DENSE_BLOCK = 2048
_QUERY_BLOCK = 256


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta, width):
    """x [T, N, H]; position t rotates the pair (x[i], x[i + width/2]),
    i < width/2, by t * theta^(-2i/width); values from ``width`` on pass."""
    T = x.shape[0]
    half = width // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:width], x[..., width:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "rotated", "window", "value_scale"))
def _attention(x, w, sink, eps: float, theta: float, rotated: int,
               window: int, value_scale: float):
    """``window`` 0: a full layer. ``sink`` [N] or None."""
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    n_head, H = w["wq"].shape[-2:]
    n_kv, Hv = w["wv"].shape[-2:]
    h = _rms(x, w["ln1_g"], eps)
    q = (h @ w["wq"].reshape(D, -1)).reshape(T, n_head, H)
    k = (h @ w["wk"].reshape(D, -1)).reshape(T, n_kv, H)
    v = (h @ w["wv"].reshape(D, -1)).reshape(T, n_kv, Hv)
    q, k = _rope(q, theta, rotated), _rope(k, theta, rotated)
    G = n_head // n_kv
    pad = -T % _QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _QUERY_BLOCK, n_kv, G, H)
    j = jnp.arange(T)[None, :]

    def block(args):
        q_b, first = args                                  # [B, K, G, H]
        i = first + jnp.arange(_QUERY_BLOCK)[:, None]
        see = j <= i
        if window:
            see = see & (i - j < window)
        s = jnp.einsum("bkgh,skh->kgbs", q_b, k) / math.sqrt(H)
        s = jnp.where(see[None, None], s, -jnp.inf)
        m = s.max(-1, keepdims=True)
        if sink is not None:
            snk = sink.astype(F32).reshape(n_kv, G, 1, 1)
            m = jnp.maximum(m, snk)
        e = jnp.exp(s - m)
        total = e.sum(-1, keepdims=True)
        if sink is not None:
            total = total + jnp.exp(snk - m)
        return jnp.einsum("kgbs,skh->bkgh", e / total, v)

    firsts = jnp.arange(qb.shape[0]) * _QUERY_BLOCK
    a = jax.lax.map(block, (qb, firsts)).reshape(-1, n_head * Hv)[:T]
    return x + (value_scale * a) @ w["wo"].reshape(-1, D)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, ln2_g, gate, up, down, eps: float):
    h = _rms(x, ln2_g.astype(F32), eps)
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
    gates = scale * chosen / chosen.sum(-1, keepdims=True)
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
    h = _rms(x, w["ln2_g"].astype(F32), eps)
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
def _head(x, g, lm_head, eps: float):
    return _rms(x, g.astype(F32), eps) @ lm_head.astype(F32)


_ATTN = ("ln1_g", "wq", "wk", "wv", "wo")
_SPARSE = ("ln2_g", "w_router", "router_bias", "we_up", "we_gate", "we_down")


def logits(weights, tokens, sizes, routing=None, edges=None):
    """[T, V] float32 next-token logits at every position of ``tokens``; a
    flat row where the choice of experts is not decided (see the top).
    ``routing``: a list that receives each EXPERT layer's chosen experts
    [T, top_k]. ``edges``: a list that receives each expert layer's [T]
    distances of the held experts from the chosen set's edge; the caller
    then does its own excusing and every row comes back as computed."""
    eps = float(sizes["layernorm_epsilon"])
    undecided = float(sizes.get("reference_check", {}).get(
        "undecided_score_gap", 0.0)) if edges is None else 0.0
    nearest = jnp.inf
    first = int(sizes.get("expert_parallel", {}).get("first_expert", 0))
    rotated = int(int(sizes["head_dim"]) * float(
        sizes["partial_rotary_factor"]))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32)
        for l, w in enumerate(weights["layers"]):
            slides = bool(sizes["hybrid_layer_pattern"][l])
            sinks = sizes["add_swa_attention_sink_bias" if slides
                          else "add_full_attention_sink_bias"]
            x = _attention(
                x, {k: w[k] for k in _ATTN}, w["sink"] if sinks else None,
                eps=eps,
                theta=float(sizes["swa_rope_theta" if slides
                                  else "rope_theta"]),
                rotated=rotated,
                window=int(sizes["sliding_window"]) if slides else 0,
                value_scale=float(sizes["attention_value_scale"]))
            if "w_router" in w:
                x, idx, edge = experts(
                    x, {k: w[k] for k in _SPARSE},
                    top_k=int(sizes["num_experts_per_tok"]),
                    scale=float(sizes["routed_scaling_factor"] or 1.0),
                    first=first, eps=eps)
                nearest = jnp.minimum(nearest, edge)
                if routing is not None:
                    routing.append(idx)
                if edges is not None:
                    edges.append(edge)
            else:
                x = _dense(x, w["ln2_g"], w["w_gate"], w["w_up"],
                           w["w_down"], eps=eps)
        out = _head(x, weights["lnf_g"], weights["lm_head"], eps=eps)
        if undecided:
            excused = nearest < undecided
            print(f"reference: mimo: {int(excused.sum())} of "
                  f"{excused.shape[0]} positions excused as undecided "
                  f"(a held expert within {undecided} of the chosen set's "
                  "edge in some layer)", flush=True)
            out = jnp.where(excused[:, None], 0.0, out)
        return out
