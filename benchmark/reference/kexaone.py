"""K-EXAONE-236B-A23B block (LGAI-EXAONE/K-EXAONE-236B-A23B ``config.json``,
``model_type`` ``exaone_moe``), one RANK'S SHARE of it: the six steps of the
configuration file's ``assumed``, for one sequence, ``x`` the residual stream,
nothing with a bias term of its own:

1. ``h = RMSNorm(x)`` (eps 1e-5); ``q = h Wq`` as 64 heads of 128, ``k``, ``v``
   as 8 heads of 128 (``head_dim`` is the config's own key, not hidden /
   heads).
2. ``q``, ``k``: RMSNorm over each HEAD's 128 values, one learned scale of
   width 128 shared by all heads, before the rotary positions.
3. A sliding layer (``layer_types[l] == "sliding_attention"``) rotates ``q``
   and ``k`` (theta 1e6, rotate-half over the whole head); a full layer has
   no positional signal at all.
4. Query ``i`` attends key ``j`` iff ``j <= i`` and, in a sliding layer,
   ``i - j < sliding_window`` (128 positions, itself included); scores over
   ``sqrt(128)``, softmax in float32, 8 query heads a key head.
5. ``h2 = RMSNorm(x)``. A dense layer (the first): SwiGLU of width 18,432.
   A sparse layer: ``s = sigmoid(h2 Wr)`` over ALL the router's experts; the
   chosen set the 8 largest of ``s + b`` (``b`` the selection bias, for the
   choice only; one group, so no group limit); ``w_e = 2.5 s_e / sum of the
   chosen s``; ``y = sum over chosen e HELD HERE of w_e FFN_e(h2) +
   FFN_shared(h2)``, each a SwiGLU of width 2,048. What the absent experts
   would add is left out (they live on the deployment's other ranks).
6. Final RMSNorm, untied head over this rank's slice of the vocabulary.

Depth, widths, the router's width and how many experts are held come from
the arrays given; which experts (``expert_parallel.first_expert``), which
layers slide, the window, top-k, the scaling factor and eps from the
configuration file. With every expert held and ``first_expert`` 0 this is
the uncut layer. The multi-token-prediction layer is not part of the main
model's logits and is not here.

Experts run one at a time and the dense MLP in column blocks: a layer's
weights cast to float32 whole would not fit beside a served model that fills
the chip.

Where the choice of step 5 is NOT DECIDED at the precision the configuration
states, this reference says so instead of naming a token. The eighth and the
ninth best of 128 scores lie about 0.016 apart; a served path that computes
in bfloat16 carries a residual stream a few thousandths off this one, ranks
two near-tied scores the other way at about one chosen expert in a hundred,
and the token then goes through ANOTHER expert whose gate (2.5 / 8) weighs as
much as the best one's: another function, not wrong arithmetic, and logits
0.2-1.2 away. So each sparse layer also reports, per position, how far the
nearest expert HELD HERE lies from the edge of the chosen set (a chosen
expert's score above the best one left out, an unchosen one's below the
worst one chosen; ``inf`` where this rank holds none of either), and where
``reference_check.undecided_score_gap`` of the configuration file is set,
``logits`` returns a FLAT row (all zeros: every token equally good) at the
positions where some layer's distance is under it. A flat row passes any
comparison of margins, so those positions are excused; every other position
is held to the choice made here. Swaps among experts this rank does not hold
change nothing it computes (the renormalising sum moves by the near-tie's
width) and are not excused.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_DENSE_BLOCK = 2048


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


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "eps", "theta", "window"))
def _attention(x, w, n_head: int, n_kv: int, eps: float, theta: float,
               window: int):
    """``window`` 0: a full layer (and no rotary positions)."""
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    # projections may arrive as [D, heads, head] / [heads, head, D]
    for k in ("wq", "wk", "wv"):
        w[k] = w[k].reshape(D, -1)
    w["wo"] = w["wo"].reshape(-1, D)
    H = w["q_norm_g"].shape[0]
    h = _rms(x, w["ln1_g"], eps)
    q = _rms((h @ w["wq"]).reshape(T, n_head, H), w["q_norm_g"], eps)
    k = _rms((h @ w["wk"]).reshape(T, n_kv, H), w["k_norm_g"], eps)
    v = (h @ w["wv"]).reshape(T, n_kv, H)
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    s = jnp.einsum("tnh,snh->nts", q, k) / math.sqrt(H)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    see = j <= i
    if window:
        see = see & (i - j < window)
    s = jnp.where(see[None], s, -jnp.inf)
    a = jnp.einsum("nts,snh->tnh", jax.nn.softmax(s, axis=-1), v)
    return x + a.reshape(T, n_head * H) @ w["wo"]


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
    "top_k", "scale", "first", "eps", "shared"))
def experts(x, w, top_k: int, scale: float, first: int, eps: float,
            shared: bool = True):
    """x + the held experts' part of the mixture (+ the shared expert,
    unless ``shared`` is False: a test sums the ranks' parts and counts it
    once); also the chosen experts [T, top_k], ids among all the router's,
    and the held experts' least distance from the chosen set's edge [T]."""
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
    if shared:
        y = y + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    return x + y, idx, edge[:, first:first + held].min(-1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, lm_head, eps: float):
    return _rms(x, g.astype(F32), eps) @ lm_head.astype(F32)


_ATTN = ("ln1_g", "wq", "wk", "wv", "wo", "q_norm_g", "k_norm_g")
_SPARSE = ("ln2_g", "w_router", "router_bias", "we_up", "we_gate", "we_down",
           "ws_gate", "ws_up", "ws_down")


def logits(weights, tokens, sizes, routing=None, edges=None):
    """[T, V] float32 next-token logits at every position of ``tokens``; a
    flat row where the choice of experts is not decided (see the top).
    ``routing``: a list that receives each SPARSE layer's chosen experts
    [T, top_k]. ``edges``: a list that receives each sparse layer's [T]
    distances of the held experts from the chosen set's edge; the caller
    then does its own excusing and every row comes back as computed."""
    eps = float(sizes["rms_norm_eps"])
    undecided = float(sizes.get("reference_check", {}).get(
        "undecided_score_gap", 0.0)) if edges is None else 0.0
    nearest = jnp.inf
    first = int(sizes.get("expert_parallel", {}).get("first_expert", 0))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32)
        for l, w in enumerate(weights["layers"]):
            slides = sizes["layer_types"][l] == "sliding_attention"
            x = _attention(
                x, {k: w[k] for k in _ATTN},
                n_head=int(sizes["num_attention_heads"]),
                n_kv=int(sizes["num_key_value_heads"]), eps=eps,
                theta=float(sizes["rope_parameters"]["rope_theta"]),
                window=int(sizes["sliding_window"]) if slides else 0)
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
                x = _dense(x, w["ln2_g"], w["w_gate"], w["w_up"],
                           w["w_down"], eps=eps)
        out = _head(x, weights["lnf_g"], weights["lm_head"], eps=eps)
        if undecided:
            out = jnp.where((nearest < undecided)[:, None], 0.0, out)
        return out
