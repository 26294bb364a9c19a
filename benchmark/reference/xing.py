"""Xing4.0-29B-A4B block (XingChen-AGI/Xing4.0-29B-A4B ``config.json``,
``model_type`` ``xing4_0``), one RANK'S SHARE of it, for one sequence: the
steps of the configuration file's ``assumed``. Nothing has a bias term.

1. STREAMS. A token's state is ``X`` in R^{4 x D} (``hc_mult`` 4). ``X_0``
   is the embedding row copied into all four streams; after the last layer
   ``h`` is the sum of the four streams, then the final RMSNorm and the
   untied head.
2. Every SUBLAYER ``F`` (attention, then MLP or experts: 2 a layer, each
   with maps of its own), in float32:
   ``x~ = RMSNorm(vec(X))`` over all ``4 D`` values, no gain;
   ``H~pre = a_pre (x~ Phi_pre) + b_pre`` in R^4,
   ``H~post = a_post (x~ Phi_post) + b_post`` in R^4,
   ``H~res = a_res mat(x~ Phi_res) + B_res`` in R^{4x4} (``Phi``: ``4 D x
   (4 + 4 + 16)``, ``mat`` row-major; ``a_*`` scalars);
   ``H_pre = sigmoid(H~pre)``, ``H_post = 2 sigmoid(H~post)``,
   ``H_res = SK(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))``:
   ``M = exp(.)``, then ``hc_sinkhorn_iters`` times (all of them)
   ``M <- M / (column sums + hc_eps)``, ``M <- M / (row sums + hc_eps)``;
   ``u = H_pre X`` in R^D; ``y = F(RMSNorm_g(u))``;
   ``X <- H_res X + H_post^T y``.
3. ATTENTION (latent, DeepSeek-V2/V3's): ``c_q = RMSNorm_g(x W_dq)``;
   ``q = c_q W_uq`` as heads of ``qk_nope_head_dim + qk_rope_head_dim``;
   ``[c_kv | k_r] = x W_dkv`` (``kv_lora_rank + qk_rope_head_dim``),
   ``c_kv <- RMSNorm_g(c_kv)``; rotary on ``q``'s rotary part and on ``k_r``
   (ONE key a position, shared by all heads), pairs ``(2i, 2i+1)``, YaRN by
   ``rope_scaling``: ``inv_freq_i`` the blend of ``theta^(-2i/d)`` and that
   over ``factor`` by the linear ramp between the correction dims of
   ``beta_fast`` and ``beta_slow`` over ``original_max_position_embeddings``,
   cos / sin times ``mscale(factor, mscale) / mscale(factor,
   mscale_all_dim)``; ``[k_n | v]_h = c_kv W_ukv``;
   ``a_tj = (q_n . k_n + q_r . k_r) x (nope + rope)^-1/2 x m^2``,
   ``m = 0.1 mscale_all_dim ln(factor) + 1``; causal softmax in float32;
   ``o_h = sum_j p_j v_jh``; out ``= concat(o) W_o``.
4. MLP: the first ``first_k_dense_replace`` layers a dense SwiGLU of
   ``intermediate_size``; the rest ``s = sigmoid(x W_r)`` over ALL the
   router's experts, the ``num_experts_per_tok`` largest of ``s + b``,
   ``w_e = routed_scaling_factor s_e / sum of the chosen s``, ``y = sum over
   chosen e HELD HERE of w_e FFN_e(x) + FFN_shared(x)`` (K-EXAONE's rule,
   ``benchmark/reference/kexaone.py``, whose excusing of UNDECIDED choices
   this file keeps: a flat row where some layer's held expert lies within
   ``reference_check.undecided_score_gap`` of the chosen set's edge).

Depth, widths, ranks, the router's width and how many experts are held come
from the arrays given; everything else from the configuration file. The
multi-token-prediction layer is not part of the main model's logits and is
not here. Attention runs a block of queries at a time, experts one at a time
and the dense MLP in column blocks: float32 scores of 5,000 x 5,000 x 32
heads, or a layer's weights cast whole, would not fit beside a served model
that fills the chip. Imports nothing from the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_DENSE_BLOCK = 2304
_QUERY_BLOCK = 256


def _rms(x, g, eps):
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
    return y if g is None else y * g


def sinkhorn(m, iters: int, eps: float):
    """``m`` [..., n, n] positive -> ``iters`` rounds of columns, then rows,
    each over its sum + ``eps``."""
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def maps(X, w, n: int, eps: float, iters: int, hc_eps: float, clamp):
    """Step 2's three maps of ``X`` [T, n, D]: ``H_pre`` [T, n], ``H_post``
    [T, n], ``H_res`` [T, n, n]. ``w``: ``phi`` [n D, 2 n + n n], ``a`` [3]
    (pre, post, res), ``b_pre`` [n], ``b_post`` [n], ``b_res`` [n, n]."""
    T = X.shape[0]
    x = _rms(X.reshape(T, -1), None, eps)
    raw = x @ w["phi"].astype(F32)
    a = w["a"].astype(F32)
    pre = a[0] * raw[:, :n] + w["b_pre"].astype(F32)
    post = a[1] * raw[:, n:2 * n] + w["b_post"].astype(F32)
    res = a[2] * raw[:, 2 * n:].reshape(T, n, n) + w["b_res"].astype(F32)
    res = sinkhorn(jnp.exp(jnp.clip(res, clamp[0], clamp[1])), iters, hc_eps)
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), res


def mix(X, y, post, res):
    """``X <- H_res X + H_post^T y``."""
    return (jnp.einsum("tij,tjd->tid", res, X)
            + post[:, :, None] * y[:, None, :])


def yarn_inv_freq(dim: int, theta: float, scaling: dict):
    """[dim / 2] rotary frequencies under YaRN (DeepSeek-V3's form)."""
    base = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if not scaling:
        return base
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(scaling["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, inv_freq, gain: float):
    """x [T, ..., d]; position t rotates the pair (x[2i], x[2i+1]) by
    ``t inv_freq_i``; the result holds the pairs' first halves, then their
    second halves (the same order for q and k: scores do not see it)."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    shape = (T,) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = (gain * jnp.cos(ang)).reshape(shape), (
        gain * jnp.sin(ang)).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "scaling", "nope", "rope"))
def attention(x, w, eps: float, theta: float, scaling, nope: int, rope: int):
    """Step 3 on the sublayer's normed input ``x`` [T, D] -> [T, D].
    ``scaling``: ``rope_scaling`` as a tuple of items (hashable), or ()."""
    scaling = dict(scaling)
    T, D = x.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    n_head = w["w_uq"].shape[-2]
    rank = w["kv_norm_g"].shape[0]
    Hv = w["w_ukv"].shape[-1] - nope
    c_q = _rms(x @ w["w_dq"], w["q_norm_g"], eps)
    q = (c_q @ w["w_uq"].reshape(c_q.shape[-1], -1)).reshape(
        T, n_head, nope + rope)
    ckv = x @ w["w_dkv"]
    c_kv, k_r = _rms(ckv[:, :rank], w["kv_norm_g"], eps), ckv[:, rank:]
    inv = yarn_inv_freq(rope, theta, scaling)
    factor = float(scaling.get("factor", 1.0))
    gain = (_mscale(factor, float(scaling.get("mscale", 1.0)))
            / _mscale(factor, float(scaling.get("mscale_all_dim", 0.0)))
            ) if scaling else 1.0
    q_n, q_r = q[..., :nope], _rope(q[..., nope:], inv, gain)
    k_r = _rope(k_r, inv, gain)                          # [T, rope]: ONE key
    kv = (c_kv @ w["w_ukv"].reshape(rank, -1)).reshape(T, n_head, nope + Hv)
    k_n, v = kv[..., :nope], kv[..., nope:]
    m = _mscale(factor, float(scaling.get("mscale_all_dim", 0.0))
                ) if scaling else 1.0
    scale = (nope + rope) ** -0.5 * m * m
    pad = -T % _QUERY_BLOCK
    blocks = lambda a: jnp.pad(  # noqa: E731
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, _QUERY_BLOCK) + a.shape[1:])
    j = jnp.arange(T)[None, :]

    def block(args):
        qn_b, qr_b, first = args
        i = first + jnp.arange(_QUERY_BLOCK)[:, None]
        s = (jnp.einsum("bnh,snh->nbs", qn_b, k_n)
             + jnp.einsum("bnh,sh->nbs", qr_b, k_r)) * scale
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        return jnp.einsum("nbs,snh->bnh", jax.nn.softmax(s, axis=-1), v)

    firsts = jnp.arange(-(-T // _QUERY_BLOCK)) * _QUERY_BLOCK
    o = jax.lax.map(block, (blocks(q_n), blocks(q_r), firsts))
    return o.reshape(-1, n_head * Hv)[:T] @ w["wo"].reshape(-1, D)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


@jax.jit
def dense(h, gate, up, down):
    y = jnp.zeros_like(h)
    for a in range(0, gate.shape[1], _DENSE_BLOCK):   # columns of the width
        b = a + _DENSE_BLOCK
        y = y + _swiglu(h, gate[:, a:b], up[:, a:b], down[a:b])
    return y


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
    "top_k", "scale", "first", "shared"))
def experts(h, w, top_k: int, scale: float, first: int, shared: bool = True):
    """The held experts' part of the mixture of ``h`` [T, D] (normed), plus
    the shared expert (unless ``shared`` is False: a test sums the ranks'
    parts and counts it once); also the chosen experts [T, top_k], ids among
    all the router's, and the held experts' least distance from the chosen
    set's edge [T]."""
    idx, weight, edge = route(
        h, w["w_router"], w["router_bias"], top_k, scale)
    held = w["we_up"].shape[0]

    def one(acc, e):
        up, gate, down, w_e = e
        return acc + w_e[:, None] * _swiglu(h, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (w["we_up"], w["we_gate"], w["we_down"],
         weight[:, first:first + held].T))
    if shared:
        y = y + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    return y, idx, edge[:, first:first + held].min(-1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(X, g, lm_head, eps: float):
    return _rms(X.sum(1), g.astype(F32), eps) @ lm_head.astype(F32)


_ATTN = ("w_dq", "q_norm_g", "w_uq", "w_dkv", "kv_norm_g", "w_ukv", "wo")
_SPARSE = ("w_router", "router_bias", "we_up", "we_gate", "we_down",
           "ws_gate", "ws_up", "ws_down")


def logits(weights, tokens, sizes, routing=None, edges=None):
    """[T, V] float32 next-token logits at every position of ``tokens``; a
    flat row where the choice of experts is not decided (see the top).
    ``routing``: a list that receives each EXPERT layer's chosen experts
    [T, top_k]. ``edges``: a list that receives each expert layer's [T]
    distances of the held experts from the chosen set's edge; the caller
    then does its own excusing and every row comes back as computed."""
    eps = float(sizes["rms_norm_eps"])
    n = int(sizes["hc_mult"])
    hc = dict(n=n, eps=eps, iters=int(sizes["hc_sinkhorn_iters"]),
              hc_eps=float(sizes["hc_eps"]),
              clamp=(float(sizes["mhc_h_res_clamp_min"]),
                     float(sizes["mhc_h_res_clamp_max"])))
    jmaps = jax.jit(functools.partial(maps, **hc))
    scaling = tuple(sorted((sizes.get("rope_scaling") or {}).items()))
    undecided = float(sizes.get("reference_check", {}).get(
        "undecided_score_gap", 0.0)) if edges is None else 0.0
    nearest = jnp.full((len(tokens),), jnp.inf, F32)
    first = int(sizes.get("expert_parallel", {}).get("first_expert", 0))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32)
        X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
        for w in weights["layers"]:
            pre, post, res = jmaps(X, w["hc_attn"])
            u = jnp.einsum("ti,tid->td", pre, X)
            y = attention(
                _rms(u, w["ln1_g"].astype(F32), eps),
                {k: w[k] for k in _ATTN}, eps=eps,
                theta=float(sizes["rope_theta"]), scaling=scaling,
                nope=int(sizes["qk_nope_head_dim"]),
                rope=int(sizes["qk_rope_head_dim"]))
            X = mix(X, y, post, res)
            pre, post, res = jmaps(X, w["hc_mlp"])
            u = jnp.einsum("ti,tid->td", pre, X)
            h = _rms(u, w["ln2_g"].astype(F32), eps)
            if "w_router" in w:
                y, idx, edge = experts(
                    h, {k: w[k] for k in _SPARSE},
                    top_k=int(sizes["num_experts_per_tok"]),
                    scale=float(sizes["routed_scaling_factor"]),
                    first=first)
                nearest = jnp.minimum(nearest, edge)
                if routing is not None:
                    routing.append(idx)
                if edges is not None:
                    edges.append(edge)
            else:
                y = dense(h, w["w_gate"], w["w_up"], w["w_down"])
            X = mix(X, y, post, res)
        out = _head(X, weights["lnf_g"], weights["lm_head"], eps=eps)
        if undecided:
            excused = nearest < undecided
            print(f"reference: xing: {int(excused.sum())} of "
                  f"{excused.shape[0]} positions excused as undecided "
                  f"(a held expert within {undecided} of the chosen set's "
                  "edge in some layer)", flush=True)
            out = jnp.where(excused[:, None], 0.0, out)
        return out
