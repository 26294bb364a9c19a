"""Falcon-H1 block (tiiuae/Falcon-H1-34B-Instruct ``config.json``,
``model_type`` ``falcon_h1``): for one sequence, ``h`` the residual stream;
EVERY layer mixes its sequence twice, a Mamba-2 state-space mixer and GQA
attention in parallel on the same normed input, their outputs summed. No
bias but the conv's; RMSNorm eps ``rms_norm_eps`` with gain. With ``N`` =
``mamba_d_state``, ``G`` = ``mamba_n_groups``, ``H`` = ``mamba_n_heads``,
``P`` = ``mamba_d_head``, ``d_ssm`` = ``mamba_d_ssm``, ``K`` =
``mamba_d_conv``, for layer i:

1. ``u = RMSNorm(h; input_layernorm)``.
2. State-space branch: ``p = ((u * ssm_in_multiplier) W_in) * m``, ``W_in``:
   hidden -> ``d_ssm + (d_ssm + 2 G N) + H``; ``m`` is ``ssm_multipliers``
   spread over the five zones ``[z | x | B | C | dt]``. Split ``z``, ``xBC``,
   ``dt``. ``xBC_t <- silu(b + sum_{j<K} w_j * xBC_{t-K+1+j})``: depthwise,
   causal, zeros before the sequence's start (``mamba_conv_bias``). Split
   ``x_t`` ``[H, P]``, ``B_t``, ``C_t`` ``[G, N]``. ``d_t = softplus(dt_t +
   dt_bias)`` a head; ``A = -exp(A_log)`` a head. The state ``S`` ``[H, P,
   N]``, TOKEN BY TOKEN (``lax.scan``: the program's blocked chunk form is
   held to this independent formulation): ``S_t[h] = exp(d_t[h] A[h])
   S_{t-1}[h] + d_t[h] x_t[h] (x) B_t[g(h)]``, ``g(h) = h // (H / G)``;
   ``y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]``. Then (``mamba_rms_norm``,
   ``mamba_norm_before_gate`` false) ``y <- y * silu(z)``, RMSNorm over
   each of the ``G`` groups of ``d_ssm / G`` channels with a ``d_ssm``-wide
   gain, ``s = (y W_out) * ssm_out_multiplier``.
3. Attention branch, on the same ``u``: ``a = u * attention_in_multiplier``;
   ``q = a W_q``, ``k = (a W_k) * key_multiplier``, ``v = a W_v``; no
   biases, no q/k norm; rotate-half RoPE over the whole head (``head_dim``,
   NOT hidden / heads), theta ``rope_theta``; causal softmax in float32 at
   ``head_dim^-1/2``; ``t = (o W_o) * attention_out_multiplier``.
4. ``h' = h + s + t``; ``v = RMSNorm(h'; pre_ff_layernorm)``; ``h'' = h' +
   ((silu((v W_gate) * mlp_multipliers[0]) * (v W_up)) W_down) *
   mlp_multipliers[1]``.
5. ``h_0 = E[id] * embedding_multiplier``; logits ``= (RMSNorm(h_L;
   final_layernorm) W_head) * lm_head_multiplier``, untied.

Depth and every width come from the arrays given; the state-space sizes, the
head counts, ``head_dim``, theta, eps and the twelve multipliers from the
configuration file's published keys. Imports nothing from the program.

Long prompts: attention in BLOCKS of query rows (no ``[T, T]`` score matrix
of the whole prompt), the MLP in column blocks and the 261,120-wide head in
column blocks: a layer's weights cast to float32 whole would not fit beside
a served model that fills the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_DENSE_BLOCK = 3072     # columns of the MLP's width at a time
_ROW_BLOCK = 512        # query rows of the attention at a time
_HEAD_BLOCK = 32640     # columns of the vocabulary at a time (261,120 / 8)


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, N, H], row t at position t: rotates the pair (x[i], x[i + H/2])
    by t * theta^(-2i/H)."""
    T, _, H = x.shape
    half = H // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=(
    "H", "P", "N", "G", "eps", "m_in", "m_out", "zones"))
def ssm_branch(u, w, H: int, P: int, N: int, G: int, eps: float,
               m_in: float, m_out: float, zones: tuple, S0=None):
    """``s`` [T, D] of step 2 on the normed rows ``u`` [T, D]; also the
    state after the last row. ``S0``: the state before the first (zeros)."""
    T, _ = u.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    d_ssm, width = H * P, H * P + 2 * G * N
    p = (u * m_in) @ w["ssm_in"]
    z = p[:, :d_ssm] * zones[0]
    xBC = jnp.concatenate([
        p[:, d_ssm:2 * d_ssm] * zones[1],
        p[:, 2 * d_ssm:2 * d_ssm + G * N] * zones[2],
        p[:, 2 * d_ssm + G * N:d_ssm + width] * zones[3]], axis=1)
    dt = p[:, d_ssm + width:] * zones[4]
    K = w["conv_taps"].shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, width), F32), xBC], axis=0)
    c = jax.nn.silu(w["conv_bias"] + sum(
        w["conv_taps"][j] * ext[j:j + T] for j in range(K)))
    x = c[:, :d_ssm].reshape(T, H, P)
    Bm = jnp.repeat(c[:, d_ssm:d_ssm + G * N].reshape(T, G, N), H // G, 1)
    Cm = jnp.repeat(c[:, d_ssm + G * N:].reshape(T, G, N), H // G, 1)
    delta = jax.nn.softplus(dt + w["dt_bias"])              # [T, H]
    A = -jnp.exp(w["A_log"])                                # [H]

    def step(S, row):
        x_t, B_t, C_t, d_t = row
        S = (jnp.exp(d_t * A)[:, None, None] * S
             + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, (S * C_t[:, None, :]).sum(-1)

    S0 = jnp.zeros((H, P, N), F32) if S0 is None else S0
    S, y = jax.lax.scan(step, S0, (x, Bm, Cm, delta))
    y = (y + w["D"][:, None] * x).reshape(T, d_ssm) * jax.nn.silu(z)
    y = _rms(y.reshape(T, G, d_ssm // G), 1.0, eps).reshape(T, d_ssm)
    return ((y * w["ssm_norm_g"]) @ w["ssm_out"]) * m_out, S


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "head", "theta", "m_in", "m_key", "m_out"))
def attention_branch(u, w, n_head: int, n_kv: int, head: int, theta: float,
                     m_in: float, m_key: float, m_out: float):
    """``t`` [T, D] of step 3 on the normed rows ``u`` [T, D]."""
    T, D = u.shape
    w = {k: a.astype(F32) for k, a in w.items()}
    a = u * m_in
    # projections may arrive as [D, heads, head] / [heads, head, D]
    q = _rope((a @ w["wq"].reshape(D, -1)).reshape(T, n_head, head), theta)
    k = _rope(((a @ w["wk"].reshape(D, -1)) * m_key).reshape(T, n_kv, head),
              theta)
    v = (a @ w["wv"].reshape(D, -1)).reshape(T, n_kv, head)
    group = n_head // n_kv
    outs = []
    for lo in range(0, T, _ROW_BLOCK):       # a block of queries, keys 0..hi
        hi = min(lo + _ROW_BLOCK, T)
        qb = q[lo:hi].reshape(hi - lo, n_kv, group, head)
        s = jnp.einsum("tkgh,skh->kgts", qb, k[:hi]) / math.sqrt(head)
        see = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgts,skh->tkgh", p, v[:hi]).reshape(
            hi - lo, n_head * head))
    return (jnp.concatenate(outs, axis=0) @ w["wo"].reshape(-1, D)) * m_out


@functools.partial(jax.jit, static_argnames=("eps", "m_gate", "m_down"))
def _mlp(x, g, gate, up, down, eps: float, m_gate: float, m_down: float):
    v = _rms(x, g.astype(F32), eps)
    y = jnp.zeros_like(x)
    for lo in range(0, gate.shape[1], _DENSE_BLOCK):   # columns of the width
        hi = lo + _DENSE_BLOCK
        y = y + (jax.nn.silu((v @ gate[:, lo:hi].astype(F32)) * m_gate)
                 * (v @ up[:, lo:hi].astype(F32))) @ down[lo:hi].astype(F32)
    return x + y * m_down


@functools.partial(jax.jit, static_argnames=("lo", "m_head"))
def _head_block(v, w_head, lo: int, m_head: float):
    return (v @ w_head[:, lo:lo + _HEAD_BLOCK].astype(F32)) * m_head


def _head(x, g, w_head, eps: float, m_head: float):
    """The logits, a block of columns at a time, put together ON THE HOST:
    [T, 261,120] float32 is a gigabyte at the check's lengths, and a second
    copy of it does not fit beside a served model that fills the chip."""
    v = _rms(x, g.astype(F32), eps)
    return np.concatenate([
        np.asarray(_head_block(v, w_head, lo=lo, m_head=m_head))
        for lo in range(0, w_head.shape[1], _HEAD_BLOCK)], axis=1)


_SSM = ("ssm_in", "conv_taps", "conv_bias", "A_log", "D", "dt_bias",
        "ssm_norm_g", "ssm_out")
_ATTN = ("wq", "wk", "wv", "wo")


def logits(weights, tokens, sizes, without=(), cut_state_at=0):
    """[T, V] float32 next-token logits at every position of ``tokens``.
    ``without`` (controls: a reference that is wrong on purpose): ``"ssm"``
    or ``"attention"`` leaves that branch out of every layer.
    ``cut_state_at`` > 0: the state-space state (and the conv's inputs) are
    NOT carried across that position, as a program that dropped the
    hand-over between two chunks would compute."""
    eps = float(sizes["rms_norm_eps"])
    H, P = int(sizes["mamba_n_heads"]), int(sizes["mamba_d_head"])
    ssm = dict(H=H, P=P, N=int(sizes["mamba_d_state"]),
               G=int(sizes["mamba_n_groups"]), eps=eps,
               m_in=float(sizes["ssm_in_multiplier"]),
               m_out=float(sizes["ssm_out_multiplier"]),
               zones=tuple(float(m) for m in sizes["ssm_multipliers"]))
    attn = dict(n_head=int(sizes["num_attention_heads"]),
                n_kv=int(sizes["num_key_value_heads"]),
                head=int(sizes["head_dim"]),
                theta=float(sizes["rope_theta"]),
                m_in=float(sizes["attention_in_multiplier"]),
                m_key=float(sizes["key_multiplier"]),
                m_out=float(sizes["attention_out_multiplier"]))
    m_gate, m_down = (float(m) for m in sizes["mlp_multipliers"])
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32) * float(
            sizes["embedding_multiplier"])
        for w in weights["layers"]:
            u = _rms(x, w["in_norm_g"].astype(F32), eps)
            mixed = x
            if "ssm" not in without:
                ws = {k: w[k] for k in _SSM}
                if 0 < cut_state_at < len(tokens):
                    mixed = mixed + jnp.concatenate([
                        ssm_branch(u[:cut_state_at], ws, **ssm)[0],
                        ssm_branch(u[cut_state_at:], ws, **ssm)[0]], axis=0)
                else:
                    mixed = mixed + ssm_branch(u, ws, **ssm)[0]
            if "attention" not in without:
                mixed = mixed + attention_branch(
                    u, {k: w[k] for k in _ATTN}, **attn)
            x = _mlp(mixed, w["ff_norm_g"], w["w_gate"], w["w_up"],
                     w["w_down"], eps=eps, m_gate=m_gate, m_down=m_down)
        return _head(x, weights["lnf_g"], weights["w_head"], eps=eps,
                     m_head=float(sizes["lm_head_multiplier"]))
