"""GLM-5 block (zai-org/GLM-5 ``config.json``, ``model_type``
``glm_moe_dsa``), one RANK'S SHARE of it, for one sequence: the steps of the
configuration file's ``assumed``. ``x`` is the sublayer's RMSNorm'd input;
nothing has a bias term but the index key's LayerNorm.

1. LATENT ATTENTION (DeepSeek-V3's): ``c_q = RMSNorm_g(x W_dq)``; ``q = c_q
   W_uq`` as heads of ``qk_nope_head_dim`` (no positions) +
   ``qk_rope_head_dim`` (rotary); ``[c_kv | k_r] = x W_dkv``
   (``kv_lora_rank + qk_rope_head_dim``), ``c_kv <- RMSNorm_g(c_kv)``;
   rotary on q's rotary part and on ``k_r`` (ONE key a position, shared by
   all heads), pairs ``(2i, 2i+1)``, ``rope_parameters.rope_theta``, no
   scaling; ``[k_n | v]_h = c_kv W_ukv``; score ``(q_n . k_n + q_r . k_r) x
   (nope + rope)^-1/2``; softmax in float32 OVER THE SELECTED POSITIONS ONLY
   (step 3); ``o_h = sum_j p_j v_jh``; out ``= concat(o) W_o``. Keys and
   values are EXPANDED here, never absorbed.
2. INDEXER (DeepSeek-V3.2's): ``qI = c_q W_qI`` as ``index_n_heads`` heads
   of ``index_head_dim``, from the q LATENT; ``kI = LayerNorm(x W_kI)``
   (scale and bias, eps 1e-6), ONE key a position; ``w = x W_w``
   (``index_n_heads``). Rotary at the same theta, pairs ``(2i, 2i+1)``, on
   the FIRST ``qk_rope_head_dim`` values of qI and kI, the rest unrotated.
   ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) x index_head_dim^-1/2 x
   index_n_heads^-1/2`` in float32, ``s <= t``: the full ``[T, T]``.
3. SELECTION: query t attends the ``index_topk`` positions ``s <= t`` with
   the largest ``I[t, s]``, all of them while ``t < index_topk``; of equal
   scores the LOWER position; the same set for every head. Literal: a
   stable sort of every row, the first ``index_topk`` of it.
4. MLP: a leading dense SwiGLU layer (as many as the arrays given have);
   the rest ``s = sigmoid(x W_r)`` over ALL the router's experts, the
   ``num_experts_per_tok`` largest of ``s + b``, ``w_e =
   routed_scaling_factor s_e / sum of the chosen s``, ``y = sum over chosen
   e HELD HERE of w_e FFN_e(x) + FFN_shared(x)`` (K-EXAONE's rule,
   ``benchmark/reference/kexaone.py``, whose excusing of UNDECIDED choices
   this file keeps: a flat row where some layer's held expert lies within
   ``reference_check.undecided_score_gap`` of the chosen set's edge).
5. Pre-norm, a final RMSNorm, an untied head over the rows held.

Depth, widths, ranks, the router's width and how many experts are held come
from the arrays given; everything else from the configuration file. The
multi-token-prediction layer is not part of the main model's logits and is
not here. Attention runs a group of heads and a block of queries at a time,
experts one at a time and the dense MLP in column blocks: 64 heads of 5,000
keys and values expanded at once, or a layer's weights cast whole, would not
fit beside a served model that fills the chip. Imports nothing from the
program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
_DENSE_BLOCK = 2048
_QUERY_BLOCK = 256
_HEAD_GROUP = 16
_INDEX_EPS = 1e-6       # the index key's LayerNorm (DeepSeek-V3.2's)


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _rope(x, theta: float):
    """x [T, ..., d]; position t rotates the pair (x[2i], x[2i+1]) by ``t
    theta^(-2i/d)``; the result holds the pairs' first halves, then their
    second halves (the same order for queries and keys: a score does not
    see it)."""
    T, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    shape = (T,) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(a, pad):
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (-1, _QUERY_BLOCK) + a.shape[1:])


def index_parts(x, c_q, w, theta: float, rope: int,
                rotate_keys: bool = True):
    """Step 2's queries [T, n, Hi], key [T, Hi] and head weights [T, n].
    ``rotate_keys`` False is a WITNESS (the index keys left unrotated: a
    wrong indexer, which the comparison that decides ``correct`` must
    refuse), never the model."""
    T = x.shape[0]
    n = w["ww_index"].shape[-1]
    q_i = (c_q @ w["wq_index"].reshape(c_q.shape[-1], -1)).reshape(T, n, -1)
    k_i = _layer_norm(x @ w["wk_index"], w["k_index_norm_g"],
                      w["k_index_norm_b"], _INDEX_EPS)
    turn = lambda a: jnp.concatenate(  # noqa: E731
        [_rope(a[..., :rope], theta), a[..., rope:]], -1)
    return turn(q_i), turn(k_i) if rotate_keys else k_i, x @ w["ww_index"]


def select(q_i, k_i, w_i, topk: int):
    """Steps 2 and 3: ``chosen`` [T, T] bool, row t the positions query t
    attends; and the scores ``I`` [T, T] (``-inf`` where ``s > t``)."""
    T, n, Hi = q_i.shape
    pad = -T % _QUERY_BLOCK
    j = jnp.arange(T)[None, :]

    def rows(block):
        t, q_b, w_b = block
        causal = j <= t[:, None]
        index = (jax.nn.relu(jnp.einsum("rjh,sh->rjs", q_b, k_i))
                 * w_b[:, :, None]).sum(1) * (Hi ** -0.5 * n ** -0.5)
        index = jnp.where(causal, index, -jnp.inf)
        best = jnp.argsort(-index, axis=-1, stable=True)[:, :topk]
        chosen = jnp.zeros(causal.shape, bool).at[
            jnp.arange(len(t))[:, None], best].set(True) & causal
        return chosen, index

    chosen, index = jax.lax.map(
        rows, (_blocks(jnp.arange(T), pad), _blocks(q_i, pad),
               _blocks(w_i, pad)))
    return chosen.reshape(-1, T)[:T], index.reshape(-1, T)[:T]


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "nope", "rope", "topk", "rotate_keys"))
def attention(x, w, eps: float, theta: float, nope: int, rope: int,
              topk: int, chosen=None, rotate_keys: bool = True):
    """Steps 1-3 on the sublayer's normed input ``x`` [T, D] -> [T, D].
    ``chosen`` [T, T]: a selection given (a test's), not computed."""
    T, D = x.shape
    # (cast where used, a group of heads at a time: the output projection
    # alone is 100M values)
    wide = ("w_uq", "w_ukv", "wo")
    w = {k: a if k in wide else a.astype(F32) for k, a in w.items()}
    n_head = w["w_uq"].shape[-2]
    rank = w["kv_norm_g"].shape[0]
    Hv = w["w_ukv"].shape[-1] - nope
    c_q = _rms(x @ w["w_dq"], w["q_norm_g"], eps)
    ckv = x @ w["w_dkv"]
    c_kv = _rms(ckv[:, :rank], w["kv_norm_g"], eps)
    k_r = _rope(ckv[:, rank:], theta)                     # [T, rope]: ONE key
    if chosen is None:
        chosen, _ = select(
            *index_parts(x, c_q, w, theta, rope, rotate_keys), topk)
    scale = (nope + rope) ** -0.5
    pad = -T % _QUERY_BLOCK
    picked = _blocks(chosen, pad)
    out = jnp.zeros((T, D), F32)
    for h in range(0, n_head, _HEAD_GROUP):               # a group of heads
        heads = slice(h, min(h + _HEAD_GROUP, n_head))
        q = jnp.einsum("tr,rnh->tnh", c_q, w["w_uq"][:, heads].astype(F32))
        kv = jnp.einsum("tr,rnh->tnh", c_kv,
                        w["w_ukv"][:, heads].astype(F32))
        k_n, v = kv[..., :nope], kv[..., nope:]
        q_n, q_r = q[..., :nope], _rope(q[..., nope:], theta)

        def block(args, k_n=k_n, v=v):
            qn_b, qr_b, keep = args
            s = (jnp.einsum("bnh,snh->nbs", qn_b, k_n)
                 + jnp.einsum("bnh,sh->nbs", qr_b, k_r)) * scale
            s = jnp.where(keep[None], s, -jnp.inf)
            p = jnp.where(keep[None], jax.nn.softmax(s, axis=-1), 0.0)
            return jnp.einsum("nbs,snh->bnh", p, v)

        o = jax.lax.map(block, (_blocks(q_n, pad), _blocks(q_r, pad), picked))
        out = out + o.reshape(-1, o.shape[-2] * Hv)[:T] @ w["wo"][
            heads].astype(F32).reshape(-1, D)
    return out


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
    choice = s + bias.astype(F32)
    best, idx = jax.lax.top_k(choice, top_k + 1)
    idx = idx[:, :top_k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    gates = scale * chosen / chosen.sum(-1, keepdims=True)
    picked = jax.nn.one_hot(idx, s.shape[-1], dtype=F32)          # [T, k, E]
    weight = (picked * gates[..., None]).sum(1)
    edge = jnp.where(picked.sum(1) > 0, choice - best[:, top_k:],
                     best[:, top_k - 1:top_k] - choice)
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
def _head(x, g, lm_head, eps: float):
    return _rms(x, g.astype(F32), eps) @ lm_head.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, eps: float):
    return _rms(x, g.astype(F32), eps)


_ATTN = ("w_dq", "q_norm_g", "w_uq", "w_dkv", "kv_norm_g", "w_ukv", "wo",
         "wq_index", "wk_index", "k_index_norm_g", "k_index_norm_b",
         "ww_index")
_SPARSE = ("w_router", "router_bias", "we_up", "we_gate", "we_down",
           "ws_gate", "ws_up", "ws_down")


def attention_sizes(sizes) -> dict:
    """The configuration file's keys :func:`attention` takes."""
    return dict(
        eps=float(sizes["rms_norm_eps"]),
        theta=float(sizes["rope_parameters"]["rope_theta"]),
        nope=int(sizes["qk_nope_head_dim"]),
        rope=int(sizes["qk_rope_head_dim"]),
        topk=int(sizes["index_topk"]))


def logits(weights, tokens, sizes, routing=None, edges=None,
           rotate_index_keys: bool = True):
    """[T, V] float32 next-token logits at every position of ``tokens``; a
    flat row where the choice of experts is not decided (see the top).
    ``routing``: a list that receives each EXPERT layer's chosen experts
    [T, top_k]. ``edges``: a list that receives each expert layer's [T]
    distances of the held experts from the chosen set's edge; the caller
    then does its own excusing and every row comes back as computed.
    ``rotate_index_keys`` False: the witness of :func:`index_parts`."""
    attn = dict(attention_sizes(sizes), rotate_keys=rotate_index_keys)
    eps = attn["eps"]
    undecided = float(sizes.get("reference_check", {}).get(
        "undecided_score_gap", 0.0)) if edges is None else 0.0
    nearest = jnp.full((len(tokens),), jnp.inf, F32)
    first = int(sizes.get("expert_parallel", {}).get("first_expert", 0))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights["wte"][tokens].astype(F32)
        for w in weights["layers"]:
            x = x + attention(_norm(x, w["ln1_g"], eps=eps),
                              {k: w[k] for k in _ATTN}, **attn)
            h = _norm(x, w["ln2_g"], eps=eps)
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
            x = x + y
        out = _head(x, weights["lnf_g"], weights["lm_head"], eps=eps)
        if undecided:
            excused = nearest < undecided
            print(f"reference: glm5: {int(excused.sum())} of "
                  f"{excused.shape[0]} positions excused as undecided "
                  f"(a held expert within {undecided} of the chosen set's "
                  "edge in some layer)", flush=True)
            out = jnp.where(excused[:, None], 0.0, out)
        return out
