"""A Mamba-2 state-space mixer as one of a HYBRID layer's two sequence mixers
(Falcon-H1's; ``DecoderConfig.ssm_state``, letter "H" of ``layer_pattern``).
It runs beside the layer's attention on the same normed rows ``u``, and the
two outputs are summed. Imported by a model with such layers alone.

    p = ((u m_in) W_in) * m          (D -> d_ssm + W + H, no bias; m the
                                      five zone multipliers [z|x|B|C|dt])
    [z | xBC | dt] = p               (W = d_ssm + 2 G N: the conv's width)
    xBC_t = silu(b + sum_j w_j * xBC_{t-K+1+j})      (depthwise, causal)
    [x | B | C] = xBC                x [H, P], B and C [G, N]
    d_t = softplus(dt_t + dt_bias);  A = -exp(A_log)          (a head)
    S_t[h] = exp(d_t[h] A[h]) S_{t-1}[h] + d_t[h] x_t[h] (x) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
    y = RMSNorm_groups(y * silu(z));  s = (y W_out) m_out

What the mixer keeps of a sequence is a MATRIX a head, ``S`` ``[H, P, N]``
(``PagedKVCache.ssm_state`` ``[L, B, H, P, N]``, float32 whatever the
model's dtype: what is rounded into the state stays for the rest of the
sequence), and the conv's last ``K - 1`` inputs (``conv_state`` ``[L, B,
K - 1, W]``): a fixed size a slot, no pages. A chunk of a prompt runs
BLOCKED (:func:`chunk_scan`: blocks of ``ssm_chunk`` positions, the
within-block product, one state a block, the short recurrence over blocks),
starts from the state its slot holds (zeros where the prompt starts) and
leaves the state at its TRUE end: a padded position has ``d = 0``, which
leaves ``S`` as it is and adds nothing. A decode row is one step of the
recurrence on the plane in place; a slot that does not advance keeps its
state bit for bit. Where Pallas is on, that row is ONE kernel
(``ops/ssm_update.py``: a tile of the plane read once, moved on, read out and
written back where it lay); where it is not, or the kernel declines
(``SsmPath.declines`` names why), :func:`decode_update` in plain XLA, as the
chunk's scan is.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_dynamic_batching_tpu.models import short_conv
from ray_dynamic_batching_tpu.ops import attention as attn_ops
from ray_dynamic_batching_tpu.ops import ssm_update
from ray_dynamic_batching_tpu.ops.pallas_common import resolve_interpret

PATH_SSM = "ssm"
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SsmPath(attn_ops.AttentionPath):
    """A state-space mixer's dispatch among
    ``ops.attention.attention_paths()``: ``q_shape`` the rows mixed ``[B, T,
    H, P]``, ``kv_shape`` the states they start from and leave (``[L, B, H,
    P, N]``; ``()`` without a cache); ``kernel``: a decode row went to
    ``ops/ssm_update.py`` (else ``declines`` says why not)."""

    kernel: bool = False

    def describe(self) -> str:
        if not self.kv_shape:
            return "state-space scan in XLA from a zero state"
        if self.q_shape[1] == 1:
            return ("state-space mixer, one step of the recurrence on the "
                    f"{self.kv_dtype} state a slot in place (no pages), "
                    + ("one kernel, the plane in place" if self.kernel
                       else "in XLA"))
        return ("state-space mixer, a blocked scan in XLA from the "
                f"{self.kv_dtype} state a slot (no pages)")


def _record(x: jax.Array, states: Optional[jax.Array], kernel: bool = False,
            declines: Tuple[str, ...] = ()) -> None:
    attn_ops._PATHS.append(SsmPath(
        program=attn_ops.current_program(), path=PATH_SSM,
        gathered=False, stacked=states is not None, tp=1,
        interpret=kernel and resolve_interpret(None),
        q_shape=tuple(x.shape),
        kv_shape=() if states is None else tuple(states.shape),
        kv_dtype=str(x.dtype if states is None else states.dtype),
        declines=tuple(declines), kernel=kernel))


# --- the family's initial values ------------------------------------------------
def a_log_init(key, shape, dtype=F32):
    """``A_log = log U[1, 16]``: decays between ``exp(-d)`` and
    ``exp(-16 d)``. A normal draw gives states that vanish or explode."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=F32, lo: float = 1e-3, hi: float = 1e-1):
    """The inverse softplus of a step drawn log-uniform in ``[lo, hi]``."""
    d = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(lo), np.log(hi)))
    return d + jnp.log(-jnp.expm1(-d))


# --- the two forms of the scan ----------------------------------------------------
def chunk_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array, S0: jax.Array, block: int,
               ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a chunk's rows, BLOCKED. ``x`` ``[b, T, H, P]``,
    ``dt`` ``[b, T, H]`` (0 at a padded position: the state passes it
    unchanged), ``A`` ``[H]`` (negative), ``Bm`` / ``Cm`` ``[b, T, G, N]``,
    ``S0`` ``[b, H, P, N]``; all float32. Returns (``y`` ``[b, T, H, P]``
    without the ``D x`` term, the state after row ``T - 1``).

    Within a block of ``block`` rows, with ``cs`` the running sum of ``dt
    A``: ``y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s +
    exp(cs_t) C_t . S_in``; a block hands ``exp(cs_end) S_in + sum_s
    exp(cs_end - cs_s) dt_s x_s (x) B_s`` to the next: ``T / block`` steps of
    a recurrence over blocks, not ``T`` over rows."""
    b, T, H, P = x.shape
    G, N = Bm.shape[2:]
    Q = min(block, T)
    pad = -T % Q
    if pad:
        # (dt 0: a pad row leaves the state as it is)
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (x, dt, Bm, Cm))
    nb, Hg = (T + pad) // Q, H // G
    blocks = lambda a: a.reshape((b, nb, Q) + a.shape[2:])  # noqa: E731
    dx = blocks(dt[..., None] * x).reshape(b, nb, Q, G, Hg, P)
    Bm, Cm = blocks(Bm), blocks(Cm)
    cs = jnp.cumsum(blocks(dt * A), axis=2)                # [b, nb, Q, H]
    # within a block: row t reads rows s <= t of its own block
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]      # [b, nb, t, s, H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("bcqgn,bcsgn->bcqsg", Cm, Bm)
    weights = scores[..., None] * decay.reshape(b, nb, Q, Q, G, Hg)
    y = jnp.einsum("bcqsgh,bcsghp->bcqghp", weights, dx)
    # a block's own contribution to the state it hands on
    to_end = jnp.exp(cs[:, :, -1:, :] - cs).reshape(b, nb, Q, G, Hg)
    made = jnp.einsum("bcsgn,bcsghp->bcghpn", Bm, to_end[..., None] * dx)
    # the short recurrence over blocks
    through = jnp.exp(cs[:, :, -1, :]).reshape(b, nb, G, Hg)
    S = S0.reshape(b, G, Hg, P, N)
    came_in = []
    for c in range(nb):
        came_in.append(S)
        S = through[:, c, :, :, None, None] * S + made[:, c]
    y = y + jnp.einsum(
        "bcqgn,bcghpn->bcqghp", Cm, jnp.stack(came_in, axis=1)
    ) * jnp.exp(cs).reshape(b, nb, Q, G, Hg)[..., None]
    return (y.reshape(b, nb * Q, H, P)[:, :T], S.reshape(b, H, P, N))


def decode_update(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                  Cm: jax.Array, S: jax.Array, advance: jax.Array,
                  ) -> Tuple[jax.Array, jax.Array]:
    """One row a slot: ``x`` ``[B, H, P]``, ``dt`` ``[B, H]``, ``Bm`` /
    ``Cm`` ``[B, G, N]``, ``S`` ``[B, H, P, N]``. Returns (``y`` ``[B, H,
    P]`` without the ``D x`` term, the state moved on by the row where
    ``advance[b]``, bit for bit as it came in where not)."""
    Hg = x.shape[1] // Bm.shape[1]
    Bh, Ch = (jnp.repeat(a, Hg, axis=1) for a in (Bm, Cm))   # [B, H, N]
    moved = (jnp.exp(dt * A)[:, :, None, None] * S
             + (dt[..., None] * x)[..., None] * Bh[:, :, None, :])
    S = jnp.where(advance.astype(bool)[:, None, None, None], moved, S)
    return (S * Ch[:, :, None, :]).sum(-1), S


# --- the mixer ------------------------------------------------------------------
def mixer(layer: nn.Module, dense: Any, kind: Any, u: jax.Array,
          cache_kv: Optional[Any], state_lens: Optional[jax.Array],
          ) -> Tuple[jax.Array, Optional[Any]]:
    """The state-space mixer of ``layer`` (a ``DecoderLayer`` inside its
    compact call; ``dense`` its kernel factory) on the normed rows ``u``
    ``[B, T, D]``: (``s`` ``[B, T, D]``, the layer's state with this
    mixer's two planes updated, or None without a cache).
    ``cache_kv.ssm_state`` / ``conv_state`` are the rows' states (this
    layer's is ``kind.pool_layer``); ``state_lens`` ``[B]`` the real tokens
    of each row (a decode row's 1 or 0). Without a cache the sequence starts
    at its first row: the states are zeros and nothing is kept."""
    cfg = layer.cfg
    K, H, P = cfg.conv_kernel, cfg.ssm_heads, cfg.ssm_head_dim
    N, G, d_ssm, W = cfg.ssm_state, cfg.ssm_groups, cfg.d_ssm, cfg.conv_width
    B, T, _ = u.shape
    w = layer.param("conv_taps", nn.initializers.normal(K ** -0.5),
                    (K, W), F32)
    bias = (layer.param("conv_bias", nn.initializers.zeros, (W,), F32)
            if cfg.conv_bias else None)
    A = -jnp.exp(layer.param("ssm_A_log", a_log_init, (H,), F32).astype(F32))
    D = layer.param("ssm_D", nn.initializers.ones, (H,), F32).astype(F32)
    dt_bias = layer.param("ssm_dt_bias", dt_bias_init, (H,), F32).astype(F32)
    gain = layer.param("ssm_norm_scale", nn.initializers.ones, (d_ssm,), F32)
    if cache_kv is not None and state_lens is None:
        raise ValueError("a state-space mixer over a cache needs its rows' "
                         "real lengths (state_lens)")
    li = kind.pool_layer

    with jax.named_scope("ssm_in_proj"):
        if cfg.ssm_in_multiplier != 1.0:
            u = u * cfg.ssm_in_multiplier
        p = dense(d_ssm + W + H, "ssm_in")(u)
        if any(m != 1.0 for m in cfg.ssm_multipliers):
            zones = np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                              (d_ssm, d_ssm, G * N, G * N, H))
            p = p * jnp.asarray(zones, p.dtype)
        z, xBC, dt = jnp.split(p, (d_ssm, d_ssm + W), axis=-1)

    with jax.named_scope("ssm_conv"):
        if cache_kv is None:
            c = short_conv.taps(jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0))), w)
        elif T == 1:
            c, conv_new = short_conv.decode_row(
                xBC, cache_kv.conv_state[li], state_lens, w)
        else:
            c, conv_new = short_conv.chunk(
                xBC, cache_kv.conv_state[li], state_lens, w)
        if bias is not None:
            c = c + bias.astype(F32)
        c = nn.silu(c)                                    # [B, T, W] float32
        x = c[..., :d_ssm].reshape(B, T, H, P)
        Bm = c[..., d_ssm:d_ssm + G * N].reshape(B, T, G, N)
        Cm = c[..., d_ssm + G * N:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt.astype(F32) + dt_bias)    # [B, T, H]

    states = None if cache_kv is None else cache_kv.ssm_state
    if states is not None and T == 1:
        with jax.named_scope("ssm_state_update"):
            row = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
            declines = []
            # the kernel takes the plane WHOLE and hands it back with this
            # layer moved on where it lay: the old one is not read again
            out = ssm_update.state_update(*row, states, li, state_lens,
                                          declines)
            _record(x, states, out is not None, declines)
            if out is None:
                y, S = decode_update(*row, states[li], state_lens)
                out = y, states.at[li].set(S.astype(states.dtype))
            y, states = out
            y = y[:, None]
    else:
        _record(x, states)
        with jax.named_scope("ssm_chunk_scan"):
            if states is None:
                S0 = jnp.zeros((B, H, P, N), F32)
            else:
                S0 = states[li].astype(F32)
                # a padded row passes the state on unchanged
                real = jnp.arange(T)[None, :] < state_lens[:, None]
                dt = jnp.where(real[..., None], dt, 0.0)
            y, S = chunk_scan(x, dt, A, Bm, Cm, S0, cfg.ssm_chunk)
            if states is not None:
                states = states.at[li].set(S.astype(states.dtype))
    new_cache = None
    if cache_kv is not None:
        new_cache = cache_kv._replace(
            conv_state=cache_kv.conv_state.at[li].set(conv_new),
            ssm_state=states)

    with jax.named_scope("ssm_gate_norm"):
        y = (y + D[:, None] * x).reshape(B, T, d_ssm)
        y = (y * nn.silu(z.astype(F32))).reshape(B, T, G, d_ssm // G)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
        y = (y.reshape(B, T, d_ssm) * gain.astype(F32)).astype(u.dtype)
    s = dense(cfg.d_model, "ssm_out")(y)
    if cfg.ssm_out_multiplier != 1.0:
        s = s * cfg.ssm_out_multiplier
    return s, new_cache
