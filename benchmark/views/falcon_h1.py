"""Falcon-H1 (``models/decoder.py`` with hybrid layers, ``models/ssm.py``):
every layer an input norm (``attn_norm``) and a pre-FFN norm (``mlp_norm``);
its state-space mixer ``ssm_in`` ``[D, d_ssm + W + H]``, ``conv_taps`` ``[K,
W]``, ``conv_bias`` ``[W]``, ``ssm_A_log`` / ``ssm_D`` / ``ssm_dt_bias``
``[H]``, ``ssm_norm_scale`` ``[d_ssm]`` and ``ssm_out`` ``[d_ssm, D]``; the
dense decoder's attention half (``q``, ``k``, ``v``, ``o``); a dense SwiGLU
(``mlp_gate`` / ``mlp_up`` / ``mlp_down``); an untied head (``lm_head``).

The seeding rules are this file's own; each is stated with the reading that
justifies it under the configuration file's ``assumed.weights``."""

import math

# Falcon-H1-34B-Instruct's published multipliers, by the kernel each one
# follows: the kernel is drawn at its fan-in rule DIVIDED by the multiplier,
# so that the product has unit size AFTER it (see ``seeding``).
_AFTER = {
    "tok_embed": 5.656854249492381,            # embedding_multiplier
    "lm_head": 0.0078125,                      # lm_head_multiplier
    "k": 0.011048543456039804,                 # key_multiplier
    "o": 0.0375,                               # attention_out_multiplier
    "ssm_out": 0.08838834764831845,            # ssm_out_multiplier
    "mlp_gate": 0.1767766952966369,            # mlp_multipliers[0]
    "mlp_down": 0.011160714285714284,          # mlp_multipliers[1]
}
_SSM_IN = 0.25                                 # ssm_in_multiplier
# ssm_multipliers, over the zones [z | x | B | C | dt] of ssm_in's columns
_ZONES = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
          0.3535533905932738)
DELTA = (1e-3, 1e-1)        # the step softplus(dt_bias), drawn log-uniform
A_RANGE = (1.0, 16.0)       # -A = exp(A_log), drawn uniform


def seeding(names, shape):
    """``(mean, std)`` of a leaf, or None for the common table.

    - EVERY KERNEL THAT A MULTIPLIER FOLLOWS at its fan-in rule divided by
      that multiplier: a trained Falcon-H1's multipliers are of its
      parametrisation (a kernel's learned size times the multiplier is what
      the layer sees), while a fan-in draw TIMES ``key_multiplier`` 0.011
      gives scores near 0, a flat softmax, and a comparison blind to the
      paged kernel's reads (what LFM2's q/k gains hit at PR 50); times
      ``lm_head_multiplier`` 1/128, logits of spread 0.008 under a margin
      of 0.15. ``ssm_in`` has ONE std for its 9,248 columns (a draw is one
      normal block): fan-in over ``ssm_in_multiplier`` and the geometric
      mean of the five zone multipliers, so the zones come out within 1.7x
      of unit size (the zones' own ratios are the model's, kept).
    - ``ssm_A_log`` and ``ssm_dt_bias`` cannot be normal draws (one ``(mean,
      std)`` a leaf is all this table gives): ``A_log`` normal around the
      log of the geometric middle of ``A_RANGE`` with the spread of a
      log-uniform over it, ``dt_bias`` likewise around the inverse softplus
      of ``DELTA``'s middle: decays ``exp(d A)`` a token between 0.999 and
      0.2, memories of one to a thousand positions, as the family's
      ``A_log = log U[1, 16]`` and log-uniform ``d`` in [1e-3, 1e-1] give
      (``models/ssm.py``'s initialisers draw those exactly; a standard
      normal ``A_log`` gives states that vanish or explode).
    - ``ssm_D`` around 1 (the family's ones) with std 0.1, the norms' gains
      likewise, so that a gain or a skip the arithmetic drops shows.
    - the conv's taps at ``K``^-1/2 (so that the conv's output has its
      input's size) and its bias at 0.1."""
    leaf, parent = names[-1], names[-2]
    if leaf == "kernel" and parent == "ssm_in":
        zones = math.exp(sum(math.log(m) for m in _ZONES) / len(_ZONES))
        return (0.0, 1.0 / (math.sqrt(shape[0]) * _SSM_IN * zones))
    if leaf == "kernel" and parent in _AFTER:
        fan_in = shape[0] if parent == "k" else math.prod(shape[:-1])
        return (0.0, 1.0 / (math.sqrt(fan_in) * _AFTER[parent]))
    if leaf == "embedding":
        return (0.0, 1.0 / (math.sqrt(shape[-1]) * _AFTER["tok_embed"]))
    if leaf == "conv_taps":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if leaf == "conv_bias":
        return (0.0, 0.1)
    if leaf == "ssm_A_log":
        lo, hi = (math.log(a) for a in A_RANGE)
        return ((lo + hi) / 2, (hi - lo) / math.sqrt(12.0))
    if leaf == "ssm_dt_bias":
        lo, hi = (math.log(d) for d in DELTA)
        mid = math.exp((lo + hi) / 2)
        # softplus^-1(d) ~ log(d) for a small d: the log's spread
        return (mid + math.log(-math.expm1(-mid)), (hi - lo) / math.sqrt(12.0))
    if leaf in ("ssm_D", "ssm_norm_scale", "scale"):
        return (1.0, 0.1)
    return None


def view(params, config):
    """The same arrays under the names ``benchmark/reference/falcon_h1.py``
    reads; nothing copied or reshaped."""
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        layers.append({
            "in_norm_g": lp["attn_norm"]["scale"],
            "ff_norm_g": lp["mlp_norm"]["scale"],
            "ssm_in": lp["ssm_in"]["kernel"],
            "conv_taps": lp["conv_taps"], "conv_bias": lp["conv_bias"],
            "A_log": lp["ssm_A_log"], "D": lp["ssm_D"],
            "dt_bias": lp["ssm_dt_bias"],
            "ssm_norm_g": lp["ssm_norm_scale"],
            "ssm_out": lp["ssm_out"]["kernel"],
            "wq": lp["q"]["kernel"], "wk": lp["k"]["kernel"],
            "wv": lp["v"]["kernel"], "wo": lp["o"]["kernel"],
            "w_gate": lp["mlp_gate"]["kernel"],
            "w_up": lp["mlp_up"]["kernel"],
            "w_down": lp["mlp_down"]["kernel"]})
    return {"wte": p["tok_embed"]["embedding"], "layers": layers,
            "lnf_g": p["final_norm"]["scale"],
            "w_head": p["lm_head"]["kernel"]}
