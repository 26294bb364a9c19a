"""Xing4.0 (``models/decoder.py`` with latent attention, ``models/latent.py``,
on a residual path of streams, ``models/hyper_connections.py``;
``models/moe.py`` holding one rank's share). Under every layer: the two
sublayers' maps ``attn_hc`` and ``mlp_hc`` (``kernel`` ``[4 D, 24]`` = ``Phi``,
``a`` ``[3]`` = pre, post, res, ``b_pre`` ``[4]``, ``b_post`` ``[4]``,
``b_res`` ``[4, 4]``); the attention half without biases (``q_down`` ``[D,
768]``, ``q_norm``, ``q_up`` ``[768, 32, 192]``, ``kv_down`` ``[D, 576]``,
``kv_norm`` ``[512]``, ``kv_up`` ``[512, 32, 256]`` = ``[k_n | v]``, ``o``
``[32, 128, D]``); layers 0-1 a dense SwiGLU
(``mlp_gate``/``mlp_up``/``mlp_down``); under the other layers' ``moe`` a
router ``[D, E]`` over all E experts, its ``selection_bias`` ``[E]``, three
stacks for the HELD experts and the shared expert's three kernels."""

import math

# YaRN's factor on the scores, m^2 = (0.1 ln 64 + 1)^2 (rope_scaling.factor
# 64, mscale_all_dim 1): the published model was TRAINED under it; a seeded
# one is not, and drawn at plain fan-in its scores would spread over 2, not 1.
YARN_M2 = (0.1 * math.log(64.0) + 1.0) ** 2


def seeding(names, shape):
    """The published initial values of the maps are not known; these are
    chosen so that each part of a map shows in the logits. ``Phi`` takes the
    common table's rule (fan-in ``4 D``), so the DYNAMIC part of every raw
    map has a spread near 1 (``x~`` has unit RMS); the gains ``a`` are 1;
    the static parts ``b_pre``, ``b_post`` and ``B_res`` are drawn at std 1,
    a score's size, so that the static and the dynamic part weigh alike:
    maps from the static part alone, ``H_post`` without its factor 2, one
    Sinkhorn round for 20 and ``H_res`` transposed each move logits by
    tenths (``tests/test_xing.py``). The two up-projections contract their
    first axis alone (the rank); q's is drawn ``YARN_M2`` times narrower, so
    that a score's spread AFTER YaRN's factor is the 1 that a model of unit
    q and k has without it (drawn at plain fan-in the softmax is twice as
    sharp as any trained model's, the function twice as sensitive to its
    input, and a bfloat16 served path lands 0.2-0.35 from a float32 reference
    where the other models land 0.03: ``PERF.md``, PR 46; leaving ``m^2``
    out still halves every score). The expert stacks contract ONE expert's
    input width,
    the router its first; the low-rank norms' scales are drawn around one
    (std 0.1) and the selection bias at std 0.02, as
    ``views/kexaone.py`` draws them and for its reasons."""
    if names[-2] in ("attn_hc", "mlp_hc"):
        if names[-1] == "a":
            return (1.0, 0.0)
        if names[-1] in ("b_pre", "b_post", "b_res"):
            return (0.0, 1.0)
        return None                       # Phi: the table's kernel rule
    if names[-2] == "kv_up" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "q_up" and names[-1] == "kernel":
        return (0.0, 1.0 / (YARN_M2 * math.sqrt(shape[0])))
    if names[-2] in ("q_norm", "kv_norm") and names[-1] == "scale":
        return (1.0, 0.1)
    if names[-2] == "moe" and names[-1] in ("wi", "wg", "wo"):
        return (0.0, 1.0 / math.sqrt(shape[1]))
    if names[-2] == "router" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "moe" and names[-1] == "selection_bias":
        return (0.0, 0.02)
    return None


def _maps(p):
    return {"phi": p["kernel"], "a": p["a"], "b_pre": p["b_pre"],
            "b_post": p["b_post"], "b_res": p["b_res"]}


def view(params, config):
    """The same arrays under the names ``benchmark/reference/xing.py``
    reads; nothing copied or reshaped."""
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        layer = {
            "hc_attn": _maps(lp["attn_hc"]), "hc_mlp": _maps(lp["mlp_hc"]),
            "ln1_g": lp["attn_norm"]["scale"],
            "w_dq": lp["q_down"]["kernel"],
            "q_norm_g": lp["q_norm"]["scale"],
            "w_uq": lp["q_up"]["kernel"],
            "w_dkv": lp["kv_down"]["kernel"],
            "kv_norm_g": lp["kv_norm"]["scale"],
            "w_ukv": lp["kv_up"]["kernel"],
            "wo": lp["o"]["kernel"],
            "ln2_g": lp["mlp_norm"]["scale"],
        }
        if "moe" in lp:
            moe = lp["moe"]
            layer.update({
                "w_router": moe["router"]["kernel"],
                "router_bias": moe["selection_bias"],
                "we_up": moe["wi"], "we_gate": moe["wg"],
                "we_down": moe["wo"],
                "ws_gate": moe["shared_gate"]["kernel"],
                "ws_up": moe["shared_up"]["kernel"],
                "ws_down": moe["shared_down"]["kernel"],
            })
        else:
            layer.update({"w_gate": lp["mlp_gate"]["kernel"],
                          "w_up": lp["mlp_up"]["kernel"],
                          "w_down": lp["mlp_down"]["kernel"]})
        layers.append(layer)
    return {"wte": p["tok_embed"]["embedding"], "layers": layers,
            "lnf_g": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}
