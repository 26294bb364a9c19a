"""K-EXAONE (``models/decoder.py`` with per-layer kinds, ``models/moe.py``
holding one rank's share): the dense decoder's attention half plus ``q_norm``
and ``k_norm`` scales of one HEAD's width; layer 0 a dense SwiGLU
(``mlp_gate``/``mlp_up``/``mlp_down``); under the other layers' ``moe`` a
router ``[D, E]`` over all E experts, its ``selection_bias`` ``[E]``, three
stacks for the HELD experts (``wi`` up and ``wg`` gate ``[held, D, F]``, ``wo``
down ``[held, F, D]``) and the shared expert's three kernels."""

import math


def seeding(names, shape):
    """An expert stack contracts ONE expert's input width (its axis 1), not
    all but its last axis as a dense kernel does; the router its first. The
    q and k norms' scales are drawn around one (std 0.1), so that a scale the
    arithmetic drops, or applies over the whole projection, shows in the
    margin. The selection bias is drawn at std 0.02: sigmoid scores near the
    eighth best of 128 lie about 0.016 apart, so leaving the bias out (or
    adding it to the gates) changes one chosen expert in seven, while the
    experts' popularity stays near even, as a trained bias is there to make
    it (at 0.1 the favoured experts drew half of all tokens, 8 of the 16
    held experts a row a step, and tokens/s spread by 4.7% over seeds, more
    than the cell may; at 0.02, 12 of 16 and 1.1-1.7%: PERF.md, PR 32). The
    shared expert's kernels take the common table's rule (fan-in over all
    but the last axis)."""
    if names[-2] == "moe" and names[-1] in ("wi", "wg", "wo"):
        return (0.0, 1.0 / math.sqrt(shape[1]))
    if names[-2] == "router" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "moe" and names[-1] == "selection_bias":
        return (0.0, 0.02)
    if names[-2] in ("q_norm", "k_norm") and names[-1] == "scale":
        return (1.0, 0.1)
    return None


def view(params, config):
    """The same arrays under the names ``benchmark/reference/kexaone.py``
    reads; nothing copied or reshaped."""
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        layer = {
            "ln1_g": lp["attn_norm"]["scale"],
            "wq": lp["q"]["kernel"], "wk": lp["k"]["kernel"],
            "wv": lp["v"]["kernel"], "wo": lp["o"]["kernel"],
            "q_norm_g": lp["q_norm"]["scale"],
            "k_norm_g": lp["k_norm"]["scale"],
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
