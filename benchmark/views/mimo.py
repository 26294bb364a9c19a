"""MiMo-V2-Flash (``models/decoder.py`` with state by layer kind,
``models/moe.py`` holding one rank's share): under every layer the attention
half without biases (``q`` ``[D, 64, 192]``; ``k`` ``[D, K, 192]`` and ``v``
``[D, K, 128]`` with K 4 in a full layer and 8 in a window layer; ``o``
``[64, 128, D]``), under a window layer also ``sink`` ``[64]``; layer 0 a
dense SwiGLU (``mlp_gate``/``mlp_up``/``mlp_down``); under the other layers'
``moe`` a router ``[D, E]`` over all E experts, its ``selection_bias``
``[E]`` and three stacks for the HELD experts (``wi`` up and ``wg`` gate
``[held, D, F]``, ``wo`` down ``[held, F, D]``). No shared expert."""

import math


def seeding(names, shape):
    """An expert stack contracts ONE expert's input width (its axis 1), the
    router its first. The selection bias is drawn at std 0.02 as
    ``views/kexaone.py`` draws it and for its reasons (the experts'
    popularity stays near even). A window layer's SINKS are drawn at mean 0
    std 1: a trained sink is of the size of a score (scores here have a
    spread near 1), and at 0 it would weigh as one more key of score 0,
    enough to tell a dropped sink from a kept one but not a sink on the
    wrong kind of layer from none."""
    if names[-2] == "moe" and names[-1] in ("wi", "wg", "wo"):
        return (0.0, 1.0 / math.sqrt(shape[1]))
    if names[-2] == "router" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "moe" and names[-1] == "selection_bias":
        return (0.0, 0.02)
    if names[-1] == "sink":
        return (0.0, 1.0)
    return None


def view(params, config):
    """The same arrays under the names ``benchmark/reference/mimo.py``
    reads; nothing copied or reshaped."""
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        layer = {
            "ln1_g": lp["attn_norm"]["scale"],
            "wq": lp["q"]["kernel"], "wk": lp["k"]["kernel"],
            "wv": lp["v"]["kernel"], "wo": lp["o"]["kernel"],
            "ln2_g": lp["mlp_norm"]["scale"],
        }
        if "sink" in lp:
            layer["sink"] = lp["sink"]
        if "moe" in lp:
            moe = lp["moe"]
            layer.update({
                "w_router": moe["router"]["kernel"],
                "router_bias": moe["selection_bias"],
                "we_up": moe["wi"], "we_gate": moe["wg"],
                "we_down": moe["wo"],
            })
        else:
            layer.update({"w_gate": lp["mlp_gate"]["kernel"],
                          "w_up": lp["mlp_up"]["kernel"],
                          "w_down": lp["mlp_down"]["kernel"]})
        layers.append(layer)
    return {"wte": p["tok_embed"]["embedding"], "layers": layers,
            "lnf_g": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}
