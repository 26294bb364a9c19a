"""Tests only: the decoder with a sparse-expert MLP (``models/moe.py``): the
dense decoder's attention half, and under ``layer{i}/moe`` a router
``[D, E]`` and three expert stacks (``wi`` up and ``wg`` gate ``[E, D, F]``,
``wo`` down ``[E, F, D]``). What the next ``model_config`` PR writes at
OLMoE's widths."""

import math


def seeding(names, shape):
    """An expert stack contracts ONE expert's input width (its axis 1), not
    all but its last axis as a dense kernel does; the router its first."""
    if names[-2] == "moe" and names[-1] in ("wi", "wg", "wo"):
        return (0.0, 1.0 / math.sqrt(shape[1]))
    if names[-2] == "router" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    return None


def view(params, config):
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        layers.append({
            "ln1_g": lp["attn_norm"]["scale"],
            "wq": lp["q"]["kernel"], "wk": lp["k"]["kernel"],
            "wv": lp["v"]["kernel"], "wo": lp["o"]["kernel"],
            "ln2_g": lp["mlp_norm"]["scale"],
            "w_router": lp["moe"]["router"]["kernel"],
            "we_up": lp["moe"]["wi"], "we_gate": lp["moe"]["wg"],
            "we_down": lp["moe"]["wo"],
        })
    return {"wte": p["tok_embed"]["embedding"], "layers": layers,
            "lnf_g": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}
