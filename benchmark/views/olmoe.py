"""OLMoE (``models/decoder.py`` with ``qk_norm`` and a sparse-expert MLP,
``models/moe.py``): the dense decoder's attention half plus ``q_norm`` and
``k_norm`` scales over the whole projection, and under ``layer{i}/moe`` a
router ``[D, E]`` and three expert stacks (``wi`` up and ``wg`` gate
``[E, D, F]``, ``wo`` down ``[E, F, D]``)."""

import math


def seeding(names, shape):
    """An expert stack contracts ONE expert's input width (its axis 1), not
    all but its last axis as a dense kernel does; the router its first. The
    q and k norms' scales are drawn around one (std 0.1), so that a scale
    the arithmetic drops, or applies per head, shows in the margin (the
    common table's ones would hide it)."""
    if names[-2] == "moe" and names[-1] in ("wi", "wg", "wo"):
        return (0.0, 1.0 / math.sqrt(shape[1]))
    if names[-2] == "router" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] in ("q_norm", "k_norm") and names[-1] == "scale":
        return (1.0, 0.1)
    return None


def view(params, config):
    """The same arrays under the names ``benchmark/reference/olmoe.py``
    reads; nothing copied or reshaped."""
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        layers.append({
            "ln1_g": lp["attn_norm"]["scale"],
            "wq": lp["q"]["kernel"], "wk": lp["k"]["kernel"],
            "wv": lp["v"]["kernel"], "wo": lp["o"]["kernel"],
            "q_norm_g": lp["q_norm"]["scale"],
            "k_norm_g": lp["k_norm"]["scale"],
            "ln2_g": lp["mlp_norm"]["scale"],
            "w_router": lp["moe"]["router"]["kernel"],
            "we_up": lp["moe"]["wi"], "we_gate": lp["moe"]["wg"],
            "we_down": lp["moe"]["wo"],
        })
    return {"wte": p["tok_embed"]["embedding"], "layers": layers,
            "lnf_g": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}
