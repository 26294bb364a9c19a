"""The dense decoder (``models/decoder.py`` without experts: gpt2-medium,
Mistral): attention and MLP projections, two norms a layer, embeddings, an
optional output head. Every leaf is seeded by the common table."""

from typing import Any, Dict


def view(params: Any, config: Dict[str, Any]) -> Dict[str, Any]:
    """The same arrays under architecture-neutral names: what
    ``benchmark/reference/*`` read. No array is copied or reshaped here
    (a reshaped copy of 7B projections would not fit beside the served
    model): projections keep the program's [D, heads, head] and
    [heads, head, D] layouts and the references flatten them inside their
    jitted layer. This is the one place that knows the program's names."""
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        w = {
            "ln1_g": lp["attn_norm"]["scale"],
            "ln1_b": lp["attn_norm"].get("bias"),
            "wq": lp["q"]["kernel"], "bq": lp["q"].get("bias"),
            "wk": lp["k"]["kernel"], "bk": lp["k"].get("bias"),
            "wv": lp["v"]["kernel"], "bv": lp["v"].get("bias"),
            "wo": lp["o"]["kernel"], "bo": lp["o"].get("bias"),
            "ln2_g": lp["mlp_norm"]["scale"],
            "ln2_b": lp["mlp_norm"].get("bias"),
            "w_up": lp["mlp_up"]["kernel"], "b_up": lp["mlp_up"].get("bias"),
            "w_down": lp["mlp_down"]["kernel"],
            "b_down": lp["mlp_down"].get("bias"),
        }
        if "mlp_gate" in lp:
            w["w_gate"] = lp["mlp_gate"]["kernel"]
        layers.append({k: v for k, v in w.items() if v is not None})
    out = {
        "wte": p["tok_embed"]["embedding"],
        "layers": layers,
        "lnf_g": p["final_norm"]["scale"],
    }
    if "bias" in p["final_norm"]:
        out["lnf_b"] = p["final_norm"]["bias"]
    if "pos_embed" in p:
        out["wpe"] = p["pos_embed"]["embedding"]
    if "lm_head" in p:
        out["lm_head"] = p["lm_head"]["kernel"]
    return out
