"""Keye-VL-2.0's language model (``models/decoder.py`` with an indexer,
``models/moe.py`` holding one rank's share): the dense decoder's attention
half plus ``q_norm`` and ``k_norm`` scales of one HEAD's width and the
indexer's three projections (``index_q`` ``[D, n, Hi]``, ``index_k``
``[D, 1, Hi]``, ``index_w`` ``[D, n]``); under ``moe`` a router ``[D, E]`` over
all E experts and three stacks for the HELD experts (``wi`` up and ``wg`` gate
``[held, D, F]``, ``wo`` down ``[held, F, D]``)."""

import math


def seeding(names, shape):
    """An expert stack contracts ONE expert's input width (its axis 1), the
    router its first, as ``views/kexaone.py``. Three draws are this model's
    own (PERF.md, PR 35: every reading below is of served LOGITS against the
    reference at the published widths, 5,016 positions keeping 2,048,
    ``tools/moe_logits_check.py``). With the common table's embeddings (std
    1 / sqrt(width) = 0.022) and q and k scales near one, 5,000 near-uniform
    attention weights average the values into ONE vector whatever is
    selected, as large as the embedding itself: the stream collapses onto
    two tokens and dense attention in the selection's place reads the same.
    So token embeddings are drawn at std ONE (the stream keeps its token's
    own direction, as a trained model's does), the q norm's scale around 1.5
    (std 0.15; the k norm's around one, std 0.1: attention logits with a
    spread near 1.5, a query's weight on some hundreds of positions) and the
    attention's OUTPUT projection at four times fan-in, so that what is
    attended is a real share of the stream: the served path (bfloat16) then
    lies a root mean square 0.009 from the reference and dense attention
    0.069, eight times as far (at fan-in 0.023 and 0.099, and one served
    token in 5,016 lay BEYOND the harness's margin: a routed expert swapped
    at a near-tie). A q scale of 2 or 3 sharpens both alike: at 3 one
    position holds most of a query's weight and the model is ill-conditioned
    (the program in FLOAT32 still lies 0.017 from the reference, in bfloat16
    0.22, one token in eight beyond the margin). The indexer's query and key
    projections are drawn at fan-in (the common table's rule: all but the
    last axis); its head weights ``index_w`` at fan-in too, so that a
    position's score is a sum of 16 rectified dots of unit scale with
    weights of either sign: scores spread over about +-1, so that of 5,000
    positions neighbours in rank lie far above float32's rounding apart and
    within bfloat16's: which of the rows at the edge a query keeps is not
    decided at bfloat16, and each carries a five-thousandth of its weight."""
    if names[-2] == "moe" and names[-1] in ("wi", "wg", "wo"):
        return (0.0, 1.0 / math.sqrt(shape[1]))
    if names[-2] == "router" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "q_norm" and names[-1] == "scale":
        return (1.5, 0.15)
    if names[-2] == "tok_embed" and names[-1] == "embedding":
        return (0.0, 1.0)
    if names[-2] == "k_norm" and names[-1] == "scale":
        return (1.0, 0.1)
    if names[-2] in ("index_q", "index_k", "index_w"):
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "o" and names[-1] == "kernel":      # [heads, head, D]
        return (0.0, 4.0 / math.sqrt(shape[0] * shape[1]))
    return None


def view(params, config):
    """The same arrays under the names ``benchmark/reference/keye.py``
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
            "wq_index": lp["index_q"]["kernel"],
            "wk_index": lp["index_k"]["kernel"],
            "ww_index": lp["index_w"]["kernel"],
            "ln2_g": lp["mlp_norm"]["scale"],
            "w_router": lp["moe"]["router"]["kernel"],
            "we_up": lp["moe"]["wi"], "we_gate": lp["moe"]["wg"],
            "we_down": lp["moe"]["wo"],
        })
    return {"wte": p["tok_embed"]["embedding"], "layers": layers,
            "lnf_g": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"]}
