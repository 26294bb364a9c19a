"""GLM-5 (``models/decoder.py`` with latent attention under an indexer's
selection, ``models/latent.py``; ``models/moe.py`` holding one rank's share).
Under every layer the attention half without biases (``q_down`` ``[D,
2048]``, ``q_norm``, ``q_up`` ``[2048, 64, 256]``, ``kv_down`` ``[D, 576]``,
``kv_norm`` ``[512]``, ``kv_up`` ``[512, 64, 448]`` = ``[k_n | v]``, ``o``
``[64, 256, D]``) and the indexer (``index_q`` ``[2048, 32, 128]`` from the q
latent, ``index_k`` ``[D, 128]``, ``index_k_norm`` scale and bias ``[128]``,
``index_w`` ``[D, 32]``); layer 0 a dense SwiGLU
(``mlp_gate``/``mlp_up``/``mlp_down``); under the other layers' ``moe`` a
router ``[D, E]`` over all E experts, its ``selection_bias`` ``[E]``, three
stacks for the HELD experts and the shared expert's three kernels."""

import math


def seeding(names, shape):
    """The two up-projections contract their first axis alone (the rank):
    a score is then a sum of 256 products over 16 (no YaRN: ``rope_type``
    default). The expert stacks contract ONE expert's input width, the
    router its first; the low-rank norms' scales and the selection bias as
    ``views/kexaone.py`` and ``views/xing.py`` draw them and for their
    reasons. What lets the SELECTION show in the logits (PERF.md, PR 57: a
    narrower proxy of this model on the CPU, 4,992 positions keeping 2,048,
    and the chip's own witnesses): token embeddings at std ONE (the stream
    keeps its token's own direction, as Keye's) and the q latent's norm
    scale around 1.5 (std 0.15: scores with a spread near 1.5, a query's
    weight on some two hundred of its 2,048 rows), and the attention's
    OUTPUT projection at PLAIN fan-in, the common table's rule. At Keye's
    four times fan-in this model's stream COLLAPSES: whatever all positions
    share passes through a softmax's average whole while what is a
    position's own is averaged down by sqrt(n_eff), so with a gain of 4 a
    layer the shared part grows 0.07 -> 0.29 -> 0.99 -> 2.6 -> 3.3 over the
    five layers, the logits become ONE vector (its spread 0.92 against 0.40
    for everything a position owns), 2,892 positions name 102 distinct
    tokens, greedy decoding falls into a cycle of one to three of them, and
    there a served path with the selection OFF, or with the index keys
    unrotated, still names the reference's token (read on the chip: worst
    margins 0.086 and 0.022 against a tolerance of 0.15). At fan-in the
    shared part stays under a tenth, the wrong selections move the logits
    by a root mean square 0.16-0.19 (worst of 32 positions 0.29-0.69) and
    the bfloat16 path by 0.03 (worst of ~2,200 positions 0.09-0.12); at 1.25
    and 1.5 times fan-in both grow and the bfloat16 path crosses 0.15 at
    one position in two thousand, at a norm scale of 2 and above it crosses
    at one in ten. The indexer's three projections at fan-in (``index_q``
    contracts the q latent's rank alone), so a position's score is a sum of
    32 rectified dots of unit scale with weights of either sign; its key
    norm's scale around one (std 0.1) and its bias by the common table (std
    0.02), so that a norm the arithmetic drops shows."""
    if names[-2] in ("kv_up", "q_up", "index_q") and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "q_norm" and names[-1] == "scale":
        return (1.5, 0.15)
    if names[-2] in ("kv_norm", "index_k_norm") and names[-1] == "scale":
        return (1.0, 0.1)
    if names[-2] == "tok_embed" and names[-1] == "embedding":
        return (0.0, 1.0)
    if names[-2] == "moe" and names[-1] in ("wi", "wg", "wo"):
        return (0.0, 1.0 / math.sqrt(shape[1]))
    if names[-2] == "router" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "moe" and names[-1] == "selection_bias":
        return (0.0, 0.02)
    return None


def view(params, config):
    """The same arrays under the names ``benchmark/reference/glm5.py``
    reads; nothing copied or reshaped."""
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        layer = {
            "ln1_g": lp["attn_norm"]["scale"],
            "w_dq": lp["q_down"]["kernel"],
            "q_norm_g": lp["q_norm"]["scale"],
            "w_uq": lp["q_up"]["kernel"],
            "w_dkv": lp["kv_down"]["kernel"],
            "kv_norm_g": lp["kv_norm"]["scale"],
            "w_ukv": lp["kv_up"]["kernel"],
            "wo": lp["o"]["kernel"],
            "wq_index": lp["index_q"]["kernel"],
            "wk_index": lp["index_k"]["kernel"],
            "k_index_norm_g": lp["index_k_norm"]["scale"],
            "k_index_norm_b": lp["index_k_norm"]["bias"],
            "ww_index": lp["index_w"]["kernel"],
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
