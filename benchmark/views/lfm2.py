"""LFM2 (``models/decoder.py`` with conv layers, ``models/short_conv.py``,
``models/moe.py`` holding one rank's share): every layer an operator norm
(``attn_norm``) and an FFN norm (``mlp_norm``); a conv layer ``conv_in``
``[D, 3D]``, ``conv_taps`` ``[K, D]`` and ``conv_out`` ``[D, D]``; an
attention layer the dense decoder's attention half plus ``q_norm`` and
``k_norm`` scales of one HEAD's width; the first layers a dense SwiGLU
(``mlp_gate``/``mlp_up``/``mlp_down``); under the other layers' ``moe`` a
router ``[D, E]`` over all E experts, its ``selection_bias`` ``[E]`` and
three stacks for the HELD experts (``wi`` up and ``wg`` gate ``[held, D,
F]``, ``wo`` down ``[held, F, D]``). The head is the embedding (tied)."""

import math

# From this layer on (the file's ``num_dense_layers``: the two dense layers
# lay the stream down) every OUTPUT projection is drawn at this share of its
# fan-in rule; the q/k norms' gains around this mean. See ``seeding``.
LATER_FROM = 2
LATER_WRITES = 0.25
QK_GAIN = 1.6
_OUT = ("conv_out", "o")


def _layer(names):
    return next((int(n[5:]) for n in names
                 if n.startswith("layer") and n[5:].isdigit()), -1)


def seeding(names, shape):
    """An expert stack contracts ONE expert's input width (its axis 1), not
    all but its last axis as a dense kernel does; the router its first. The
    conv taps are drawn at std 3^-1/2 (``K``^-1/2), so that ``c`` has ``z``'s
    size and a tap the arithmetic drops, or takes in the other order, shows.
    The selection bias is drawn at std 0.02 (K-EXAONE's reading: enough to
    change one chosen expert in several, while the experts' popularity stays
    near even). Every norm's gain is drawn with std 0.1, so that a gain the
    arithmetic drops, or applies over the whole projection, shows in the
    margin: around one, but the q/k norms' around ``QK_GAIN``.

    Two draws are this model's own, each read on the chip at the published
    widths (PERF.md, PR 50; 784 rows a reading):

    - The 38 sparse layers' OUTPUT projections (``conv_out``, the attention
      layers' ``o``, the experts' ``wo``) at ``LATER_WRITES`` of their
      fan-in rule; layers 0 and 1 (conv mixers over the dense SwiGLUs) at
      the rule itself. At the rule on all 40, layer l reads a stream that
      holds l writes of its own size (the tied embedding row is a
      thousandth of one), a conv mixer is a product of THREE projections of
      it, and what bfloat16 rounds away early grows through the layers
      after: served logits lay 0.18 rms off the float32 reference's
      (kernels on or off, chip or CPU alike; 0.02 with every conv layer an
      attention layer), where a comparison of margins at 0.15 holds
      nothing. With the later 38 writing a quarter each they add up to
      about the base's own size: 0.03 rms.
    - The q/k gains around 1.6: scores of std ~2.6, a query attends a
      handful of keys. Around 1 it averages hundreds, the attention layers
      write a hundredth of what a conv mixer does, and a reference WITHOUT
      them serves tokens beyond the tolerance on 0.0-0.5% of rows: the
      comparison was blind to the paged kernel's reads. At 1.6: 14-20%."""
    later = LATER_WRITES if _layer(names) >= LATER_FROM else 1.0
    if names[-2] == "moe" and names[-1] in ("wi", "wg"):
        return (0.0, 1.0 / math.sqrt(shape[1]))
    if names[-2] == "moe" and names[-1] == "wo":
        return (0.0, later / math.sqrt(shape[1]))
    if names[-2] in _OUT and names[-1] == "kernel":
        return (0.0, later / math.sqrt(math.prod(shape[:-1])))
    if names[-2] == "router" and names[-1] == "kernel":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-2] == "moe" and names[-1] == "selection_bias":
        return (0.0, 0.02)
    if names[-1] == "conv_taps":
        return (0.0, 1.0 / math.sqrt(shape[0]))
    if names[-1] == "scale":
        return (QK_GAIN if names[-2] in ("q_norm", "k_norm") else 1.0, 0.1)
    return None


def view(params, config):
    """The same arrays under the names ``benchmark/reference/lfm2.py``
    reads; nothing copied or reshaped."""
    p = params["params"]
    layers = []
    for i in range(int(config["program"]["decoder_config"]["num_layers"])):
        lp = p[f"layer{i}"]
        layer = {"op_norm_g": lp["attn_norm"]["scale"],
                 "ffn_norm_g": lp["mlp_norm"]["scale"]}
        if "conv_taps" in lp:
            layer.update({"w_in": lp["conv_in"]["kernel"],
                          "taps": lp["conv_taps"],
                          "w_out": lp["conv_out"]["kernel"]})
        else:
            layer.update({
                "wq": lp["q"]["kernel"], "wk": lp["k"]["kernel"],
                "wv": lp["v"]["kernel"], "wo": lp["o"]["kernel"],
                "q_norm_g": lp["q_norm"]["scale"],
                "k_norm_g": lp["k_norm"]["scale"]})
        if "moe" in lp:
            moe = lp["moe"]
            layer.update({
                "w_router": moe["router"]["kernel"],
                "router_bias": moe["selection_bias"],
                "we_up": moe["wi"], "we_gate": moe["wg"],
                "we_down": moe["wo"]})
        else:
            layer.update({"w_gate": lp["mlp_gate"]["kernel"],
                          "w_up": lp["mlp_up"]["kernel"],
                          "w_down": lp["mlp_down"]["kernel"]})
        layers.append(layer)
    return {"wte": p["tok_embed"]["embedding"], "layers": layers,
            "lnf_g": p["final_norm"]["scale"]}
