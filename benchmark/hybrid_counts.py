"""Bytes the paged decode scan NEEDS where only SOME layers hold pages: a
model whose other layers mix their sequence with a short convolution and
keep a fixed-size state a slot instead (``layer_types``: ``"conv"`` beside
``"full_attention"``). Kept here, beside ``kernel_bytes.py``, so that no PR
which claims a gain can change it; imports nothing from the program.

``readers/paged_decode_roofline.py`` counts ``decoder_config.num_layers``
layers of KV a position; here 10 of 40 hold any, and a share computed over
all 40 would read four times too high."""

from __future__ import annotations

from typing import List

from benchmark.kernel_bytes import paged_decode_scan_bytes


def page_layers(config: dict) -> List[int]:
    """The served layers that hold pages (attention layers), by index, from
    the configuration file's published ``layer_types``, as deep as its
    ``num_hidden_layers``."""
    kinds = config["layer_types"][:int(config["num_hidden_layers"])]
    return [i for i, k in enumerate(kinds) if k != "conv"]


def conv_layers(config: dict) -> List[int]:
    """The served layers that keep a conv state a slot, by index."""
    kinds = config["layer_types"][:int(config["num_hidden_layers"])]
    return [i for i, k in enumerate(kinds) if k == "conv"]


def head_dim(config: dict) -> int:
    """A head's width: the file's own ``head_dim`` where it gives one, else
    hidden / heads."""
    return int(config.get("head_dim") or (
        int(config["hidden_size"]) // int(config["num_attention_heads"])))


def hybrid_scan_bytes(resident_tokens: int, config: dict,
                      kv_itemsize: int = 2) -> int:
    """Bytes the decode KV scan must read to produce one token for one
    stream whose cache holds ``resident_tokens`` positions: keys and values
    of every resident position in the layers that hold pages, and in those
    alone (``kernel_bytes.paged_decode_scan_bytes`` over them). Whole
    positions at their true width: a share computed from this can only come
    out low."""
    return paged_decode_scan_bytes(
        resident_tokens, len(page_layers(config)),
        int(config["num_key_value_heads"]), head_dim(config), kv_itemsize)


def conv_state_bytes_per_slot(config: dict, itemsize: int = 2) -> int:
    """One slot's conv state, whatever its length: in each conv layer the
    taps' last ``conv_L_cache - 1`` inputs, ``hidden_size`` wide."""
    return (len(conv_layers(config)) * (int(config["conv_L_cache"]) - 1)
            * int(config["hidden_size"]) * itemsize)
