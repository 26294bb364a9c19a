"""Bytes the paged decode scan NEEDS where the KV state is held BY LAYER
KIND and the kinds differ in their head count and in the widths of a key and
of a value (``ops/decode_attention.py``'s ``paged_decode_attention`` handed a
full layer's pages or a window layer's ring). Kept here, beside
``kernel_bytes.py``, so that no PR which claims a gain can change it; imports
nothing from the program.

``kernel_bytes.paged_decode_scan_bytes`` takes a head's width as hidden /
heads and ``paged_window_counts`` one head count and one width for every
layer, k and v alike; this model has two head counts and keys wider than
values."""

from __future__ import annotations

from typing import Dict, List, Sequence


def layer_kinds(config: dict) -> List[Dict[str, int]]:
    """Each served layer's kind from the configuration file's published
    keys, as deep as the file's ``num_hidden_layers``: ``window`` (0 for a
    full layer: ``hybrid_layer_pattern`` 0), ``kv_heads``
    (``num_key_value_heads`` / ``swa_num_key_value_heads``) and the widths
    of a key and a value head (``head_dim`` / ``v_head_dim``, and the
    ``swa_`` pair)."""
    kinds = []
    for slides in config["hybrid_layer_pattern"][
            :int(config["num_hidden_layers"])]:
        pre = "swa_" if slides else ""
        kinds.append({
            "window": int(config["sliding_window"]) if slides else 0,
            "kv_heads": int(config[pre + "num_key_value_heads"]),
            "k_dim": int(config[pre + "head_dim"]),
            "v_dim": int(config[pre + "v_head_dim"]),
        })
    return kinds


def kind_scan_bytes(resident_tokens: int, kinds: Sequence[Dict[str, int]],
                    kv_itemsize: int = 2) -> int:
    """Bytes the decode KV scan must read to produce one token for one
    stream whose cache holds ``resident_tokens`` positions: in every layer
    the keys and the values, at their TRUE widths (192 + 128, not the 256 +
    128 lanes a pool row holds), of every position it attends: all resident
    ones in a full layer, the last ``min(resident, window)`` in a window
    layer. Whole positions, not whole pages, and no lane padding: what a
    page holds behind the window and what a row holds beside the head are
    the kernel's cost, not the model's need, so a share computed from this
    can only come out low."""
    resident = int(resident_tokens)
    return sum(
        (min(resident, k["window"]) if k["window"] else resident)
        * k["kv_heads"] * (k["k_dim"] + k["v_dim"]) * kv_itemsize
        for k in kinds)
