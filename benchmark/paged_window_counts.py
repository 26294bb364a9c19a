"""Bytes the paged decode scan NEEDS where layers differ in what they attend
(``ops/decode_attention.py``'s ``paged_decode_attention`` with sliding-window
layers beside full ones). Kept here, beside ``kernel_bytes.py``, so that no
PR which claims a gain can change it; imports nothing from the program.

``kernel_bytes.paged_decode_scan_bytes`` counts every layer at the full
resident length; a sliding layer has to read its window only, however long
the stream."""

from __future__ import annotations

from typing import Sequence


def paged_window_scan_bytes(resident_tokens: int,
                            layer_windows: Sequence[int],
                            num_kv_heads: int, head_dim: int,
                            kv_itemsize: int = 2) -> int:
    """Bytes the decode KV scan must read to produce one token for one
    stream whose cache holds ``resident_tokens`` positions: keys and values
    (x2) of every position each layer attends: all resident ones in a full
    layer (``layer_windows[l]`` 0), the last ``min(resident,
    layer_windows[l])`` in a sliding one. Whole positions, not whole pages:
    what a page holds behind the window is the kernel's cost, not the
    model's need, so a share computed from this can only come out low."""
    resident = int(resident_tokens)
    positions = sum(min(resident, int(w)) if w else resident
                    for w in layer_windows)
    return positions * 2 * num_kv_heads * head_dim * kv_itemsize


def layer_windows(config: dict) -> list:
    """Each served layer's window from the configuration file's published
    keys (``layer_types``, ``sliding_window``), as deep as the file's
    ``num_hidden_layers``; 0 for a full layer."""
    kinds = config["layer_types"][:int(config["num_hidden_layers"])]
    return [int(config["sliding_window"]) if k == "sliding_attention" else 0
            for k in kinds]
