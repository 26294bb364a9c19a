"""Bytes and operations the LATENT decode scan NEEDS: a model whose cache
holds ONE row a position a layer (``[c_kv | k_r]``: ``kv_lora_rank +
qk_rope_head_dim`` values), key and value at once, read by the absorbed
decode step. Kept here, beside ``kernel_bytes.py``, so that no PR which
claims a gain can change it; imports nothing from the program, and knows
nothing of how the kernel is implemented or how its pool pads a row.

``kernel_bytes.paged_decode_scan_bytes`` and ``kv_kind_counts`` count k/v
pairs of heads; this model has neither a pair nor a head axis."""

from __future__ import annotations


def latent_row_bytes(config: dict, itemsize: int = 2) -> int:
    """A position's TRUE bytes in one layer, from the configuration file's
    published keys: ``(kv_lora_rank + qk_rope_head_dim) x itemsize`` (576 x
    2 = 1,152 B), not the lanes a pool row holds."""
    return (int(config["kv_lora_rank"])
            + int(config["qk_rope_head_dim"])) * itemsize


def latent_scan_bytes(resident_tokens: int, config: dict,
                      itemsize: int = 2) -> int:
    """Bytes the decode scans of all served layers must read to produce one
    token for one stream whose cache holds ``resident_tokens`` positions:
    every resident row ONCE a layer (it is key and value both). Whole
    positions, not whole pages, and no lane padding: what a page holds past
    the length and what a row holds beside its 576 values are the kernel's
    cost, not the model's need, so a share computed from this can only come
    out low."""
    return (int(resident_tokens) * int(config["num_hidden_layers"])
            * latent_row_bytes(config, itemsize))


def latent_scan_flops(resident_tokens: int, config: dict) -> int:
    """Multiply-adds x 2 of the same scans, absorbed: every head scores the
    row over all its ``kv_lora_rank + qk_rope_head_dim`` values and sums its
    first ``kv_lora_rank`` as the value."""
    rank, rope = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    return (2 * int(resident_tokens) * int(config["num_hidden_layers"])
            * int(config["num_attention_heads"]) * (rank + rope + rank))
