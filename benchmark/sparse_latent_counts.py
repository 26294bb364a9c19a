"""Bytes and operations the decode attention of a SELECTING LATENT layer
NEEDS: a model whose cache holds ONE row a position a layer (``[c_kv | k_r]``:
``kv_lora_rank + qk_rope_head_dim`` values, key and value at once) and ONE
index key beside it (``index_head_dim`` values), and whose query attends the
``index_topk`` positions its indexer scores highest, all of them while there
are no more. Kept here, beside ``kernel_bytes.py`` and ``latent_counts.py``,
so that no PR which claims a gain can change it; imports nothing from the
program, and knows nothing of the form that implements the read (a walk of
every live page under a mask, or a gather of the selected rows): the count
is the same, so a form that reads more reads LOW and none can pass 100.

The ATTENTION needs the selected rows; the SELECTION needs every resident
index key. The first is :func:`sparse_latent_scan_bytes`, what the decode
kernel's time is held against; the second is :func:`index_key_scan_bytes`,
printed beside it (the keys are read by the scoring operations, not by the
kernel: added to the kernel's roofline they would let a kernel that reads
the selected rows alone at the copy's rate pass 100)."""

from __future__ import annotations

from benchmark.latent_counts import latent_row_bytes  # 576 x 2 = 1,152 B


def selected_positions(resident_tokens: int, config: dict) -> int:
    """Positions a query with ``resident_tokens`` cached positions (its own
    among them) attends in one layer: ``min(resident, index_topk)``."""
    return min(int(resident_tokens), int(config["index_topk"]))


def sparse_latent_scan_bytes(resident_tokens: int, config: dict,
                             itemsize: int = 2) -> int:
    """Bytes the decode attention of all served layers must read to produce
    one token for one stream: the selected positions' rows, ONCE a layer."""
    return (selected_positions(resident_tokens, config)
            * int(config["num_hidden_layers"])
            * latent_row_bytes(config, itemsize))


def index_key_scan_bytes(resident_tokens: int, config: dict,
                         itemsize: int = 2) -> int:
    """Bytes the SELECTION of the same layers must read: every resident
    position's one index key (128 x 2 = 256 B)."""
    return (int(resident_tokens) * int(config["num_hidden_layers"])
            * int(config["index_head_dim"]) * itemsize)


def sparse_latent_scan_flops(resident_tokens: int, config: dict) -> int:
    """Multiply-adds x 2 of the attention, absorbed, over the selected
    positions: every head scores a row over all its ``kv_lora_rank +
    qk_rope_head_dim`` values and sums its first ``kv_lora_rank``."""
    rank, rope = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    return (2 * selected_positions(resident_tokens, config)
            * int(config["num_hidden_layers"])
            * int(config["num_attention_heads"]) * (rank + rope + rank))
