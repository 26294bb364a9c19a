"""Operations and bytes a kernel's call NEEDS, computed from shapes (the
yardstick: kept here so that no PR which claims a gain can change it)."""

from __future__ import annotations


def paged_decode_scan_bytes(resident_tokens: int, num_layers: int,
                            num_kv_heads: int, head_dim: int,
                            kv_itemsize: int = 2) -> int:
    """Bytes the decode KV scan must read to produce one token for one
    stream whose cache holds ``resident_tokens`` positions: keys and values
    (x2) of every resident position in every layer. Queries, outputs and
    the page table are left out (they are thousands of times smaller)."""
    return (int(resident_tokens) * num_layers * 2 * num_kv_heads * head_dim
            * kv_itemsize)
