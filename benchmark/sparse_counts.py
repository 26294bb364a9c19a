"""Operations and bytes the decode attention of a SELECTING layer needs
(``ops/sparse_attention.py``: a query attends the ``topk`` cached positions
its indexer scores highest, all of them while there are no more), computed
from the configuration's widths and the program's own count of selected
rows. Kept here, beside ``kernel_bytes.py``, so that no PR which claims a
gain can change it; imports nothing from the program.

``rows`` is the number of (slot, layer, substep, selected position) rows:
``Turn.kv_rows_selected`` x substeps, summed over the dispatches counted.
What the MODEL must read is those rows' keys and values and nothing else,
whatever form the program reads them in: a form that walks every live page
and masks (or gathers a whole table's view) reads more, and its share of
this roofline comes out low by construction: never above 100.

``pages`` is the number of (slot, layer, substep, live page) pages a form
that WALKS every live page copies out of the pool (``Turn.kv_pages_live``,
the engine's count by the kernel's own rule, x selecting layers x substeps):
what the mask form reads, so that a slow kernel shows apart from the form's
own overhead."""

from __future__ import annotations


def selected_rows_bytes(rows: float, num_kv_heads: int, head_dim: int,
                        kv_itemsize: int = 2) -> float:
    """Keys and values (x2) of every selected row, all its KV heads."""
    return float(rows) * 2 * num_kv_heads * head_dim * kv_itemsize


def selected_rows_flops(rows: float, num_heads: int, head_dim: int) -> float:
    """Two operations a multiply-add, each selected row against every
    query head once for the score and once for the value."""
    return float(rows) * 2 * 2 * num_heads * head_dim


def walked_pages_bytes(pages: float, page_size: int, num_kv_heads: int,
                       head_dim: int, kv_itemsize: int = 2) -> float:
    """Keys and values (x2) of every position of every live page, all its
    KV heads: a page is copied whole."""
    return float(pages) * page_size * 2 * num_kv_heads * head_dim * kv_itemsize
