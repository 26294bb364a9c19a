"""Operations and bytes the grouped expert matmul NEEDS (``ops/moe.py``'s
``moe_grouped_matmul``: both of its calls a layer, the gate/up product and
the down product), computed from the configuration's widths and the
program's routing counters. Kept here, beside ``kernel_bytes.py``, so that
no PR which claims a gain can change it; imports nothing from the program.

``experts_hit`` is the number of (layer, substep, expert) triples with at
least one REAL routed row and ``rows`` the real routed rows, each summed
over the dispatches counted (``Turn.moe_experts_hit``, ``Turn.moe_rows``).
Rows of pad tokens and inactive slots are computed by the program and not
counted here: the count is what must be read, so a share of the roofline
computed from it can only come out low."""

from __future__ import annotations


def grouped_matmul_bytes(experts_hit: int, rows: int, d_model: int,
                         mlp_dim: int, gated: bool = True,
                         itemsize: int = 2) -> int:
    """Every hit expert's weights once (gate, up and down: 3 x D x F; 2
    ungated), plus each routed row in (D), its hidden row out and in again
    (2 F) and its result out (D)."""
    weights = int(experts_hit) * (3 if gated else 2) * d_model * mlp_dim
    rows_io = int(rows) * (2 * d_model + 2 * mlp_dim)
    return (weights + rows_io) * itemsize


def grouped_matmul_flops(rows: int, d_model: int, mlp_dim: int,
                         gated: bool = True) -> int:
    """Two operations a multiply-add, each routed row through its expert's
    three (two) D x F products."""
    return 2 * int(rows) * (3 if gated else 2) * d_model * mlp_dim
