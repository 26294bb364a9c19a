"""Bytes the decode scans NEED to move for a state-space mixer's state: a
model whose layers each keep a MATRIX a head a slot (Mamba-2: ``mamba_n_heads``
x ``mamba_d_head`` x ``mamba_d_state``), read and written once by every
decode substep of every slot that advances, whatever the slot's context. Kept
here, beside ``kernel_bytes.py``, so that no PR which claims a gain can
change it; imports nothing from the program, and knows nothing of what
implements the update (a Pallas kernel with the plane aliased in place, or
XLA fusions that pass over it more than once: that is the implementation's
cost, not the model's need, so a share computed from this can only come out
low). The state's precision is the configuration file's
``assumed.ssm_state_dtype`` (float32: 4 B a value)."""

from __future__ import annotations


def state_itemsize(config: dict) -> int:
    said = str(config.get("assumed", {}).get("ssm_state_dtype", "float32"))
    return 2 if said.startswith(("bfloat16", "float16")) else 4


def state_bytes_per_slot_layer(config: dict) -> int:
    """One slot's state in one layer, from the published keys: heads x head
    x state values (32 x 128 x 256 x 4 B = 4.19 MB)."""
    return (int(config["mamba_n_heads"]) * int(config["mamba_d_head"])
            * int(config["mamba_d_state"]) * state_itemsize(config))


def state_step_bytes(config: dict) -> int:
    """Bytes ONE decode substep must move for ONE advancing slot: every
    served layer's state read once and written once."""
    return (2 * int(config["num_hidden_layers"])
            * state_bytes_per_slot_layer(config))


def scan_bytes(config: dict, active: float, substeps: float) -> float:
    """... for ``substeps`` substeps of ``active`` advancing slots."""
    return float(active) * float(substeps) * state_step_bytes(config)
