"""Plain references: each architecture's forward pass in float32
``jax.numpy`` at ``default_matmul_precision("highest")`` — no cache, no
kernels, no batching, nothing imported from the program. A configuration
file names its reference (``"reference": "gpt2"``) and the harness finds
``benchmark/reference/<name>.py`` by that name.

A reference module exports ``logits(weights, tokens, sizes) -> [T, V]``:
``weights`` is the neutral layout the configuration's view gives
(``benchmark/views/<view>.py``: one dict per layer), ``sizes`` the
configuration file.
Layers run one at a time so that 7B widths fit beside the served model.
"""

import importlib


def get(name: str):
    return importlib.import_module(f"benchmark.reference.{name}")
