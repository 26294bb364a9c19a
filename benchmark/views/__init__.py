"""Views: one to a file, found by the name a configuration file gives under
``"view"`` (``dense`` where it gives none). A view file is the one place
that knows one architecture's parameter names in the PROGRAM's tree:

- ``view(params, config) -> dict``: the same arrays under the
  architecture-neutral names that architecture's plain reference reads
  (``benchmark/reference/*``); no array is copied or reshaped. ``config`` is
  the configuration file.
- ``seeding(names, shape) -> (mean, std) | None`` (optional): how to draw a
  leaf the common table of ``benchmark/weights.py`` has no rule for, or a
  wrong one. ``names`` is the leaf's path in the program's tree, ``shape``
  its shape; the leaf is ``mean + std x normal`` (``(1.0, 0.0)``: ones).
  ``None``: the common table decides.
"""

import importlib


def get(name: str):
    return importlib.import_module(f"benchmark.views.{name}")
