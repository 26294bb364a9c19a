"""``device_op_time`` with the count divided by the layers that HOLD PAGES,
taken from the configuration file's published ``layer_types``
(``hybrid_counts.page_layers``) and stated by no metric file: with
``"reduce": "ms_per_count"`` and the decode kernel's pattern, device time a
decode substep for a model whose kernel runs once a substep in its attention
layers alone (``device_op_time`` with ``"num_layers"`` divides by all of
them). ``None`` for a file without ``layer_types`` or without such a layer,
and wherever ``device_op_time`` gives ``None``."""

from benchmark.hybrid_counts import page_layers
from benchmark.readers import device_op_time


def read(ctx, **args):
    cfg = ctx["config"]
    layers = page_layers(cfg) if "layer_types" in cfg else []
    if not layers:
        return None
    return device_op_time.read(ctx, count_divisor=len(layers), **args)
