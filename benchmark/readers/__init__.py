"""Readers: one to a file, found by the name a ``layer_metrics/*.json`` file
gives under ``"reader"``. ``read(ctx, **args)`` returns the metric's value,
or ``None`` when there is nothing to read (the harness then leaves the
metric out of the line). ``ctx`` is described in ``benchmark/README.md``."""
