"""Device time of the operations matching ``op`` inside the programs matching
``module``, over the device's busy time in the traced window, percent: what
``device_op_time`` gives under ``share_of_busy_pct``, but ``None`` where NO
operation matches. For a pattern of XLA's own fusion names (which a compiler
or a shape may rename), a share of 0 would read as "that work costs nothing";
a metric left out of the line is refused in a cell that lists it."""


def read(ctx, op: str, module: str = None):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    secs, count = tr.op_time(op, module)
    busy = tr.busy_s()
    return 100.0 * secs / busy if count and busy > 0 else None
