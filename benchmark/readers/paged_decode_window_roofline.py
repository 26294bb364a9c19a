"""The paged decode kernel's share of its memory roofline, in percent, for a
configuration whose layers differ in what they attend (sliding-window layers
beside full ones).

As ``paged_decode_roofline``, with two differences: the bytes a scan had to
read are counted layer by layer (``paged_window_counts``: a sliding layer its
window, a full layer the resident length), and the head's width is the
configuration file's own ``head_dim`` (not hidden / heads). The lengths are
the replayed requests' KNOWN ones: token i (i >= 1) of a request with a
prompt of P tokens is produced by a substep that scans P + i resident
positions, stamped by the client when it arrived; tokens stamped inside the
traced window are the window's. The time is that of every call of the kernel
in the trace, sliding and full layers' alike (one kernel, one name). ``None``
without a trace, without the kernel in it, or for a configuration file
without ``layer_types``."""

from benchmark.paged_window_counts import (
    layer_windows,
    paged_window_scan_bytes,
)


def read(ctx, op: str):
    tr, win = ctx["trace"], ctx["trace_host_window"]
    if tr is None or win is None or not tr.devices:
        return None
    secs, _ = tr.op_time(op)
    cfg = ctx["config"]
    if secs <= 0 or "layer_types" not in cfg:
        return None
    windows = layer_windows(cfg)
    total = 0
    for r in ctx["records"]:
        for i, t in enumerate(r["stamps"]):
            if i >= 1 and win[0] <= t < win[1]:
                total += paged_window_scan_bytes(
                    r["prompt_len"] + i, windows,
                    int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    if total == 0:
        return None
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"] / len(tr.devices)
    return 100.0 * least_s / secs
