"""The paged decode kernel's share of its memory roofline, in percent, for a
configuration in which only SOME layers hold pages (conv layers beside
attention layers: ``layer_types`` names them).

As ``paged_decode_roofline``, with the bytes a scan had to read counted over
the layers that hold pages alone (``hybrid_counts``: 10 of 40 for LFM2; the
head's width the file's own). The lengths are the replayed requests' KNOWN
ones: token i (i >= 1) of a request with a prompt of P tokens is produced by
a substep that scans P + i resident positions, stamped by the client when it
arrived; tokens stamped inside the traced window are the window's. The time
is that of every call of the kernel in the trace. ``None`` without a trace,
without the kernel in it, or for a configuration file whose ``layer_types``
name no conv layer (every other model: ``paged_decode_roofline`` reads it)."""

from benchmark.hybrid_counts import conv_layers, hybrid_scan_bytes, page_layers


def read(ctx, op: str):
    tr, win = ctx["trace"], ctx["trace_host_window"]
    if tr is None or win is None or not tr.devices:
        return None
    cfg = ctx["config"]
    if "layer_types" not in cfg or not conv_layers(cfg):
        return None
    secs, calls = tr.op_time(op)
    if secs <= 0:
        return None
    total = tokens = 0
    for r in ctx["records"]:
        for i, t in enumerate(r["stamps"]):
            if i >= 1 and win[0] <= t < win[1]:
                total += hybrid_scan_bytes(r["prompt_len"] + i, cfg)
                tokens += 1
    if total == 0:
        return None
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"] / len(tr.devices)
    print(f"hybrid: paged decode kernel: {calls:.0f} calls, "
          f"{secs * 1000.0:.1f} ms on the device in the trace for "
          f"{tokens} tokens over {len(page_layers(cfg))} of "
          f"{int(cfg['num_hidden_layers'])} layers; they had to read "
          f"{total / 1e9:.3f} GB ({least_s * 1000.0:.1f} ms at the peak)",
          flush=True)
    return 100.0 * least_s / secs
