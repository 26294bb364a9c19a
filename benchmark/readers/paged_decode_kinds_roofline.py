"""The paged decode kernel's share of its memory roofline, in percent, for a
configuration whose KV state is held by layer kind: full layers and window
layers with their own head counts, keys wider than values.

As ``paged_decode_window_roofline``, with the bytes a scan had to read
summed by layer kind (``kv_kind_counts``: a full layer ``K_G x (192 + 128) x
2 B`` a resident position, a window layer ``K_L x 320 x 2 B`` a window
position). The lengths are the replayed requests' KNOWN ones: token i (i >=
1) of a request with a prompt of P tokens is produced by a substep that
scans P + i resident positions, stamped by the client when it arrived;
tokens stamped inside the traced window are the window's. The time is that
of every call of the kernel in the trace, both kinds' alike (one kernel, one
name). ``None`` without a trace, without the kernel in it, or for a
configuration file without ``hybrid_layer_pattern``."""

from benchmark.kv_kind_counts import kind_scan_bytes, layer_kinds


def read(ctx, op: str):
    tr, win = ctx["trace"], ctx["trace_host_window"]
    if tr is None or win is None or not tr.devices:
        return None
    cfg = ctx["config"]
    if "hybrid_layer_pattern" not in cfg:
        return None
    secs, calls = tr.op_time(op)
    if secs <= 0:
        return None
    kinds = layer_kinds(cfg)
    total = tokens = 0
    for r in ctx["records"]:
        for i, t in enumerate(r["stamps"]):
            if i >= 1 and win[0] <= t < win[1]:
                total += kind_scan_bytes(r["prompt_len"] + i, kinds)
                tokens += 1
    if total == 0:
        return None
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"] / len(tr.devices)
    print(f"kinds: paged decode kernel: {calls:.0f} calls, "
          f"{secs * 1000.0:.1f} ms on the device in the trace for "
          f"{tokens} tokens; they had to read {total / 1e9:.3f} GB "
          f"({least_s * 1000.0:.1f} ms at the peak)", flush=True)
    return 100.0 * least_s / secs
