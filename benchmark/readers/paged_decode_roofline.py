"""The paged decode kernel's share of its memory roofline, in percent.

Bytes the scans of the traced window had to read, over the chip's peak
memory bandwidth, over the kernel's summed device time. The bytes come from
the replayed requests' KNOWN lengths: token i (i >= 1) of a request with a
prompt of P tokens is produced by a substep that scans P + i resident
positions, and the client stamped when each token arrived. Tokens stamped
inside the traced window (host clock) are the window's; a scan in flight at
either edge is the error, about one scan in the window's length. The scan is
bound by memory, not by compute (two operations a byte at batch 1 a row)."""

from benchmark.kernel_bytes import paged_decode_scan_bytes


def read(ctx, op: str):
    tr, win = ctx["trace"], ctx["trace_host_window"]
    if tr is None or win is None or not tr.devices:
        return None
    secs, _ = tr.op_time(op)
    if secs <= 0:
        return None
    dc = ctx["config"]["program"]["decoder_config"]
    head_dim = dc["d_model"] // dc["num_heads"]
    total = 0
    for r in ctx["records"]:
        for i, t in enumerate(r["stamps"]):
            if i >= 1 and win[0] <= t < win[1]:
                total += paged_decode_scan_bytes(
                    r["prompt_len"] + i, dc["num_layers"],
                    dc["num_kv_heads"], head_dim)
    if total == 0:
        return None
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"] / len(tr.devices)
    return 100.0 * least_s / secs
