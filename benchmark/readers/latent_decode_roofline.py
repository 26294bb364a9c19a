"""The latent decode kernel's share of its memory roofline, in percent.

As ``paged_decode_kinds_roofline``, with the bytes a scan had to read from
``latent_counts``: every resident position's ONE row a layer at its TRUE
width (1,152 B), whatever implements the kernel or pads the row. The lengths
are the replayed requests' KNOWN ones: token i (i >= 1) of a request with a
prompt of P tokens is produced by a substep that scans P + i resident
positions, stamped by the client when it arrived; tokens stamped inside the
traced window are the window's. The time is that of every call of the kernel
in the trace. At 32 query rows a page the kernel sits near the chip's ridge,
so the share of the compute peak is printed beside it. ``None`` without a
trace, without the kernel in it, or for a configuration file without
``kv_lora_rank``."""

from benchmark.latent_counts import latent_scan_bytes, latent_scan_flops


def read(ctx, op: str):
    tr, win = ctx["trace"], ctx["trace_host_window"]
    if tr is None or win is None or not tr.devices:
        return None
    cfg = ctx["config"]
    if "kv_lora_rank" not in cfg:
        return None
    secs, calls = tr.op_time(op)
    if secs <= 0:
        return None
    total = flops = tokens = 0
    for r in ctx["records"]:
        for i, t in enumerate(r["stamps"]):
            if i >= 1 and win[0] <= t < win[1]:
                total += latent_scan_bytes(r["prompt_len"] + i, cfg)
                flops += latent_scan_flops(r["prompt_len"] + i, cfg)
                tokens += 1
    if total == 0:
        return None
    chips = len(tr.devices)
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"] / chips
    flop_s = flops / ctx["peaks"]["bf16_flops_per_s"] / chips
    print(f"latent: decode kernel: {calls:.0f} calls, "
          f"{secs * 1000.0:.1f} ms on the device in the trace for "
          f"{tokens} tokens; they had to read {total / 1e9:.3f} GB "
          f"({least_s * 1000.0:.1f} ms at the peak: "
          f"{100.0 * least_s / secs:.1f}% of the memory roofline, "
          f"{100.0 * flop_s / secs:.1f}% of the compute peak)", flush=True)
    return 100.0 * least_s / secs
