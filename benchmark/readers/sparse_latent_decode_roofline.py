"""The selecting latent decode kernel's share of its roofline, in percent.

As ``latent_decode_roofline``, with what the MODEL needs a substep from
``sparse_latent_counts``: the ``min(length, index_topk)`` selected positions'
rows a layer at their TRUE width (1,152 B) and the heads' absorbed products
over them, whatever form implements the read; the roofline is ``max(bytes /
peak bandwidth, operations / peak rate)``. The lengths are the replayed
requests' KNOWN ones: token i (i >= 1) of a request with a prompt of P tokens
is produced by a substep that attends P + i resident positions, stamped by
the client when it arrived; tokens stamped inside the traced window are the
window's. The time is that of every call of the kernel in the trace. A form
that walks every live page under a mask reads 2-9 times the selected rows,
so its share reads LOW: that is the room a form that gathers them has. The
bytes of the index keys the SELECTION reads are printed beside it, not
counted (they are not the kernel's). ``None`` without a trace, without the
kernel in it, or for a configuration file without ``kv_lora_rank`` and
``index_topk``."""

from benchmark.sparse_latent_counts import (
    index_key_scan_bytes,
    sparse_latent_scan_bytes,
    sparse_latent_scan_flops,
)


def read(ctx, op: str):
    tr, win = ctx["trace"], ctx["trace_host_window"]
    if tr is None or win is None or not tr.devices:
        return None
    cfg = ctx["config"]
    if "kv_lora_rank" not in cfg or "index_topk" not in cfg:
        return None
    secs, calls = tr.op_time(op)
    if secs <= 0:
        return None
    total = flops = keys = tokens = 0
    for r in ctx["records"]:
        for i, t in enumerate(r["stamps"]):
            if i >= 1 and win[0] <= t < win[1]:
                total += sparse_latent_scan_bytes(r["prompt_len"] + i, cfg)
                flops += sparse_latent_scan_flops(r["prompt_len"] + i, cfg)
                keys += index_key_scan_bytes(r["prompt_len"] + i, cfg)
                tokens += 1
    if total == 0:
        return None
    chips = len(tr.devices)
    byte_s = total / ctx["peaks"]["hbm_bytes_per_s"] / chips
    flop_s = flops / ctx["peaks"]["bf16_flops_per_s"] / chips
    least_s = max(byte_s, flop_s)
    print(f"sparse latent: decode kernel: {calls:.0f} calls, "
          f"{secs * 1000.0:.1f} ms on the device in the trace for "
          f"{tokens} tokens; their selected rows are {total / 1e9:.3f} GB "
          f"({byte_s * 1000.0:.1f} ms at the peak: "
          f"{100.0 * byte_s / secs:.1f}% of the memory roofline, "
          f"{100.0 * flop_s / secs:.1f}% of the compute peak); the "
          f"selection read {keys / 1e9:.3f} GB of index keys beside them",
          flush=True)
    return 100.0 * least_s / secs
