"""The decode attention call of a SELECTING layer as a share of its memory
roofline, in percent.

Bytes the model had to read in the traced window (``sparse_counts``: the
keys and values of the selected rows, counted by the program as it
dispatched each scan: ``Turn.kv_rows_selected`` x ``Turn.substeps``; a
dispatch astride an edge of the traced window by the share of its
dispatch-to-fetch time inside it, as ``moe_roofline`` counts), over the
chip's peak memory bandwidth, over the summed device time of the decode
program's attention call (``op`` inside ``module``). The bytes are the
model's need, not the form's traffic: a call that walks every live page and
masks reads 2-9 times as much at these lengths, so its share reads low and
cannot pass 100. The share of the compute peak is printed beside it.

``bytes="walked"`` is the FORM's own roofline instead: the bytes a call
that copies every live page of every slot reads (``sparse_counts.
walked_pages_bytes``: ``Turn.kv_pages_live``, the engine's count of a scan's
live pages a layer by the kernel's own rule, x selecting layers x substeps)
over the same time: how near its copy's rate the kernel runs, apart from
what the form reads in vain.
``None`` where the program keeps no such counter (a model without an
indexer, or the parent of the PR that brought it) or the call is not in the
trace."""

from benchmark.sparse_counts import (
    selected_rows_bytes,
    selected_rows_flops,
    walked_pages_bytes,
)


def read(ctx, op: str, module: str, bytes: str = "selected"):
    tr, win = ctx["trace"], ctx["trace_host_window"]
    if tr is None or win is None or not tr.devices:
        return None
    secs, calls = tr.op_time(op, module)
    if secs <= 0:
        return None
    lo = (ctx["run"]["t0"] + win[0]) * 1000.0
    hi = (ctx["run"]["t0"] + win[1]) * 1000.0
    rows = pages = 0.0
    for eng in ctx["engines"]:
        ring = getattr(eng, "turns", None)
        for t in (list(ring.copy()) if ring is not None else ()):
            if not getattr(t, "kv_rows_selected", 0) or not t.t_fetched:
                continue
            span = max(t.t_fetched - t.t_dispatch, 1e-9)
            inside = max(min(t.t_fetched, hi) - max(t.t_dispatch, lo), 0.0)
            rows += t.kv_rows_selected * t.substeps * inside / span
            pages += t.kv_pages_live * t.substeps * inside / span
    if rows <= 0:
        return None
    dc = ctx["config"]["program"]["decoder_config"]
    chips = len(tr.devices)
    if bytes == "walked":
        # every layer of a model with an indexer selects
        pages *= dc["num_layers"]
        walk_s = walked_pages_bytes(
            pages, ctx["config"]["deployment"]["llm"]["page_size"],
            dc["num_kv_heads"], dc["head_dim"],
        ) / ctx["peaks"]["hbm_bytes_per_s"] / chips
        print(f"sparse: {op}: {pages:.0f} live pages walked in "
              f"{secs * 1000.0:.1f} ms ({secs / pages * 1e6:.3f} us a page): "
              f"{100.0 * walk_s / secs:.1f}% of the memory roofline of the "
              "bytes the form reads", flush=True)
        return 100.0 * walk_s / secs
    least_s = selected_rows_bytes(
        rows, dc["num_kv_heads"], dc["head_dim"]
    ) / ctx["peaks"]["hbm_bytes_per_s"] / chips
    flop_s = selected_rows_flops(
        rows, dc["num_heads"], dc["head_dim"]
    ) / ctx["peaks"]["bf16_flops_per_s"] / chips
    print(f"sparse: {op}: {calls:.0f} calls, {secs * 1000.0:.1f} ms on the "
          f"device in the trace; {rows:.0f} selected rows counted: "
          f"{100.0 * least_s / secs:.1f}% of the memory roofline, "
          f"{100.0 * flop_s / secs:.1f}% of the compute peak", flush=True)
    return 100.0 * least_s / secs
