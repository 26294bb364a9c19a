"""The grouped expert matmul's share of its memory roofline, in percent.

Bytes the traced window's dispatches had to move (``moe_counts``: the
weights of every expert a real row reached, once a layer a substep, plus the
routed rows in and out), over the chip's peak memory bandwidth, over the
kernel's summed device time. The counts are the program's routing counters
in the engines' turn ring (``Turn.moe_experts_hit``, ``Turn.moe_rows``); a
dispatch that straddles an edge of the traced window counts by the share of
its dispatch-to-fetch time inside it. At 4 rows an expert the kernel is
bound by memory (a flop a byte); the share of the compute peak is printed
beside it. ``None`` where the program keeps no such counters (the parent of
the PR that brought them) or the kernel is not in the trace."""

from benchmark.moe_counts import grouped_matmul_bytes, grouped_matmul_flops


def read(ctx, op: str):
    tr, win = ctx["trace"], ctx["trace_host_window"]
    if tr is None or win is None or not tr.devices:
        return None
    secs, calls = tr.op_time(op)
    if secs <= 0:
        return None
    lo = (ctx["run"]["t0"] + win[0]) * 1000.0
    hi = (ctx["run"]["t0"] + win[1]) * 1000.0
    hit = rows = 0.0
    for eng in ctx["engines"]:
        ring = getattr(eng, "turns", None)
        for t in (list(ring.copy()) if ring is not None else ()):
            if not getattr(t, "moe_experts_hit", 0) or not t.t_fetched:
                continue
            span = max(t.t_fetched - t.t_dispatch, 1e-9)
            inside = max(min(t.t_fetched, hi) - max(t.t_dispatch, lo), 0.0)
            hit += t.moe_experts_hit * inside / span
            rows += t.moe_rows * inside / span
    if hit <= 0:
        return None
    dc = ctx["config"]["program"]["decoder_config"]
    gated = bool(dc.get("gated_mlp", True))
    chips = len(tr.devices)
    least_s = grouped_matmul_bytes(
        hit, rows, dc["d_model"], dc["mlp_dim"], gated
    ) / ctx["peaks"]["hbm_bytes_per_s"] / chips
    flop_s = grouped_matmul_flops(
        rows, dc["d_model"], dc["mlp_dim"], gated
    ) / ctx["peaks"]["bf16_flops_per_s"] / chips
    print(f"moe: {op}: {calls:.0f} calls, {secs * 1000.0:.1f} ms on the "
          f"device in the trace; {hit:.0f} expert reads and {rows:.0f} "
          f"routed rows counted: {100.0 * least_s / secs:.1f}% of the "
          f"memory roofline, {100.0 * flop_s / secs:.1f}% of the compute "
          "peak", flush=True)
    return 100.0 * least_s / secs
