"""The host's cost of launching ONE chunk program, from the engines' turn
ring (``DecodeEngine.turns``): over the records of ``kind`` "chunk"
dispatched in the window's part BEFORE the traced sub-window
(``engine_turns._part``'s cut: the Python tracer perturbs the rest), the
p50 of ``t_issued - t_dispatch`` in ms: the jitted call itself, its
uploads included, as the engine thread saw it. A first token waits for
exactly this call; ``host_gap_share_pct`` stops at ``t_dispatch`` and never
saw it. Several engines: the mean of their medians. ``None`` where the
program keeps no ring, a ring wrapped (``turns_dropped``), or an engine
dispatched no chunk in the part."""

from benchmark import stats


def read(ctx):
    run, win = ctx["run"], ctx.get("trace_host_window")
    until_s = win[0] if win else run["window_s"]
    lo = run["t0"] * 1000.0
    hi = lo + until_s * 1000.0
    medians = []
    for eng in ctx["engines"]:
        ring = getattr(eng, "turns", None)
        if ring is None or getattr(eng, "turns_dropped", 0):
            return None
        # one call: the engine may still append
        calls = [t.t_issued - t.t_dispatch for t in list(ring.copy())
                 if t.kind == "chunk" and lo <= t.t_dispatch < hi]
        if not calls:
            return None
        medians.append(stats.percentile(calls, 50))
    return sum(medians) / len(medians) if medians else None
