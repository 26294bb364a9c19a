"""What the engine's turn ring (``DecodeEngine.turns``: one record per
device dispatch, stamped on the engine thread) says of the window's part
BEFORE the traced sub-window — the Python tracer and the trace's write-out
perturb the rest. ``metric`` is one of:

- ``"host_gap_share_pct"``: the sum, over consecutive dispatches with no
  idle wait between them and the earlier one fetched, of ``t_dispatch`` less
  the earlier record's ``t_fetched`` — host time in which the device cannot
  be busy — as a share of that part of the window, percent;
- ``"substeps_per_dispatch"``: decode substeps over decode scans: how far a
  dispatch's fixed cost is amortised;
- ``"slot_occupancy_pct"``: sum of (active slots x substeps) over
  (slots x substeps) of the decode scans, percent.

The arithmetic is the program's (``DecodeEngine.turn_summary`` on the part's
records, which ``snapshot()["turns"]`` shows an operator for the whole
ring); this reader cuts the part and prints. Several engines: the mean over
engines. ``None`` where the program keeps no ring, the ring wrapped
(``turns_dropped``), or the part holds no scan. Printed once a run: the
gap's median, p99 and split (harvest = ``t_done - t_fetched`` of the earlier
record, feed = ``t_dispatch`` less that ``t_done``), the longest gap with
the load the engine stood under (``trains``, ``queue_len``,
``pages_allocated``, ``positions_cached``), the dispatch call and wait; and,
for the cost of a profiler session to the engine thread, the median gap and
the decoded slot-substeps a second before the trace and inside it."""

from benchmark import stats

_CACHE = "_engine_turns"
_KEYS = {"host_gap_share_pct": ("host_gap_share", 100.0),
         "substeps_per_dispatch": ("substeps_per_dispatch", 1.0),
         "slot_occupancy_pct": ("mean_occupancy", 100.0)}


def _part(ctx):
    """Per engine, the program's own summary of the ring's records
    dispatched in the part (``DecodeEngine.turn_summary``: the one
    definition of a host gap, its split, substeps a scan and occupancy);
    None where the program keeps no ring."""
    if _CACHE in ctx:
        return ctx[_CACHE]
    run, win = ctx["run"], ctx.get("trace_host_window")
    until_s = win[0] if win else run["window_s"]
    lo = run["t0"] * 1000.0
    hi = lo + until_s * 1000.0
    out = []
    for i, eng in enumerate(ctx["engines"]):
        ring = getattr(eng, "turns", None)
        summarize = getattr(eng, "turn_summary", None)
        if ring is None or summarize is None:
            out = None
            break
        ring = list(ring.copy())   # one call: the engine may still append
        recs = [t for t in ring if lo <= t.t_dispatch < hi]
        summary = summarize(records=recs, span_ms=until_s * 1000.0)
        out.append(summary)
        if until_s > 0:
            for line in _lines(i, recs, summary, until_s, lo):
                print(line, flush=True)
        if win:   # what the profiler's session costs the engine thread
            traced = [t for t in ring
                      if lo + win[0] * 1000.0 <= t.t_dispatch
                      < lo + win[1] * 1000.0]
            for label, part, secs in (("before the trace", recs, until_s),
                                      ("inside the trace", traced,
                                       win[1] - win[0])):
                gap = summarize(records=part).get("host_gap_ms")
                if gap and secs > 0:
                    work = sum(t.active * t.substeps for t in part)
                    print(f"turns: engine {i}: {label}: host gap p50="
                          f"{gap['p50']:.3f} ms, "
                          f"{work / secs:.1f} slot-substeps/s", flush=True)
    ctx[_CACHE] = (out, until_s)
    return ctx[_CACHE]


def _lines(i, recs, summary, until_s, lo):
    head = (f"turns: engine {i}: {summary['dispatches']} dispatches "
            f"({summary['scans']} scans) in the first {until_s:.1f} s, "
            f"turns_dropped={summary['dropped']}")
    gap = summary.get("host_gap_ms")
    if not gap:
        return [head]
    calls = []
    for kind in ("turn", "chunk"):
        mine = [t for t in recs if t.kind == kind and t.t_fetched]
        if mine:
            calls.append(
                f"{kind}: call p50="
                f"{stats.percentile([t.t_issued - t.t_dispatch for t in mine], 50):.3f}"
                " wait p50="
                f"{stats.percentile([t.t_fetched - t.t_issued for t in mine], 50):.3f}"
                f" ms over {len(mine)}")
    worst = summary["longest_gaps"][0]
    return [head,
            f"turns: engine {i}: dispatch call (t_dispatch -> t_issued) and "
            "wait (-> t_fetched): " + "; ".join(calls),
            f"turns: engine {i}: host gap p50={gap['p50']:.3f} "
            f"p99={gap['p99']:.3f} max={gap['max']:.3f} ms over "
            f"{gap['n']} gaps, sum {gap['sum'] / 1000.0:.3f} s = harvest "
            f"{gap['harvest_sum'] / 1000.0:.3f} s + feed "
            f"{gap['feed_sum'] / 1000.0:.3f} s (p50 "
            f"{gap['harvest_p50']:.3f} + {gap['feed_p50']:.3f} ms)",
            f"turns: engine {i}: longest gap {worst['gap_ms']:.3f} ms at "
            f"+{(worst['at_ms'] - lo) / 1000.0:.3f} s, {worst['after']} -> "
            f"{worst['before']}: harvest {worst['harvest_ms']:.3f} + feed "
            f"{worst['feed_ms']:.3f} ms, with trains={worst['trains']} "
            f"queue_len={worst['queue_len']} "
            f"pages_allocated={worst['pages_allocated']} "
            f"positions_cached={worst['positions_cached']}"]


def read(ctx, metric: str):
    if metric not in _KEYS:
        raise ValueError(f"unknown metric {metric!r}")
    engines, until_s = _part(ctx)
    if not engines or until_s <= 0:
        return None
    key, scale = _KEYS[metric]
    vals = []
    for summary in engines:
        if summary["dropped"] or "substeps_per_dispatch" not in summary:
            return None
        vals.append(scale * summary[key])
    return sum(vals) / len(vals)
