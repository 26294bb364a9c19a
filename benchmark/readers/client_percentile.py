"""A percentile of a time the load generator stamps around its own send,
in ms, over ALL requests of the window — of its part BEFORE the traced
sub-window where a trace was taken: the profiler's stop and write-out hold
the whole process for about a second (30 arrivals late by up to 0.9 s in a
traced four-replica run, none in six untraced ones: my chip runs, PR 26),
and that is the tracer's lateness, not the generator's. ``field`` is one of:

- ``"submit_ms"``: the call that hands a request to the system (handle ->
  router -> replica -> the engine's queue), as long as the generator's one
  sending thread was held by it;
- ``"gen_late_ms"``: sent - due: how late the generator ran (a starved
  generator must not be read as a fast server)."""

from benchmark import stats


def read(ctx, field: str, q: float):
    win = ctx.get("trace_host_window")
    recs = [r for r in ctx["records"] if r["sent"] is not None
            and (win is None or r["due"] < win[0])]
    if field == "submit_ms":
        vals = [r["submit_ms"] for r in recs if r["submit_ms"] is not None]
    elif field == "gen_late_ms":
        vals = [(r["sent"] - r["due"]) * 1000.0 for r in recs]
    else:
        raise ValueError(f"unknown field {field!r}")
    return stats.percentile(vals, q) if vals else None
