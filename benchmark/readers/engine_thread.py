"""Where the ENGINE THREAD's time went, on the wall clock, from the engines'
turn ring (``DecodeEngine.turns``) over the window's part BEFORE the traced
sub-window (the arithmetic is the program's own ``summarize_turns``).
Consecutive records tile the thread: each tile is the wall time between two
records' ``t_done``, split into blocked (inside the record's fetch, where
the result was not ready when the host came for it), idle (inside idle
waits) and the rest, the host's side: the thread had work of its own,
whether it ran or wanted to run and did not (the interpreter lock, the
scheduler; inside a jitted call the runtime's threads too). ``metric`` is
one of:

- ``"engine_thread_blocked_pct"``, ``"engine_thread_host_pct"``: that part
  of the tiles, percent (with the idle share the three sum to 100);
- ``"fetch_found_ready_pct"``: of the fetches, the share, percent, that found
  their result ready: the device had finished and waited for the host.

The ring is in ``t_done`` order and a part is cut by ``t_dispatch`` (a scan
is fetched after the chunk groups issued behind it), so the records read
are the ring's CONTIGUOUS run from the first record dispatched in the part
to the last: a run tiles itself, and no tile swallows a neighbour's wall.
Several engines: the mean. ``None`` where the program's summary has no such
key (the parent of the PR that brought the fields), the ring wrapped, or
the run holds fewer than two records. Printed once a run, per engine: the
three shares before the trace and INSIDE it (what the profiler's session
does to the thread, in the program's own units), beside the host's share
what the thread's CPU clock charged it (a reading: that clock moves in
10 ms ticks on some machines), and the three longest tiles."""

from benchmark.readers.engine_turns import _part

_CACHE = "_engine_thread"
_PARTS = ("blocked", "idle", "host")
_SHARES = {"engine_thread_blocked_pct": "thread_blocked_share",
           "engine_thread_host_pct": "thread_host_share",
           "fetch_found_ready_pct": "fetch_found_ready_share"}


def _run_of(ring, lo, hi):
    """The ring's contiguous run that holds every record dispatched in
    [lo, hi) ms."""
    at = [i for i, t in enumerate(ring) if lo <= t.t_dispatch < hi]
    return ring[at[0]:at[-1] + 1] if at else []


def _shares_line(i, label, summary):
    ms = summary["thread_ms"]
    shares = " ".join(
        f"{name}={100.0 * summary[f'thread_{name}_share']:.2f}"
        for name in _PARTS)
    ready = summary.get("fetch_found_ready_share")
    found = "" if ready is None else f", fetches found ready {100.0 * ready:.2f}%"
    return (f"thread: engine {i}: {label}: {shares} % of "
            f"{ms['wall'] / 1000.0:.3f} s tiled by {summary['dispatches'] - 1}"
            f" records (clipped {ms['clipped']:.3f} ms; the thread's CPU "
            f"clock moved {ms['cpu']:.0f} of the host's {ms['host']:.0f} ms)"
            f"{found}")


def _summaries(ctx, until_s):
    """Per engine, the program's summary of the run before the trace; the
    run inside it is printed beside it and not kept."""
    run, win = ctx["run"], ctx.get("trace_host_window")
    lo = run["t0"] * 1000.0
    out = []
    for i, eng in enumerate(ctx["engines"]):
        ring = list(eng.turns.copy())   # one call: the engine may append
        before = eng.turn_summary(
            records=_run_of(ring, lo, lo + until_s * 1000.0))
        out.append(before)
        if "thread_blocked_share" not in before:
            continue
        print(_shares_line(i, "before the trace", before), flush=True)
        if win:
            inside = eng.turn_summary(records=_run_of(
                ring, lo + win[0] * 1000.0, lo + win[1] * 1000.0))
            if "thread_blocked_share" in inside:
                print(_shares_line(i, "inside the trace", inside), flush=True)
        for r in before["longest_records"][:3]:
            print(f"thread: engine {i}: longest tile {r['wall']:.3f} ms at "
                  f"+{(r['t_dispatch'] - lo) / 1000.0:.3f} s, a {r['kind']} "
                  f"(substeps={r['substeps']} queued_behind="
                  f"{r['queued_behind']}): blocked {r['blocked']:.3f} idle "
                  f"{r['idle']:.3f} host {r['host']:.3f} ms (CPU clock "
                  f"{r['cpu']:.3f})", flush=True)
    return out


def read(ctx, metric: str):
    if metric not in _SHARES:
        raise ValueError(f"unknown metric {metric!r}")
    engines, until_s = _part(ctx)   # no ring, or no part before the trace
    if not engines or until_s <= 0:
        return None
    if _CACHE not in ctx:
        ctx[_CACHE] = _summaries(ctx, until_s)
    vals = [s.get(_SHARES[metric]) for s in ctx[_CACHE]]
    if any(v is None for v in vals) or any(s["dropped"] for s in ctx[_CACHE]):
        return None
    return 100.0 * sum(vals) / len(vals)
