"""The first chip's idle time in the traced window, split by what the
engine thread was doing in it: the program's ``rdb.engine.*`` phase spans
(``jax.profiler.TraceAnnotation``, so on the profiler's clock) laid over the
gaps between device operations. ``part`` is one of:

- ``"host_work"``: idle time under a phase other than ``idle_wait``,
  ``*.dispatch`` and ``*.fetch`` — the host working with an empty device;
- ``"no_work"``: idle time under ``rdb.engine.idle_wait``;

each as a share of the traced window, percent. ``host_work`` is an UPPER
bound of what an untraced run spends: the profiler's Python tracer, on in
the benchmark's traced window, about doubles the host's time between a
fetch and the next dispatch (the ring's median gap inside the trace against
before it, which ``engine_turns`` prints); ``host_gap_share_pct``, from the
ring's untraced part, is the fair reading. The rest of the idle share
is the pauses between the operations of a RUNNING program (the device's own;
no host phase is charged with them), the runtime's edges (under
``*.dispatch``: the call with its uploads and the launch; under ``*.fetch``:
a launch still under way, and the result's way back) and what no span
covers; all five are printed, with one ``idle:`` line a phase and the
number of its spans in the window, and sum to ``device_idle_pct``. One more
line names the longest piece of idle time under one span (the idle wait
aside), with the
attributes of the spans around it (``horizon``, ``active``, ``spec`` of a
turn; ``trains``, ``tokens`` of a prefill; ``admitted``, ``queue_len``).

Each gap is split over the INNERMOST spans it overlaps (a gap runs from a
program's end through harvest, admission and the next preparation to the
next launch: its midpoint alone would name one of them). Before that the
device's clock is shifted onto the host's by the skew the trace itself
shows: a fetch cannot return before its program ends, so the skew is the
minimum over the window's fetches of (``*.fetch`` end - the nearest
program end). That is an upper bound of the true offset (it reads the
fastest fetch as instant); the lower bound, which the launches give (a
program cannot start before its ``*.dispatch``), is printed beside it. A
shift between the two moves idle time only between the two edges, never
into or out of the host's work, which lies in the middle of a gap. ``None``
where the trace has no device or no such span (a program without the
spans)."""

import bisect

from benchmark.trace_reduce import clip, total, union

PREFIX = "rdb.engine."
IDLE_WAIT = PREFIX + "idle_wait"
EDGES = (".dispatch", ".fetch")
IN_PROGRAM = "(between a running program's operations)"
MATCH_WITHIN_S = 0.020   # a fetch's program ended this near its return
_CACHE = "_idle_by_phase"


def engine_spans(trace, chip: int):
    """The phase spans of ONE engine, sorted outermost first: where the
    trace holds several engines (each tags its spans ``replica`` =
    ``<model>:<ordinal>[@<chip>]``), the first of those pinned to ``chip``,
    or the first of all where none is. One engine is one thread, so its
    spans nest, which ``innermost`` relies on."""
    spans = [ev for evs in trace.host.values() for ev in evs
             if ev.name.startswith(PREFIX)]
    replicas = {str(ev.stats.get("replica", "")) for ev in spans}
    if len(replicas) > 1:
        pinned = {r for r in replicas if r.endswith(f"@{chip}")}
        one = min(pinned or replicas)
        spans = [ev for ev in spans
                 if str(ev.stats.get("replica", "")) == one]
    return sorted(spans, key=lambda ev: (ev.start, -ev.end))


def clock_skew(spans, modules):
    """Seconds to ADD to a device time to get the host's (the upper bound:
    the minimum of fetch end - nearest program end), the number of fetches
    it was taken over, and the lower bound (the maximum of dispatch start -
    the start of the first program that, shifted by the upper bound, starts
    after it; None without a dispatch)."""
    ends = sorted(m.end for m in modules)
    upper = []
    for ev in spans:
        if ev.name.endswith(".fetch") and ends:
            i = bisect.bisect_left(ends, ev.end)
            near = min(ends[max(i - 1, 0):i + 1],
                       key=lambda m: abs(ev.end - m))
            if abs(ev.end - near) <= MATCH_WITHIN_S:
                upper.append(ev.end - near)
    if not upper:
        return 0.0, 0, None
    skew = min(upper)
    starts = sorted(m.start for m in modules)
    lower = []
    for ev in spans:
        if ev.name.endswith(".dispatch"):
            i = bisect.bisect_left(starts, ev.start - skew)
            if i < len(starts) and starts[i] + skew - ev.start <= MATCH_WITHIN_S:
                lower.append(ev.start - starts[i])
    return skew, len(upper), (max(lower) if lower else None)


def innermost(spans):
    """Disjoint (start, end, name) segments: at each instant the innermost
    open span's name. ``spans`` sorted by (start, -end)."""
    out, stack, cur = [], [], float("-inf")

    def emit(upto, name):
        nonlocal cur
        if upto > cur:
            out.append((cur, upto, name))
            cur = upto

    for ev in spans:
        while stack and stack[-1].end <= ev.start:
            top = stack.pop()
            emit(top.end, top.name)
        if stack:
            emit(ev.start, stack[-1].name)
        cur = max(cur, ev.start)
        stack.append(ev)
    while stack:
        top = stack.pop()
        emit(top.end, top.name)
    return out


def split_idle(trace):
    """{phase name, IN_PROGRAM or None: idle seconds}, what ``clock_skew``
    gives, and the longest piece of idle time under one span other than the
    idle wait as (seconds, its start on the host's clock, the spans open
    then, outermost first);
    None where there is nothing to read."""
    if trace is None or not trace.devices or trace.window_s() <= 0:
        return None
    chip = sorted(trace.devices)[0]
    spans = engine_spans(trace, chip)
    if not spans:
        return None
    modules = trace.modules.get(chip, [])
    skew, n_fetch, lower = clock_skew(spans, modules)
    lo, hi = trace.window
    edges = [lo] + [x for iv in trace.busy_intervals(chip) for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1])
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    running = union((m.start, m.end) for m in modules)
    starts = [s for s, _ in running]
    acc, gaps = {}, []
    for gs, ge in idle:    # on the device's clock: inside a program or not
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        inside = clip(running[i:bisect.bisect_left(starts, ge)], gs, ge)
        if inside:
            acc[IN_PROGRAM] = acc.get(IN_PROGRAM, 0.0) + total(inside)
        cuts = [gs] + [x for iv in inside for x in iv] + [ge]
        gaps += [(cuts[k] + skew, cuts[k + 1] + skew)
                 for k in range(0, len(cuts), 2) if cuts[k + 1] > cuts[k]]
    segs = innermost(spans)
    starts = [s for s, _, _ in segs]
    worst = (0.0, 0.0)
    for gs, ge in gaps:
        left = ge - gs
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(segs) and segs[i][0] < ge:
            s, e, name = segs[i]
            part = min(e, ge) - max(s, gs)
            if part > 0:
                acc[name] = acc.get(name, 0.0) + part
                left -= part
                if name != IDLE_WAIT:
                    worst = max(worst, (part, max(s, gs)))
            i += 1
        if left > 0:
            acc[None] = acc.get(None, 0.0) + left
    secs, at = worst
    around = [ev for ev in spans if ev.start <= at < ev.end]
    return acc, skew, n_fetch, lower, (secs, at, around)


def kind_of(name):
    if name is None:
        return "unattributed"
    if name == IN_PROGRAM:
        return "in_program"
    if name == IDLE_WAIT:
        return "no_work"
    return "edge" if name.endswith(EDGES) else "host_work"


def _analyse(ctx):
    if _CACHE in ctx:
        return ctx[_CACHE]
    trace = ctx["trace"]
    got = split_idle(trace)
    if got is None:
        ctx[_CACHE] = None
        return None
    acc, skew, n_fetch, lower, (worst_s, worst_at, around) = got
    window, idle = trace.window_s(), sum(acc.values())
    pct = {k: 0.0 for k in ("host_work", "no_work", "edge", "in_program",
                            "unattributed")}
    for name, secs in acc.items():
        pct[kind_of(name)] += 100.0 * secs / window
    lo, hi = trace.window
    count = {}
    for evs in trace.host.values():
        for ev in evs:
            if ev.name in acc and ev.end > lo and ev.start < hi:
                count[ev.name] = count.get(ev.name, 0) + 1
    print(f"idle: device clock shifted by {skew * 1000.0:+.3f} ms onto the "
          f"host's (minimum of fetch end - program end over {n_fetch} "
          "fetches; the launches allow no less than "
          + ("nothing said" if lower is None else f"{lower * 1000.0:+.3f}")
          + " ms)", flush=True)
    for name, secs in sorted(acc.items(), key=lambda kv: -kv[1]):
        spans = f" under {count[name]} spans" if name in count else ""
        print(f"idle: {kind_of(name):12s} {name or '(no span)':34s} "
              f"{secs:.4f} s = {100.0 * secs / idle if idle else 0.0:.1f}% "
              f"of idle, {100.0 * secs / window:.2f}% of the window{spans}",
              flush=True)
    if around:
        attrs = " ".join(
            f"{k}={v}" for ev in around for k, v in sorted(ev.stats.items())
            if k != "replica")
        print(f"idle: longest under one span {worst_s * 1000.0:.3f} ms at "
              f"+{worst_at - lo:.3f} s of the trace, under "
              + " > ".join(ev.name[len(PREFIX):] for ev in around)
              + (f" ({attrs})" if attrs else ""), flush=True)
    print("idle: host work {host_work:.2f} + no work {no_work:.2f} + runtime "
          "edges {edge:.2f} + inside programs {in_program:.2f} + "
          "unattributed {unattributed:.2f} = ".format(**pct)
          + f"{sum(pct.values()):.2f}% of the window idle", flush=True)
    ctx[_CACHE] = pct
    return pct


def read(ctx, part: str):
    pct = _analyse(ctx)
    if pct is None:
        return None
    if part not in ("host_work", "no_work"):
        raise ValueError(f"unknown part {part!r}")
    return pct[part]
