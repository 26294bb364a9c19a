"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

The yardstick's part that reads the device: which planes are chips, which
events are device operations, when the device was busy, how long each
operation and each program took, and what the host was doing in the
longest idle gaps. Read with ``jax.profiler.ProfileData`` alone.

``python3 -m benchmark.trace_reduce --dump <file.xplane.pb>`` prints the
planes, lines and first events of a trace: look at one by hand before
writing a reader against it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench.trace_window"

Interval = Tuple[float, float]  # seconds


class Event:
    __slots__ = ("name", "start", "end", "stats")

    def __init__(self, name: str, start: float, end: float,
                 stats: Dict[str, Any]) -> None:
        self.name, self.start, self.end, self.stats = name, start, end, stats

    @property
    def dur(self) -> float:
        return self.end - self.start


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Any:
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line: Any) -> List[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns) * 1e-9
        out.append(Event(e.name, start, start + float(e.duration_ns) * 1e-9,
                         dict(e.stats)))
    return out


_HLO = re.compile(
    r"^%?(?P<name>[A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=\s*\(*(?P<dtype>[a-z]+\d*)"
    r"\[(?P<shape>[\d,]*)\]")


def stable_name(ev: Event) -> str:
    """A name that survives a recompile. On the TPU an operation's event
    name is its whole HLO line (``%copy.17 = bf16[24,128,128,16,64]{...}
    copy(...)``): keep the operation's name without its numeric suffix and
    the (first) result's type and shape -> ``copy_bf16_24_128_128_16_64_``.
    Other events keep their name, less a numeric suffix."""
    m = _HLO.match(ev.name)
    if m:
        base = (f"{m.group('name')}_{m.group('dtype')}_"
                f"{m.group('shape').replace(',', '_')}_")
    else:
        base = re.sub(r"[.\-]\d+$", "", ev.name.split(" ")[0].lstrip("%"))
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", base)[:120]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event's own time: its duration less the time its children (the
    events nested inside it on the same line, e.g. the body of a ``while``)
    cover. Sums of self times never count a nanosecond twice."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out: List[List[Any]] = []
    stack: List[int] = []
    for ev in evs:
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(ev.end, parent[0].end) - ev.start
        out.append([ev, ev.dur])
        stack.append(len(out) - 1)
    return [(ev, max(t, 0.0)) for ev, t in out]


class Trace:
    """One trace, reduced once: per chip the device-operation events, and
    the host's events by thread."""

    def __init__(self, pd: Any) -> None:
        self.devices: Dict[int, List[Event]] = {}
        self.modules: Dict[int, List[Event]] = {}
        self.host: Dict[str, List[Event]] = {}
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        self.devices[int(m.group(1))] = _events(line)
                    elif line.name == MODULES_LINE:
                        self.modules[int(m.group(1))] = _events(line)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.host.setdefault(line.name, []).extend(_events(line))
        self.devices = {d: ev for d, ev in self.devices.items() if ev}
        self.window = self._window()

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        return cls(load(find_xplane(trace_dir)))

    def _window(self) -> Optional[Interval]:
        """The traced window: the harness's own annotation on the host,
        else the extent of the device's events."""
        for evs in self.host.values():
            for ev in evs:
                if ev.name == WINDOW_ANNOTATION:
                    return (ev.start, ev.end)
        spans = [(ev.start, ev.end) for evs in self.devices.values()
                 for ev in evs]
        if not spans:
            return None
        return (min(s for s, _ in spans), max(e for _, e in spans))

    def in_window(self, events: Sequence[Event]) -> List[Event]:
        lo, hi = self.window
        return [e for e in events if e.end > lo and e.start < hi]

    def busy_intervals(self, device: int) -> List[Interval]:
        lo, hi = self.window
        return union(clip(((e.start, e.end) for e in self.devices[device]),
                          lo, hi))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips that
        ran any."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy_intervals(d))
                   for d in self.devices) / len(self.devices)

    def window_s(self) -> float:
        return self.window[1] - self.window[0] if self.window else 0.0

    def op_self_times(self, device: int) -> List[Tuple[Event, float]]:
        return self_times(self.in_window(self.devices[device]))

    def top_ops(self, n: int = 10) -> List[List[Any]]:
        """Device operations by summed self time, averaged over chips."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for ev, t in self.op_self_times(d):
                key = stable_name(ev)
                acc[key] = acc.get(key, 0.0) + t
        k = max(len(self.devices), 1)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs / k] for name, secs in rows]

    def _module_spans(self, device: int, pattern: str) -> List[Interval]:
        rx = re.compile(pattern)
        return union((e.start, e.end) for e in self.modules.get(device, [])
                     if rx.search(e.name))

    def op_time(self, pattern: str, module: Optional[str] = None,
                ) -> Tuple[float, float]:
        """Summed self time and count of device operations whose stable
        name matches ``pattern``, averaged over chips. ``module`` keeps
        only operations that start inside a program whose name matches (the
        TPU's operations carry no program name: membership is by time)."""
        import bisect

        rx = re.compile(pattern)
        secs, count = 0.0, 0
        for d in self.devices:
            spans = self._module_spans(d, module) if module else None
            starts = [s for s, _ in spans] if spans is not None else []
            for ev, t in self.op_self_times(d):
                if spans is not None:
                    i = bisect.bisect_right(starts, ev.start) - 1
                    if i < 0 or ev.start >= spans[i][1]:
                        continue
                if rx.search(stable_name(ev)):
                    secs += t
                    count += 1
        k = max(len(self.devices), 1)
        return secs / k, count / k

    def module_time(self, pattern: str) -> Tuple[float, float]:
        """Device time (clipped to the window) and count of whole programs
        whose name matches, averaged over chips: the trace's modules line."""
        rx = re.compile(pattern)
        k = max(len(self.devices), 1)
        lo, hi = self.window
        hits = [e for d in self.modules for e in self.in_window(self.modules[d])
                if rx.search(e.name)]
        secs = sum(min(e.end, hi) - max(e.start, lo) for e in hits)
        return secs / k, len(hits) / k

    def idle_gaps(self, n: int = 10) -> List[List[Any]]:
        """The longest idle gaps of the first chip, summed by what the host
        was doing in them: the innermost host event covering the middle of
        the gap, as ``<thread>:<event>``."""
        if not self.devices:
            return []
        d = sorted(self.devices)[0]
        busy = self.busy_intervals(d)
        lo, hi = self.window
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] > 0]
        gaps.sort(key=lambda g: g[0] - g[1])
        doing = self._host_index()
        acc: Dict[str, float] = {}
        for s, e in gaps[:400]:
            key = doing(s, e)
            acc[key] = acc.get(key, 0.0) + (e - s)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs] for name, secs in rows if secs >= 1e-6]

    def _host_index(self):
        """(gap start, gap end) -> ``<thread>:<event>``: the shortest host
        event that covers the WHOLE gap — the call inside which the device
        sat idle — on the interpreter's threads where the trace has them,
        else on any thread."""
        import numpy as np

        def index(threads):
            names, starts, ends = [], [], []
            for thread in threads:
                for ev in self.host[thread]:
                    if ev.name != WINDOW_ANNOTATION and ev.dur > 0:
                        names.append(f"{thread}:{ev.name}")
                        starts.append(ev.start)
                        ends.append(ev.end)
            st, en = np.asarray(starts), np.asarray(ends)
            return names, st, en, en - st

        python = [t for t in self.host if t.startswith("python")]
        tiers = [index(python), index([t for t in self.host
                                       if t not in python])]

        def doing(s: float, e: float) -> str:
            for names, st, en, du in tiers:
                if not names:
                    continue
                hit = np.nonzero((st <= s) & (e <= en))[0]
                if hit.size:
                    name = names[int(hit[np.argmin(du[hit])])]
                    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)[:120]
            return "host:_no_span_recorded"

        return doing


def dump(path: str, per_line: int = 12) -> None:
    pd = load(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:per_line]:
                print(f"    {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={dict(e.stats)}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dump", required=True)
    ap.add_argument("--per-line", type=int, default=12)
    a = ap.parse_args()
    dump(a.dump, a.per_line)
