"""Metric arithmetic of the benchmark (the yardstick; no program import).

A request record is a dict of host-clock seconds relative to the window's
start: ``due`` (when the schedule wanted it sent), ``sent``, ``first`` and
``last`` (first and last token seen by the client, ``None`` if never),
``n_out`` tokens received, ``want_out`` tokens asked for, ``ok``.

An end-to-end tail is the percentile over ALL requests due in the window
(``whole_window_percentile``), and the share of requests that met their
limits is over all of them too: a stall in the window shows in both. The
median over consecutive parts of the window of a per-part percentile
(``parts_percentile``), which one stall cannot move, stands beside them as
a per-layer diagnostic only.
"""

from __future__ import annotations

import math
from statistics import median
from typing import Any, Dict, List, Optional, Sequence, Tuple

Record = Dict[str, Any]


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default rule), q in [0, 100]."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def part_of(due_s: float, window_s: float, n_parts: int) -> int:
    """Index of the consecutive equal part of the window ``due_s`` falls in."""
    if n_parts < 1:
        raise ValueError("n_parts must be >= 1")
    i = int(due_s * n_parts / window_s)
    return min(max(i, 0), n_parts - 1)


def ttft_ms(rec: Record, observed_until_s: float) -> float:
    """Time from the request's DUE time to its first token. A request that
    never produced one counts with the time it was observed waiting, so it
    sits in the tail instead of vanishing from it."""
    end = rec["first"] if rec.get("first") is not None else observed_until_s
    return (end - rec["due"]) * 1000.0


def ttft_from_send_ms(rec: Record, observed_until_s: float) -> float:
    """The same from the SEND time: what a generator that ran late would
    report if it hid its own lateness. Printed only for comparison."""
    end = rec["first"] if rec.get("first") is not None else observed_until_s
    return (end - rec["sent"]) * 1000.0


def tpot_ms(rec: Record, observed_until_s: float) -> Optional[float]:
    """(last token - first token) / (tokens - 1) of one request; ``None``
    for a request asked for a single token. An unfinished request counts
    the time observed over the tokens it did receive."""
    if rec["want_out"] < 2:
        return None
    if rec.get("first") is None:
        return (observed_until_s - rec["due"]) * 1000.0
    if rec["ok"]:
        return (rec["last"] - rec["first"]) * 1000.0 / (rec["n_out"] - 1)
    return (observed_until_s - rec["first"]) * 1000.0 / max(rec["n_out"] - 1, 1)


_FIELDS = {"ttft_ms": ttft_ms, "tpot_ms": tpot_ms,
           "ttft_from_send_ms": ttft_from_send_ms}


def field_values(records: Sequence[Record], field: str,
                 observed_until_s: float) -> List[Tuple[float, float]]:
    """(due, value) of every request that has the field."""
    fn = _FIELDS[field]
    out = []
    for r in records:
        v = fn(r, observed_until_s)
        if v is not None:
            out.append((r["due"], v))
    return out


def parts_percentile(records: Sequence[Record], field: str, q: float,
                     window_s: float, n_parts: int,
                     observed_until_s: float) -> Dict[str, Any]:
    """Percentile ``q`` of ``field`` in each consecutive part of the window
    (a request belongs to the part its due time falls in), and the median
    of the parts. Returns the value, the per-part values and counts."""
    if n_parts < 3 or n_parts % 2 == 0:
        raise ValueError("n_parts must be odd and >= 3")
    buckets: List[List[float]] = [[] for _ in range(n_parts)]
    for due, v in field_values(records, field, observed_until_s):
        buckets[part_of(due, window_s, n_parts)].append(v)
    if any(not b for b in buckets):
        raise ValueError(
            f"a part of the window holds no request: counts "
            f"{[len(b) for b in buckets]}")
    per_part = [percentile(b, q) for b in buckets]
    return {"value": median(per_part), "parts": per_part,
            "counts": [len(b) for b in buckets],
            "beyond": [int(len(b) * (100.0 - q) / 100.0) for b in buckets]}


def whole_window_percentile(records: Sequence[Record], field: str, q: float,
                            observed_until_s: float) -> Dict[str, Any]:
    """Percentile ``q`` of ``field`` over every request due in the window
    that has the field; with the count, and how many lie beyond it."""
    vals = [v for _, v in field_values(records, field, observed_until_s)]
    return {"value": percentile(vals, q), "count": len(vals),
            "beyond": int(len(vals) * (100.0 - q) / 100.0)}


def met_limits(rec: Record, limits: Dict[str, float],
               observed_until_s: float) -> bool:
    """A request met its limits when it returned every token asked for,
    its first within ``ttft_ms`` of its due time and the rest at a mean
    gap within ``tpot_ms``. Failed, refused and unfinished requests miss."""
    if not rec["ok"] or rec["n_out"] != rec["want_out"]:
        return False
    if ttft_ms(rec, observed_until_s) > limits["ttft_ms"]:
        return False
    gap = tpot_ms(rec, observed_until_s)
    return gap is None or gap <= limits["tpot_ms"]


def slo_met_pct(records: Sequence[Record], limits: Dict[str, float],
                observed_until_s: float) -> float:
    """Share of ALL requests due in the window that met both limits."""
    if not records:
        raise ValueError("no requests were due in the window")
    met = sum(met_limits(r, limits, observed_until_s) for r in records)
    return 100.0 * met / len(records)


def window_rate(stamps: Sequence[float], window_s: float) -> float:
    """Events with a stamp inside [0, window_s) over the window's length."""
    return sum(1 for t in stamps if 0.0 <= t < window_s) / window_s


def quartile_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median as ``statistics.quantiles(n=4)`` gives them: the
    spread the bounds are set from."""
    from statistics import quantiles

    q1, _, q3 = quantiles(values, n=4)
    return (q3 - q1) / median(values)
