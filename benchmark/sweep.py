"""Find a fixed-rate cell's knee once: one process, one set-up, several rates.

    python3 -m benchmark.sweep --workload <name> --seed <n> --seconds 30 \
        --rates 4,6,8,9,10,12 [--out chiprun_out/sweep.json]

For each rate an open-loop window of ``--seconds`` with the cell's traffic
file at that rate. Knee = the highest rate at which at least 90% of the
requests met both limits and the backlog at the window's end is no larger
than at its middle (or than a tenth of a second's arrivals:
``sustained``). The cell's rate is at most 0.8 of the knee, rounded
down to 0.5 requests/s, and lower where the distribution printed for each
rate shows the tail on the step between two modes (the traffic file says
which rule fixed it). It goes into the traffic file as a number: no run of
the benchmark searches for a rate.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
from typing import Any, Dict, List

from benchmark import loadgen, stats
from benchmark.run import (Cell, Deployed, check_device, distribution_lines,
                           reference_check, say, use_checkout_cache)


def backlog(records: List[Dict[str, Any]], lo: float, hi: float) -> float:
    """Requests sent and still waiting for their first token, averaged
    over 21 instants of [lo, hi] (one instant of a queue is mostly noise)."""
    total = 0
    for k in range(21):
        t = lo + (hi - lo) * k / 20.0
        total += sum(1 for r in records
                     if r["sent"] is not None and r["sent"] <= t
                     and (r["first"] is None or r["first"] > t))
    return total / 21.0


def one_rate(dep: Deployed, cell: Cell, rate: float, seed: int,
             seconds: float) -> Dict[str, Any]:
    traffic = copy.deepcopy(cell.traffic)
    traffic["arrivals"]["rate_rps"] = rate
    requests = loadgen.build_requests(traffic, dep.vocab_size, seed, seconds)
    for e in dep.engines:
        e.reset_ttft_window()
    run = loadgen.run_traffic(dep.submit, traffic, requests, seconds)
    recs, until = run["records"], run["observed_until_s"]
    row: Dict[str, Any] = {
        "rate_rps": rate, "requests": len(recs),
        "failed": sum(1 for r in recs if not r["ok"]),
        "slo_met_pct": stats.slo_met_pct(recs, traffic["limits"], until),
        "backlog_mid": backlog(recs, seconds / 2.0 - 1.0, seconds / 2.0 + 1.0),
        "backlog_end": backlog(recs, seconds - 2.0, seconds),
        "drained_at_s": until,
    }
    for field in ("ttft_ms", "tpot_ms"):
        for q in (50, 90, 99):
            row[f"{field}_p{q}"] = stats.whole_window_percentile(
                recs, field, q, until)["value"]
    say(f"sweep: {json.dumps(row)}")
    # every request's two latencies, so that the share inside ANY limits
    # can be worked out from the file afterwards
    row["ttft_ms"] = [round(v, 2) for _, v in
                      stats.field_values(recs, "ttft_ms", until)]
    row["tpot_ms"] = [round(v, 2) for _, v in
                      stats.field_values(recs, "tpot_ms", until)]
    for line in distribution_lines(run, traffic, dep.engines):
        say(f"sweep[{rate}]: {line}")
    return row


def sustained(row: Dict[str, Any]) -> bool:
    """At least 90% inside both limits and no growing backlog. A backlog of
    a tenth of a second's arrivals (or of one request) is the steady state's
    own, rate x time to first token, and not growth: at 32 requests/s and 24
    ms to the first token four replicas read 0.71 in the middle and 1.10 at
    the end (my chip run, PR 26), which a floor of one request called
    growing."""
    floor = max(1.0, 0.1 * row["rate_rps"])
    return (row["slo_met_pct"] >= 90.0
            and row["backlog_end"] <= max(row["backlog_mid"], floor))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--stop-past-knee", action="store_true",
                    help="rates ascend: stop after the first that fails")
    a = ap.parse_args()
    use_checkout_cache()
    cell = Cell(a.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("a sweep is for an open-loop cell")
    device = check_device(cell.chips, require_tpu=True)
    split: Dict[str, float] = {}
    dep = Deployed(cell.config, a.seed, device["devices"], split)
    try:
        ref = reference_check(dep, a.seed)
        say(f"sweep: set-up {split}; reference ok={ref['ok']} worst margin "
            f"{ref['worst_gap']:.4f}")
        rows = []
        for i, rate in enumerate(float(x) for x in a.rates.split(",")):
            rows.append(one_rate(dep, cell, rate, a.seed + i, a.seconds))
            if a.stop_past_knee and not sustained(rows[-1]):
                break
            time.sleep(1.0)
    finally:
        dep.close()
    knee = max((r["rate_rps"] for r in rows if sustained(r)), default=None)
    summary = {"workload": a.workload, "seconds": a.seconds,
               "limits": cell.traffic["limits"], "rows": rows, "knee_rps": knee,
               "device": {"platform": device["platform"],
                          "kind": device["kind"]}}
    say(f"sweep: knee {knee} requests/s -> 0.8 x knee = "
        f"{None if knee is None else int(knee * 0.8 * 2) / 2.0}")
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
