"""Latency budget gate: replay a flight record through the hop ledger,
fail naming the guilty hop.

Reads a span JSONL (``FileSpanExporter`` / ``run_slo_demo --trace``),
decomposes every request trace into a conserving per-hop ledger
(``utils/hops``: sum(hops) + unattributed == end-to-end, asserted),
grades only SERVED requests (``hops.is_served`` — front-door spans also
wrap admission 429s, 404s and /metrics scrapes, whose sub-ms "latency"
would dilute every percentile; excluded traces are counted in the
report as ``unserved_traces``), and
compares the per-hop p50/p95 — computed with the mergeable relative-
error quantile sketch — against the ceilings in a budget manifest
(``tools/budgets/ttft.json`` by default). A regression FAILS NAMING THE
GUILTY HOP and its overshoot, instead of "TTFT got slower somewhere".

Manifest semantics (lint-style shrink-only ratchet):
- ``hops.<name>.p50_ms`` / ``.p95_ms`` are CEILINGS. ``unattributed``
  and ``end_to_end`` are budgetable like any hop — the residual ceiling
  is what catches cost invisible between spans (page evictions, table
  refreshes, host gaps).
- ``--ratchet`` rewrites the manifest to ``min(old, measured * margin)``
  per ceiling: ceilings only ever SHRINK. A measured value above the
  old ceiling does not loosen it — it is a regression the ratchet
  refuses to bless (reported, manifest left at the old value).
- A manifest hop unknown to the taxonomy is an error (a typo'd hop
  would otherwise gate nothing, silently).
- A budgeted hop ABSENT from the capture fails the gate by default
  (``min_count`` per hop, default 1): a renamed span or instrumentation
  regression must not un-gate its ceilings by vanishing. Hops that are
  legitimately absent from healthy captures (``failover``) opt out with
  ``"min_count": 0``.

Usage:
    python tools/check_budgets.py SPANS.jsonl [--budgets FILE]
        [--report OUT.json] [--ratchet] [--margin 1.25]
        [--allow-empty]

Exit: 0 within budget, 1 guilty hop / conservation failure / empty
capture, 2 usage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_dynamic_batching_tpu.utils.hops import (  # noqa: E402
    HOP_ORDER,
    UNATTRIBUTED,
    LedgerError,
    hop_sketches,
    is_served,
    request_ledgers,
)
from ray_dynamic_batching_tpu.utils.trace_export import (  # noqa: E402
    read_export_header,
    read_spans_jsonl,
)

DEFAULT_BUDGETS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "budgets", "ttft.json"
)

# Manifest keys that budget something other than a taxonomy hop.
_EXTRA_BUDGET_KEYS = (UNATTRIBUTED, "end_to_end")

_QUANTS = {"p50_ms": 0.5, "p95_ms": 0.95}


def load_manifest(path: str) -> Dict[str, Any]:
    with open(path) as f:
        manifest = json.load(f)
    unknown = [
        h for h in manifest.get("hops", {})
        if h not in HOP_ORDER and h not in _EXTRA_BUDGET_KEYS
    ]
    if unknown:
        raise ValueError(
            f"{path}: unknown hop(s) in manifest: {unknown} — known: "
            f"{list(HOP_ORDER) + list(_EXTRA_BUDGET_KEYS)} (a typo'd hop "
            "gates nothing)"
        )
    return manifest


def grade(manifest: Dict[str, Any], sketches: Dict[str, Any]
          ) -> Dict[str, Any]:
    """Measured quantiles vs ceilings; verdicts name the guilty hop."""
    hops_out: Dict[str, Any] = {}
    guilty: List[str] = []
    for hop, ceilings in manifest.get("hops", {}).items():
        sk = sketches.get(hop)
        count = 0 if sk is None else sk.count
        entry: Dict[str, Any] = {"count": count}
        min_count = int(ceilings.get("min_count", 1))
        if count < min_count:
            # An absent hop must not pass its ceilings at measured 0.0 —
            # that is how a renamed span silently un-gates a budget.
            entry["absent"] = True
            guilty.append(
                f"{hop}: budgeted but absent from the capture ({count} "
                f"sample(s) < min_count {min_count}) — renamed span or "
                "instrumentation regression, not a pass"
            )
            hops_out[hop] = entry
            continue
        for key, q in _QUANTS.items():
            if key not in ceilings:
                continue
            ceiling = float(ceilings[key])
            measured = 0.0 if sk is None else sk.quantile(q)
            ok = measured <= ceiling
            entry[key] = {
                "ceiling_ms": ceiling,
                "measured_ms": round(measured, 3),
                "ok": ok,
            }
            if not ok:
                overshoot = measured - ceiling
                entry[key]["overshoot_ms"] = round(overshoot, 3)
                entry[key]["overshoot_x"] = round(measured / ceiling, 3)
                guilty.append(
                    f"{hop}: {key[:-3]} {measured:.1f} ms exceeds budget "
                    f"{ceiling:.1f} ms (overshoot {overshoot:.1f} ms, "
                    f"{measured / ceiling:.2f}x) — guilty hop"
                )
        hops_out[hop] = entry
    return {"hops": hops_out, "guilty": guilty, "ok": not guilty}


def ratchet(manifest: Dict[str, Any], sketches: Dict[str, Any],
            margin: float) -> Dict[str, Any]:
    """Shrink-only ceiling update: ``min(old, measured * margin)``.
    Returns {hop: {key: (old, new)}} for the entries that tightened;
    never loosens — a measured value above the old ceiling leaves the
    ceiling in place (that is a regression to fix, not to bless)."""
    if margin < 1.0:
        raise ValueError(f"margin must be >= 1.0, got {margin}")
    tightened: Dict[str, Any] = {}
    for hop, ceilings in manifest.get("hops", {}).items():
        sk = sketches.get(hop)
        if sk is None or sk.count == 0:
            continue  # no data: a blind ratchet would tighten to zero
        for key, q in _QUANTS.items():
            if key not in ceilings:
                continue
            old = float(ceilings[key])
            # 3 decimals (microsecond resolution): rounding any coarser
            # erases the margin for sub-ms hops — round(0.03*1.25, 1)
            # is 0.0, a ceiling nothing can ever pass and shrink-only
            # semantics can never recover.
            proposal = round(sk.quantile(q) * margin, 3)
            if 0.0 < proposal < old:
                ceilings[key] = proposal
                tightened.setdefault(hop, {})[key] = (old, proposal)
    return tightened


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/check_budgets.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("spans", help="flight-record span JSONL")
    ap.add_argument("--budgets", default=DEFAULT_BUDGETS,
                    help="budget manifest (default: %(default)s)")
    ap.add_argument("--report", default=None,
                    help="write the full JSON report here")
    ap.add_argument("--ratchet", action="store_true",
                    help="tighten manifest ceilings to min(old, "
                         "measured*margin) and rewrite it (shrink-only)")
    ap.add_argument("--margin", type=float, default=1.25,
                    help="ratchet headroom multiplier (default "
                         "%(default)s)")
    ap.add_argument("--allow-empty", action="store_true",
                    help="a capture with zero request traces passes "
                         "instead of failing (a capture cut short)")
    args = ap.parse_args(argv)

    try:
        manifest = load_manifest(args.budgets)
    except (OSError, ValueError) as e:
        print(f"budget manifest: {e}", file=sys.stderr)
        return 2
    try:
        spans = read_spans_jsonl(args.spans)
    except (OSError, ValueError) as e:
        print(f"{args.spans}: {e}", file=sys.stderr)
        return 2
    header = read_export_header(args.spans)
    if header and header.get("truncated"):
        # A capped capture under-reports tail latency — say so in the
        # gate's own output rather than grading silently optimistic.
        print(f"warning: capture truncated ({header.get('dropped')} spans "
              "dropped at the sink) — tail quantiles are optimistic",
              file=sys.stderr)

    try:
        all_ledgers, skipped = request_ledgers(spans)
    except LedgerError as e:
        print(f"LEDGER CONSERVATION FAILED: {e}", file=sys.stderr)
        return 1
    # Grade only SERVED requests: front-door spans also wrap admission
    # 429s, 404s and /metrics scrapes, whose sub-ms "latency" would
    # dilute every percentile (and, during an overload capture, let
    # --ratchet tighten ceilings to reject scale — unrecoverable under
    # shrink-only semantics). Counted in the report, never silent.
    ledgers = [l for l in all_ledgers if is_served(l)]
    unserved = len(all_ledgers) - len(ledgers)
    relative_accuracy = float(manifest.get("relative_accuracy", 0.01))
    sketches = hop_sketches(ledgers, relative_accuracy=relative_accuracy)

    report: Dict[str, Any] = {
        "metric": "budget_check",
        "spans_file": args.spans,
        "budgets_file": args.budgets,
        "spans": len(spans),
        "request_ledgers": len(ledgers),
        "unserved_traces": unserved,
        "skipped_traces": skipped,
        "truncated_capture": bool(header and header.get("truncated")),
        "relative_accuracy": relative_accuracy,
    }

    if not ledgers:
        report["ok"] = bool(args.allow_empty)
        msg = (f"{args.spans}: no served request traces "
               f"({len(spans)} spans, {skipped} non-request traces, "
               f"{unserved} unserved rejects/scrapes)")
        print(json.dumps(report))
        if args.report:
            with open(args.report, "w") as f:
                json.dump(report, f, indent=2, sort_keys=True)
        if args.allow_empty:
            print(f"note: {msg} — passing (--allow-empty)",
                  file=sys.stderr)
            return 0
        print(f"BUDGET GATE FAILED: {msg} — an empty gate proves nothing",
              file=sys.stderr)
        return 1

    if args.ratchet:
        tightened = ratchet(manifest, sketches, args.margin)
        with open(args.budgets, "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
            f.write("\n")
        for hop, keys in sorted(tightened.items()):
            for key, (old, new) in sorted(keys.items()):
                print(f"ratchet: {hop}.{key} {old} -> {new} ms",
                      file=sys.stderr)
        if not tightened:
            print("ratchet: nothing tightened (ceilings never loosen)",
                  file=sys.stderr)

    graded = grade(manifest, sketches)
    report.update(graded)
    print(json.dumps({
        "metric": "budget_check",
        "request_ledgers": len(ledgers),
        "ok": graded["ok"],
        "guilty": graded["guilty"],
    }))
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
    if not graded["ok"]:
        print("BUDGET GATE FAILED:", file=sys.stderr)
        for g in graded["guilty"]:
            print(f"  {g}", file=sys.stderr)
        return 1
    n = sum(1 for h in graded["hops"].values() for k in h if k != "count")
    print(f"budget gate OK: {len(ledgers)} request ledger(s) conserve, "
          f"{n} ceiling(s) hold", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
