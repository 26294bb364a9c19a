#!/usr/bin/env python
"""Post-warmup zero-recompile gate (ISSUE 20).

"No recompiles after warmup" was a comment, not a contract: one stray
bucket shape or a donation-broken cache layout re-traces mid-serving
and a 20-40s XLA stall lands on live requests. This gate makes the
contract executable on the CPU backend:

1. Build the canonical chunked-paged llama_tiny engine (the budget
   fixture's shape: num_slots=4, max_len=96, buckets [8, 16], decode
   horizon 4) and run ``warmup()`` — which brackets itself in the
   compile ledger's warmup phase and arms the steady-state mark.
2. Serve the canonical seeded segment (seed 17: bucketed single-chunk
   and over-bucket multi-chunk-train prompts, the capture fixture's
   mix) to completion.
3. Fail on ANY compile episode recorded after the steady-state mark —
   the ledger names the guilty function, shapes, and callsite.
4. Ratchet warmup's compile counts against ``tools/compile_budget.json``
   (shrink-only): a new fn or a count over budget fails; a count UNDER
   budget is a stale budget and also fails until re-ratcheted — warmup
   getting cheaper must be banked, exactly like the lint baseline.

Usage:
    python tools/check_compiles.py              # the CI gate
    python tools/check_compiles.py --ratchet    # rewrite the budget
                                                # from this run's counts
    python tools/check_compiles.py --json       # full ledger report

Exit: 0 clean, 1 on steady-state compiles / budget violations, 2 usage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The gate pins the CPU backend BEFORE jax loads: compile discipline is
# a property of the trace/lower layer, identical across backends.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET_PATH = os.path.join(REPO, "tools", "compile_budget.json")


def _serve_segment():
    """Warmup + the canonical seed-17 serving segment; returns the
    process ledger with the steady-state mark armed and the segment's
    compile history recorded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
    from ray_dynamic_batching_tpu.engine.queue import RequestQueue
    from ray_dynamic_batching_tpu.engine.request import Request
    from ray_dynamic_batching_tpu.models import registry  # noqa: F401
    from ray_dynamic_batching_tpu.models.base import get_model
    from ray_dynamic_batching_tpu.utils.compile_ledger import get_ledger

    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    queue = RequestQueue(model.name, max_len=256)
    engine = DecodeEngine(
        model, params, queue,
        num_slots=4, max_len=96, prompt_buckets=[8, 16],
        eos_token_id=None, default_max_new_tokens=8, decode_horizon=4,
        page_size=128,
    )
    ledger = get_ledger()
    engine.warmup()  # brackets the warmup phase; arms the steady mark

    rng = np.random.default_rng(17)
    reqs = []
    for i in range(10):
        # The capture fixture's mix: mostly bucketed single-chunk
        # trains, every third an over-bucket multi-chunk train.
        plen = (int(rng.integers(3, 14)) if i % 3
                else int(rng.integers(40, 70)))
        req = Request(model=model.name, payload={
            "tokens": rng.integers(1, 500, plen).tolist(),
            "max_new_tokens": 6,
        }, slo_ms=60_000.0)
        queue.add_request(req)
        reqs.append(req)
    engine.run_until_idle(timeout_s=300)
    for r in reqs:
        r.future.result(timeout=5)
    engine._allocator.check()
    return ledger


def _load_budget():
    if not os.path.exists(BUDGET_PATH):
        return None
    with open(BUDGET_PATH) as f:
        return json.load(f)


def check_budget(warmup_counts, budget) -> list:
    """Shrink-only ratchet of per-fn warmup compile counts. Returns a
    list of error strings (empty = clean)."""
    errors = []
    if budget is None:
        errors.append(
            f"no budget at {os.path.relpath(BUDGET_PATH, REPO)} — run "
            "`python tools/check_compiles.py --ratchet` to bank one"
        )
        return errors
    budgeted = budget.get("warmup_max", {})
    for fn, n in sorted(warmup_counts.items()):
        cap = budgeted.get(fn)
        if cap is None:
            errors.append(
                f"warmup compiles unbudgeted fn '{fn}' ({n} episode(s)) "
                "— a NEW compile source must be banked deliberately "
                "(--ratchet) or eliminated"
            )
        elif n > cap:
            errors.append(
                f"warmup compile count for '{fn}' grew: {n} > budget "
                f"{cap} — more shapes compiling at startup means slower "
                "cold starts; shrink the grid or re-ratchet deliberately"
            )
    for fn, cap in sorted(budgeted.items()):
        n = warmup_counts.get(fn, 0)
        if n < cap:
            errors.append(
                f"budget is stale: '{fn}' budgeted {cap} but warmup "
                f"compiled {n} — the budget may only shrink; bank the "
                "improvement with --ratchet"
            )
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ratchet", action="store_true",
                    help="rewrite tools/compile_budget.json from this "
                         "run's warmup counts")
    ap.add_argument("--json", action="store_true",
                    help="print the full ledger report")
    args = ap.parse_args(argv)

    from ray_dynamic_batching_tpu.utils.compile_ledger import PHASE_WARMUP

    ledger = _serve_segment()
    report = ledger.report()
    warmup_counts = ledger.counts(phase=PHASE_WARMUP)
    violations = ledger.violations()

    errors = []
    for v in violations:
        errors.append(
            "compile AFTER the steady-state mark: "
            f"fn={v['fn']} shapes={v.get('shapes', '')!r} "
            f"callsite={v.get('callsite', '')} "
            f"({v.get('compile_ms', 0)}ms compile) — a serving-path "
            "retrace; fix the shape/donation hazard or warm the program"
        )

    if args.ratchet:
        budget = {
            "version": 1,
            "segment": "llama_tiny chunked-paged seed-17 canonical "
                       "segment (see tools/check_compiles.py)",
            "warmup_max": {fn: n for fn, n in sorted(
                warmup_counts.items())},
        }
        with open(BUDGET_PATH, "w") as f:
            json.dump(budget, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"ratcheted {os.path.relpath(BUDGET_PATH, REPO)}: "
              f"{budget['warmup_max']}")
    else:
        errors.extend(check_budget(warmup_counts, _load_budget()))

    if args.json:
        # The report IS the stdout (consumers json.loads it);
        # verdicts go to stderr.
        print(ledger.to_json(), end="")
    if errors:
        print("COMPILE GATE FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    if not args.json:
        total = sum(warmup_counts.values())
        print(f"compile gate OK: {total} warmup episode(s) across "
              f"{len(warmup_counts)} fn(s), 0 steady-state compiles")
    return 0


if __name__ == "__main__":
    sys.exit(main())
