#!/usr/bin/env python
"""Chunked-prefill interleave conformance gate (ISSUE 15).

Two modes:

  --sim    (CI fast lane) two deterministic arms of
           ``sim/scenarios.interleave_scenario`` over IDENTICAL traffic
           — a long-prompt FLASH CROWD spiking into a latency-sensitive
           interactive stream — each run TWICE for byte-identical
           reports, graded against the shrink-only
           ``tools/interleave_smoke.json`` ratchet:
             - mono:    monolithic prefill — a popped long request's
                        whole prefill runs inside its turn, stalling
                        everything behind it (head-of-line blocking).
             - chunked: the same prefill spent as budgeted chunk events
                        interleaved between decode turns (the engine's
                        token-budget scheduler, executed on the virtual
                        clock).
           The gate pins: interactive latency p50 (the sim's TTFT
           proxy — prefill head-of-line blocking is exactly what moves
           it) STRICTLY below the mono arm by the ratcheted factor, at
           equal-or-better completed volume (the tok/s proxy at fixed
           offered load), with exact request conservation and zero
           drops on both arms.
  --live   (CI full lane) a real DecodeEngine on CPU (llama_tiny)
           against the model's full forward (teacher forcing, no cache,
           no engine code): byte-identical greedy tokens over a mixed
           short+long workload, the stall bound read from the engine's
           own turn ring, `engine.turns` (never more than one budget's
           worth of chunk tokens between decode turns), zero
           client-visible errors, and page conservation after drain.

Exit: 0 conformant, 1 violation, 2 usage.

Examples:
  python tools/run_interleave_soak.py --sim
  python tools/run_interleave_soak.py --live
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RATCHET = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "interleave_smoke.json")


def _load_floors() -> dict:
    with open(RATCHET) as f:
        return json.load(f)["floors"]


def _conservation(report: dict, failures: list, arm: str) -> None:
    for name, s in report["models"].items():
        accounted = (s["completed"] + s["stale"] + s["dropped"]
                     + s["pending"])
        if s["arrivals"] != accounted:
            failures.append(
                f"{arm}/{name}: accounting leak — {s['arrivals']} "
                f"arrivals vs {accounted} accounted; a chunk backlog "
                "made requests vanish"
            )


def run_sim(seed: int = 0) -> int:
    from ray_dynamic_batching_tpu.sim import Simulation, render_json
    from ray_dynamic_batching_tpu.sim.scenarios import (
        interleave_profiles,
        interleave_scenario,
    )

    floors = _load_floors()
    failures: list = []
    arms = {}
    for arm, chunked in (("mono", False), ("chunked", True)):
        reports = [
            Simulation(
                interleave_profiles(),
                interleave_scenario(chunked=chunked, seed=seed),
            ).run()
            for _ in range(2)
        ]
        if render_json(reports[0]) != render_json(reports[1]):
            failures.append(
                f"{arm}: nondeterministic — same seed produced different "
                "report bytes"
            )
        arms[arm] = reports[0]
        _conservation(reports[0], failures, arm)
        for name, s in reports[0]["models"].items():
            if s["dropped"] != 0:
                failures.append(
                    f"{arm}/{name}: {s['dropped']} dropped request(s) — "
                    "the interleave must never shed by drop"
                )

    ia_mono = arms["mono"]["models"]["interactive"]
    ia_chunk = arms["chunked"]["models"]["interactive"]
    f = floors["interactive"]
    p50_mono = ia_mono["latency_p50_ms"]
    p50_chunk = ia_chunk["latency_p50_ms"]
    if not p50_chunk < p50_mono:
        failures.append(
            f"chunked: interactive p50 {p50_chunk:.1f} ms is not strictly "
            f"below the mono arm's {p50_mono:.1f} ms — the interleave "
            "bought nothing"
        )
    ratio = p50_mono / max(p50_chunk, 1e-9)
    if ratio < f["p50_improvement"]:
        failures.append(
            f"chunked: interactive p50 improvement only {ratio:.3f}x "
            f"(ratcheted floor {f['p50_improvement']}) — head-of-line "
            "blocking crept back"
        )
    total_mono = sum(s["completed"]
                     for s in arms["mono"]["models"].values())
    total_chunk = sum(s["completed"]
                      for s in arms["chunked"]["models"].values())
    if total_chunk < total_mono * floors["completed_ratio"]:
        failures.append(
            f"chunked: completed {total_chunk} under "
            f"{floors['completed_ratio']}x the mono arm's {total_mono} — "
            "the interleave traded throughput for latency"
        )
    if ia_chunk["slo_attainment"] < f["slo_attainment"]:
        failures.append(
            f"chunked: interactive attainment "
            f"{ia_chunk['slo_attainment']:.4f} under ratcheted floor "
            f"{f['slo_attainment']}"
        )

    summary = {
        "metric": "interleave_soak",
        "mode": "sim",
        "ok": not failures,
        "interactive_p50_ms": {"mono": p50_mono, "chunked": p50_chunk},
        "p50_improvement": round(ratio, 4),
        "completed": {"mono": total_mono, "chunked": total_chunk},
        "interactive_attainment": {
            "mono": ia_mono["slo_attainment"],
            "chunked": ia_chunk["slo_attainment"],
        },
        "violations": failures,
    }
    print(json.dumps(summary, indent=2, sort_keys=True))
    if failures:
        for v in failures:
            print(f"interleave soak FAILED: {v}", file=sys.stderr)
        return 1
    return 0


def run_live(n_long: int = 4) -> int:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
    from ray_dynamic_batching_tpu.engine.queue import RequestQueue
    from ray_dynamic_batching_tpu.engine.request import Request
    from ray_dynamic_batching_tpu.models import registry  # noqa: F401
    from ray_dynamic_batching_tpu.models.base import get_model

    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))

    def payloads():
        rng = np.random.default_rng(23)
        out = [{"tokens": rng.integers(1, 500, 5).tolist(),
                "max_new_tokens": 40}]  # the long-lived stream
        for _ in range(n_long):
            out.append({"tokens": rng.integers(1, 500, 80).tolist(),
                        "max_new_tokens": 4})
        for _ in range(3):
            out.append({"tokens": rng.integers(1, 500, 9).tolist(),
                        "max_new_tokens": 6})
        return out

    forward = jax.jit(model.apply)

    def full_forward(p):
        """Greedy continuation by the model's full forward over the
        growing sequence (right-padded to one compiled width: a causal
        model's logits at a position do not see what follows it)."""
        seq, out = list(p["tokens"]), []
        for _ in range(p["max_new_tokens"]):
            tokens = np.zeros((1, 128), np.int32)
            tokens[0, :len(seq)] = seq
            mask = (np.arange(128) < len(seq))[None].astype(np.int32)
            logits = forward(params, jnp.asarray(tokens), jnp.asarray(mask))
            out.append(int(jnp.argmax(logits[0, len(seq) - 1])))
            seq.append(out[-1])
        return tuple(out)

    queue = RequestQueue(model.name, max_len=256)
    engine = DecodeEngine(
        model, params, queue, num_slots=8, max_len=96,
        prompt_buckets=[8, 16], eos_token_id=None,
        default_max_new_tokens=8, decode_horizon=4, page_size=128,
    )
    reqs = []
    for p in payloads():
        r = Request(model=model.name, payload=dict(p), slo_ms=600_000.0)
        queue.add_request(r)
        reqs.append(r)
    engine.run_until_idle(timeout_s=600)
    served, errors = [], 0
    for r in reqs:
        try:
            served.append(tuple(r.future.result(timeout=10).tokens))
        except Exception:  # noqa: BLE001 — classification is the gate
            errors += 1
    engine._allocator.check()
    leaked = engine.num_pages - engine._allocator.free_pages

    violations = []
    if errors:
        violations.append(f"client-visible errors: {errors}")
    elif served != [full_forward(p) for p in payloads()]:
        violations.append(
            "chunked-interleaved tokens diverge from the model's full "
            "forward — the exactness contract broke end to end"
        )
    if leaked:
        violations.append(f"page leak after drain: {leaked}")
    # Stall bound from the engine's own turn ring: never more than
    # one budget of chunk tokens between decode turns.
    budget = engine.prefill_token_budget
    since_turn = 0
    worst = 0
    chunk_events = 0
    for turn in engine.turns:
        if turn.kind == "turn":
            since_turn = 0
        else:
            chunk_events += 1
            since_turn += turn.tokens
            worst = max(worst, since_turn)
    if chunk_events == 0:
        violations.append("the engine dispatched no chunk programs — "
                          "the gate exercised nothing")
    if worst > budget:
        violations.append(
            f"stall bound violated: {worst} chunk tokens between decode "
            f"turns exceeds the budget {budget}"
        )
    summary = {
        "metric": "interleave_soak",
        "mode": "live",
        "ok": not violations,
        "requests": len(served),
        "chunk_dispatches": chunk_events,
        "worst_tokens_between_turns": worst,
        "token_budget": budget,
        "violations": violations,
    }
    print(json.dumps(summary, indent=2, sort_keys=True))
    if violations:
        for v in violations:
            print(f"interleave soak FAILED: {v}", file=sys.stderr)
        return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--sim", action="store_true",
                      help="deterministic two-arm sim gate (CI fast lane)")
    mode.add_argument("--live", action="store_true",
                      help="a real engine on CPU against the model's "
                           "full forward (full lane)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.live:
        return run_live()
    return run_sim(seed=args.seed)


if __name__ == "__main__":
    sys.exit(main())
