#!/usr/bin/env python
"""KV page fabric migration conformance gate (ISSUE 18).

Three modes:

  --sim    (CI fast lane) two deterministic arms of
           ``sim/kvfabric.run_migration_sim`` over IDENTICAL seeded
           traffic — every replica of a deployment rolled once while
           its streams are mid-decode — each arm run TWICE for
           byte-identical reports, graded against the shrink-only
           ``tools/migration_smoke.json`` ratchet:
             - drain:   the pre-fabric baseline — streams past their
                        first token at roll time are SHED (the
                        at-most-once pin forbids replay).
             - migrate: every live stream ships as a parcel to a
                        surviving replica and resumes. ZERO drops, zero
                        replays, exact token conservation, parcel
                        pauses bounded by the ratchet.
  --live   (CI full lane; run under RDB_TESTING_LOCKORDER=1) a real
           two-engine rolling update on CPU (llama_tiny, paged): decode
           a workload partway on engine A, migrate every live stream to
           engine B through the real parcel path, drain both. Gates:
           tokens byte-identical to an unmigrated straight run, zero
           client-visible errors, page conservation on both engines,
           queue books balanced through migrated_out/migrated_in.
  --bench  one migration timed against recompute-from-scratch: the
           parcel pause (freeze -> ship -> splice -> resume) vs. paying
           a fresh prefill TTFT for the same cache. Emits JSON.

Exit: 0 conformant, 1 violation, 2 usage.

Examples:
  python tools/run_migration_soak.py --sim
  RDB_TESTING_LOCKORDER=1 python tools/run_migration_soak.py --live
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RATCHET = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "migration_smoke.json")


def _load_floors() -> dict:
    with open(RATCHET) as f:
        return json.load(f)["floors"]


def run_sim(seed: int = 0) -> int:
    from ray_dynamic_batching_tpu.sim.kvfabric import (
        MigrationScenario,
        render_json,
        run_migration_sim,
    )

    floors = _load_floors()
    failures: list = []
    arms = {}
    for arm in ("drain", "migrate"):
        reports = [
            run_migration_sim(MigrationScenario(seed=seed), arm)
            for _ in range(2)
        ]
        if render_json(reports[0]) != render_json(reports[1]):
            failures.append(
                f"{arm}: nondeterministic — same seed produced different "
                "report bytes"
            )
        arms[arm] = reports[0]
        if not reports[0]["conserved"]:
            failures.append(
                f"{arm}: ledger conservation broke — "
                f"{reports[0]['arrivals']} arrivals vs "
                f"{reports[0]['completed']} completed + "
                f"{reports[0]['dropped']} dropped, tokens "
                f"{reports[0]['tokens_emitted']} vs "
                f"{reports[0]['tokens_expected']}"
            )

    mig, drn = arms["migrate"], arms["drain"]
    f = floors["migrate"]
    if mig["dropped"] > f["max_dropped"]:
        failures.append(
            f"migrate: {mig['dropped']} dropped stream(s) — the fabric "
            "arm must be zero-drop by construction"
        )
    if mig["requeued"] > f["max_requeued"]:
        failures.append(
            f"migrate: {mig['requeued']} replayed stream(s) over the "
            f"ratcheted bound {f['max_requeued']} — post-first-token "
            "work leaked into the requeue path"
        )
    if mig["migrations"] < f["min_migrations"]:
        failures.append(
            f"migrate: only {mig['migrations']} migrations "
            f"(ratcheted floor {f['min_migrations']}) — the rolling "
            "update stopped exercising the fabric"
        )
    if mig["pause_ms_mean"] > f["max_pause_ms_mean"]:
        failures.append(
            f"migrate: mean parcel pause {mig['pause_ms_mean']:.3f} ms "
            f"over the ratcheted bound {f['max_pause_ms_mean']} — "
            "parcels grew past what the courier rate justifies"
        )
    if drn["dropped"] < floors["drain"]["min_dropped"]:
        failures.append(
            f"drain: baseline arm shed only {drn['dropped']} stream(s) "
            f"(floor {floors['drain']['min_dropped']}) — the scenario "
            "no longer catches streams mid-decode, so the migrate arm's "
            "zero proves nothing"
        )

    summary = {
        "metric": "migration_soak",
        "mode": "sim",
        "ok": not failures,
        "dropped": {"drain": drn["dropped"], "migrate": mig["dropped"]},
        "requeued": {"drain": drn["requeued"],
                     "migrate": mig["requeued"]},
        "migrations": mig["migrations"],
        "parcel_mb_total": mig["parcel_mb_total"],
        "pause_ms_mean": mig["pause_ms_mean"],
        "pause_ms_max": mig["pause_ms_max"],
        "violations": failures,
    }
    print(json.dumps(summary, indent=2, sort_keys=True))
    if failures:
        for v in failures:
            print(f"migration soak FAILED: {v}", file=sys.stderr)
        return 1
    return 0


def _build_engine(model, params, name_suffix: str):
    from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
    from ray_dynamic_batching_tpu.engine.queue import RequestQueue

    queue = RequestQueue(f"{model.name}:{name_suffix}", max_len=256)
    engine = DecodeEngine(
        model, params, queue, num_slots=8, max_len=96,
        prompt_buckets=[8, 16], eos_token_id=None,
        default_max_new_tokens=8, decode_horizon=4,
        paged=True, page_size=128,
    )
    return engine, queue


def _payloads(n: int = 6):
    import numpy as np

    rng = np.random.default_rng(41)
    return [{"tokens": rng.integers(1, 500, int(rng.integers(4, 10))).tolist(),
             "max_new_tokens": 24} for _ in range(n)]


def _submit(queue, model_name, payloads):
    from ray_dynamic_batching_tpu.engine.request import Request

    reqs = []
    for p in payloads:
        r = Request(model=model_name, payload=dict(p), slo_ms=600_000.0)
        queue.add_request(r)
        reqs.append(r)
    return reqs


def _results(reqs):
    outs, errors = [], 0
    for r in reqs:
        try:
            outs.append(tuple(r.future.result(timeout=10).tokens))
        except Exception:  # noqa: BLE001 — classification is the gate
            errors += 1
            outs.append(None)
    return outs, errors


def run_live() -> int:
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.models import registry  # noqa: F401
    from ray_dynamic_batching_tpu.models.base import get_model

    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    payloads = _payloads()

    # Straight reference: the same workload, never migrated.
    ref_engine, _ = _build_engine(model, params, "ref")
    ref_reqs = _submit(ref_engine.queue, model.name, payloads)
    ref_engine.run_until_idle(timeout_s=600)
    ref_tokens, ref_errors = _results(ref_reqs)

    # Rolling-update arm: decode on A until every stream is past its
    # first token, migrate everything live to B, drain both.
    a, qa = _build_engine(model, params, "a")
    b, qb = _build_engine(model, params, "b")
    reqs = _submit(qa, model.name, payloads)
    for _ in range(40):
        a._admit()
        a._pump_prefill()
        if a._active_mask.any():
            a._step()
        if a.live_stream_ids() and not a._trains and not len(qa):
            break
    deliver = b.accept_parcel
    requested = sum(
        1 for rid in a.live_stream_ids()
        if a.request_migration(rid, deliver)
    )
    a._service_fabric()   # export + commit on the source
    b.run_until_idle(timeout_s=600)   # import + resume + finish
    a.run_until_idle(timeout_s=600)   # anything that finished pre-roll
    mig_tokens, mig_errors = _results(reqs)

    violations = []
    if ref_errors or mig_errors:
        violations.append(
            f"client-visible errors: ref={ref_errors} "
            f"migrated={mig_errors}"
        )
    if mig_tokens != ref_tokens:
        violations.append(
            "migrated tokens diverge from the straight run — mid-stream "
            "migration broke token exactness end to end"
        )
    if a.migrated_out == 0 or b.migrated_in != a.migrated_out:
        violations.append(
            f"migration accounting: src migrated_out={a.migrated_out} "
            f"dst migrated_in={b.migrated_in} (requested={requested}) — "
            "the rolling update exercised nothing or lost parcels"
        )
    for name, engine in (("a", a), ("b", b)):
        engine._allocator.check()
        leaked = engine.num_pages - engine._allocator.free_pages
        if leaked:
            violations.append(f"{name}: {leaked} page(s) leaked after "
                              "drain")
    sa, sb = qa.stats(), qb.stats()
    if sa["enqueued"] != sa["completed"] + sa.get("migrated_out", 0.0):
        violations.append(
            f"src queue books broken: enqueued {sa['enqueued']} != "
            f"completed {sa['completed']} + migrated_out "
            f"{sa.get('migrated_out', 0.0)}"
        )
    if sb.get("migrated_in", 0.0) != float(b.migrated_in) \
            or sb["completed"] < sb.get("migrated_in", 0.0):
        violations.append(
            f"dst queue books broken: migrated_in "
            f"{sb.get('migrated_in', 0.0)} vs engine {b.migrated_in}, "
            f"completed {sb['completed']}"
        )
    kinds = [e["kind"] for e in a._page_journal.snapshot()]
    if "migrate_out" not in kinds:
        violations.append("src journal has no migrate_out event")
    if "migrate_in" not in [e["kind"] for e in b._page_journal.snapshot()]:
        violations.append("dst journal has no migrate_in event")

    summary = {
        "metric": "migration_soak",
        "mode": "live",
        "ok": not violations,
        "requests": len(payloads),
        "migrated": a.migrated_out,
        "violations": violations,
    }
    print(json.dumps(summary, indent=2, sort_keys=True))
    if violations:
        for v in violations:
            print(f"migration soak FAILED: {v}", file=sys.stderr)
        return 1
    return 0


def run_bench(record_file: str = "") -> int:
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.models import registry  # noqa: F401
    from ray_dynamic_batching_tpu.models.base import get_model

    model = get_model("llama_tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    payloads = _payloads(1)

    a, qa = _build_engine(model, params, "bench_a")
    b, _ = _build_engine(model, params, "bench_b")
    reqs = _submit(qa, model.name, payloads)
    for _ in range(40):
        a._admit()
        a._pump_prefill()
        if a._active_mask.any():
            a._step()
        if a.live_stream_ids():
            break
    rid = a.live_stream_ids()[0]
    t0 = time.perf_counter()
    a.request_migration(rid, b.accept_parcel)
    a._service_fabric()
    b._service_fabric()
    pause_ms = (time.perf_counter() - t0) * 1e3

    # Recompute-from-scratch comparison: a fresh engine pays full
    # prefill TTFT for the same prompt instead of splicing pages.
    c, qc = _build_engine(model, params, "bench_c")
    creqs = _submit(qc, model.name, payloads)
    t0 = time.perf_counter()
    for _ in range(40):
        c._admit()
        c._pump_prefill()
        if any(s.generated for s in c._slots if not s.free):
            break
        if c._active_mask.any():
            c._step()
    recompute_ttft_ms = (time.perf_counter() - t0) * 1e3

    b.run_until_idle(timeout_s=600)
    a.run_until_idle(timeout_s=600)
    c.run_until_idle(timeout_s=600)
    _results(reqs)
    _results(creqs)

    out = {
        "metric": "bench_llm_migrate",
        "backend": jax.default_backend(),
        "migration_pause_ms": round(pause_ms, 2),
        "recompute_ttft_ms": round(recompute_ttft_ms, 2),
        "migrated": a.migrated_out,
    }
    print(json.dumps(out, indent=2, sort_keys=True))
    if record_file:
        with open(record_file, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
    return 0 if a.migrated_out == 1 else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--sim", action="store_true",
                      help="deterministic two-arm sim gate (CI fast lane)")
    mode.add_argument("--live", action="store_true",
                      help="real two-engine migration on CPU (full lane)")
    mode.add_argument("--bench", action="store_true",
                      help="migration pause vs recompute TTFT")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--record", default="",
                    help="write the bench JSON here too")
    args = ap.parse_args()
    if args.live:
        return run_live()
    if args.bench:
        return run_bench(record_file=args.record)
    return run_sim(seed=args.seed)


if __name__ == "__main__":
    sys.exit(main())
