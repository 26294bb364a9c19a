"""On-chip A/B of the Pallas decode-attention kernel vs the XLA path.

Completes VERDICT r4 #8's "measured on chip" half: the kernel is
parity-tested in interpret mode on CPU (tests/test_decode_attention.py)
and lowering-tested via cross-platform export (tests/test_tpu_lowering.py),
but whether it actually BEATS the XLA repeat path — and agrees with it
numerically under real MXU bf16 passes — can only be measured on the
device. The reference's analogous practice is committed measured latency
tables as scheduler ground truth (``293-project/profiling/*_summary.csv``).

For each serving geometry (the bench LLM row, llama-family GQA at
several capacities, a speculative window) this measures the full decode
ATTENTION substep under both backends with the profiler's timing
discipline (``profiles/profiler.py::timed_steps_ms`` — chained
dispatches, one scalar fetched at the end), checks max-abs parity
between the two backends on the same inputs, and writes one JSON record.

Usage: python tools/run_kernel_ab.py [out_dir] [--iters N]
                                     [--only tag1,tag2] [--out-name F]
Writes <out_dir>/<F> (default kernel_ab.json in profiles/tpu_v5e) and
prints one JSON summary line. ``--only`` restricts to named geometries
(a couple of geometries are ~2 compiles each — a short chip call). Exit
0 only when EVERY selected geometry succeeded on a non-CPU backend.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Geometries: (tag, B slots, Tq, N q-heads, H, S capacity, K kv-heads,
# int8_kv) — int8 rows time the quantized-cache scan (codes + scales in
# the kernel) against the XLA dequantize-then-attend fallback.
GEOMETRIES = [
    ("bench_llm_row_gpt2m", 64, 1, 16, 64, 256, 16, False),
    ("gqa_s512", 32, 1, 32, 128, 512, 8, False),
    ("gqa_s2048", 32, 1, 32, 128, 2048, 8, False),
    ("gqa_s8192", 8, 1, 32, 128, 8192, 8, False),
    ("spec_window5", 16, 5, 16, 64, 512, 8, False),
    ("bench_llm_row_int8kv", 64, 1, 16, 64, 256, 16, True),
    ("gqa_s2048_int8kv", 32, 1, 32, 128, 2048, 8, True),
]


def _time_attention(backend: str, q, k, v, mask, iters: int,
                    k_scale=None, v_scale=None):
    """Median ms/step for the dispatched attention substep."""
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.ops import attention as attn

    attn.set_attention_backend(backend)
    try:
        fn = jax.jit(
            lambda q, k, v, m: attn.dot_product_attention(
                q, k, v, mask=m, k_scale=k_scale, v_scale=v_scale)
        )
        out = fn(q, k, v, mask)
        float(jnp.sum(out.astype(jnp.float32)))  # compile + fetch
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(q, k, v, mask)
            float(jnp.sum(out.astype(jnp.float32)))  # host fetch = fence
            samples.append((time.perf_counter() - t0) * 1000.0 / iters)
        return statistics.median(samples), out
    finally:
        attn.set_attention_backend("auto")


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 and not sys.argv[1].startswith(
        "--") else os.path.join(REPO, "profiles", "tpu_v5e")
    iters = 20
    if "--iters" in sys.argv:
        iters = int(sys.argv[sys.argv.index("--iters") + 1])
    geometries = GEOMETRIES
    if "--only" in sys.argv:
        # A couple of geometries (~2 compiles each) fit a short chip
        # call; the full list is a longer one.
        tags = set(sys.argv[sys.argv.index("--only") + 1].split(","))
        geometries = [g for g in GEOMETRIES if g[0] in tags]
        if not geometries:
            # Not assert: under -O an unmatched tag would run ZERO
            # geometries, exit 0, and commit an empty record as
            # verified ground truth.
            raise SystemExit(f"--only matched nothing: {tags}")
    out_name = "kernel_ab.json"
    if "--out-name" in sys.argv:
        out_name = sys.argv[sys.argv.index("--out-name") + 1]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_dynamic_batching_tpu.models.decoder import decode_mask

    backend = jax.default_backend()
    rows = []
    for tag, B, Tq, N, H, S, K, int8_kv in geometries:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, Tq, N, H), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, K, H), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, K, H), jnp.bfloat16)
        kscale = vscale = None
        if int8_kv:
            from ray_dynamic_batching_tpu.models.decoder import (
                quantize_kv_rows,
            )

            k, kscale = quantize_kv_rows(k)
            v, vscale = quantize_kv_rows(v)
        lengths = jax.random.randint(ks[3], (B,), Tq, S - Tq)
        if Tq > 1:
            # Speculative-verify staircase: row r attends through its own
            # position base + r (the per-row windows verify_step builds).
            pos = jnp.arange(S)[None, None, None, :]
            row = jnp.arange(Tq)[None, None, :, None]
            mask = pos < (lengths[:, None, None, None] + row + 1)
        else:
            mask = decode_mask(lengths, S)
        try:
            xla_ms, xla_out = _time_attention(
                "xla", q, k, v, mask, iters,
                k_scale=kscale, v_scale=vscale)
            pl_ms, pl_out = _time_attention(
                "pallas", q, k, v, mask, iters,
                k_scale=kscale, v_scale=vscale)
            max_abs = float(
                jnp.max(jnp.abs(pl_out.astype(jnp.float32)
                                - xla_out.astype(jnp.float32)))
            )
            rows.append({
                "geometry": tag,
                "shape": {"B": B, "Tq": Tq, "N": N, "H": H, "S": S, "K": K},
                "xla_ms": round(xla_ms, 4),
                "pallas_ms": round(pl_ms, 4),
                "speedup": round(xla_ms / pl_ms, 3) if pl_ms > 0 else None,
                "max_abs_diff": max_abs,
                # bf16 has ~2-3 decimal digits; attention outputs are O(1)
                "parity_ok": max_abs < 0.1,
            })
            print(f"{tag}: xla {xla_ms:.3f} ms  pallas {pl_ms:.3f} ms  "
                  f"speedup {xla_ms / pl_ms:.2f}x  maxdiff {max_abs:.2e}",
                  file=sys.stderr, flush=True)
        except Exception as exc:  # noqa: BLE001
            rows.append({"geometry": tag, "error": repr(exc)[:500]})
            print(f"{tag}: FAILED {exc!r}", file=sys.stderr, flush=True)

    ok_rows = [r for r in rows if "error" not in r]
    record = {
        "backend": backend,
        "captured": time.strftime("%Y%m%dT%H%M%S"),
        "iters": iters,
        "rows": rows,
        "all_parity_ok": bool(ok_rows) and all(
            r["parity_ok"] for r in ok_rows),
        "median_speedup": round(statistics.median(
            [r["speedup"] for r in ok_rows]), 3) if ok_rows else None,
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, out_name)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(json.dumps({
        "metric": "decode_kernel_median_speedup_vs_xla",
        "value": record["median_speedup"],
        "unit": "x",
        "backend": backend,
        "all_parity_ok": record["all_parity_ok"],
        "rows_ok": len(ok_rows),
        "rows_total": len(rows),
    }), flush=True)
    # All-or-nothing: a partially-failed A/B must not read as if the
    # kernel were verified across the serving geometries.
    if backend == "cpu" or len(ok_rows) != len(rows):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
