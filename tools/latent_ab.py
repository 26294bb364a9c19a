"""Time the latent pool's reads on the chip at the published widths
(``ops/latent_attention.py``): a 512-row chunk's attention over 4-16k cached
positions in its two forms (keys and values EXPANDED from the latent a block
of pages at a time, the form the program keeps, against ABSORBED: the
up-projection folded into q, every head scoring the 576-wide row itself),
and the absorbed decode kernel's time a live page.

    chiprun -- python3 -m tools.latent_ab [--config xing4-29b-ep8-1chip]

Prints one line a measurement; a time is the median of ``--reps`` calls,
each ended by ``block_until_ready``. Refuses without a TPU."""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _time(fn, args, reps: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t)
    return statistics.median(took)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="xing4-29b-ep8-1chip")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--decode-only", action="store_true")
    ap.add_argument("--folds", default="", metavar="PAGES:DEPTH,..",
                    help="time the decode kernel at each (pages a fold, "
                         "ring depth) instead of the module's own")
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("no TPU: a time from this machine is not a device time",
              file=sys.stderr)
        return 2
    from ray_dynamic_batching_tpu.ops import latent_attention as la

    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / f"{a.config}.json").read_text())
    dc, llm = cfg["program"]["decoder_config"], cfg["deployment"]["llm"]
    N, rank, rope = dc["num_heads"], dc["kv_lora_rank"], dc["rope_dim"]
    nope, Hv = dc["head_dim"] - rope, dc["v_head_dim"]
    ps, NP = llm["page_size"], llm["max_len"] // llm["page_size"]
    Wp = la.row_width(rank, rope)
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    rand = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape).astype(np.float32), bf)
    w = rand(rank, N, nope + Hv) / 22.0
    scale = dc["head_dim"] ** -0.5
    print(f"device: {jax.devices()[0].device_kind}; {a.config}: {N} heads, "
          f"row {rank}+{rope} held as {Wp}, pages of {ps}", flush=True)

    # --- a chunk's rows, both forms ------------------------------------
    for B in () if a.decode_only else (1, 2):
        pool = rand(2, B * NP, ps, Wp).at[..., rank + rope:].set(0)
        table = jnp.arange(B * NP, dtype=jnp.int32).reshape(B, NP)
        T = max(llm["prompt_buckets"])
        q_n, q_r = rand(B, T, N, nope), rand(B, T, N, rope)
        q_abs = jnp.pad(
            jnp.concatenate([jnp.einsum(
                "btnh,rnh->btnr", q_n, w[..., :nope]), q_r], -1),
            ((0, 0),) * 3 + ((0, Wp - rank - rope),))
        expand = jax.jit(lambda qn, qr, p, t, n: la.expanded(
            qn, qr, p, w, t, n, 1, scale=scale))
        absorb = jax.jit(lambda q, p, t, n: jnp.einsum(
            "btnr,rnh->btnh", la.absorbed(q, p, t, n, 1, rank=rank,
                                          scale=scale), w[..., nope:]))
        for cached in (4096, 8192, 16384 - T):
            n = jnp.full((B,), cached, jnp.int32)
            e = _time(expand, (q_n, q_r, pool, table, n), a.reps)
            s = _time(absorb, (q_abs, pool, table, n), a.reps)
            gap = float(jnp.abs(
                expand(q_n, q_r, pool, table, n).astype(jnp.float32)
                - absorb(q_abs, pool, table, n).astype(jnp.float32)).max())
            print(f"chunk: {B} x {T} rows over {cached} cached positions, "
                  f"one layer: expanded {e * 1e3:.2f} ms, absorbed "
                  f"{s * 1e3:.2f} ms (outputs differ by at most {gap:.3f})",
                  flush=True)

    # --- the decode kernel against its XLA fallback ----------------------
    B = llm["num_slots"]
    pages = 2048
    pool = rand(2, pages, ps, Wp).at[..., rank + rope:].set(0)
    table = jnp.asarray(rng.integers(0, pages, size=(B, NP)), jnp.int32)
    q = rand(B, 1, N, Wp).at[..., rank + rope:].set(0)
    fallback = jax.jit(lambda q, p, t, n: la.absorbed(
        q, p, t, n, 1, rank=rank, scale=scale))
    for fold in a.folds.split(",") if a.folds else [""]:
        if fold:
            la.FOLD_PAGES, la.RING_DEPTH = (int(x) for x in fold.split(":"))
            jax.clear_caches()
        print(f"decode: {la.FOLD_PAGES} pages a fold, a ring of "
              f"{la.RING_DEPTH}", flush=True)
        kernel = jax.jit(lambda q, p, t, n: la.decode(
            q, p, t, n, 1, rank=rank, scale=scale))
        read = {}
        for cached in (127, 4096, 9400, 18000):
            n = jnp.full((B,), cached, jnp.int32)
            read[cached] = k = _time(kernel, (q, pool, table, n), a.reps)
            x = _time(fallback, (q, pool, table, n), a.reps)
            live = B * (cached // ps + 1)
            gap = float(jnp.abs(
                kernel(q, pool, table, n).astype(jnp.float32)
                - fallback(q, pool, table, n).astype(jnp.float32)).max())
            print(f"decode: {B} slots at {cached} positions ({live} live "
                  f"pages), one layer: kernel {k * 1e6:.0f} us "
                  f"({live * ps * (rank + rope) * 2 / k / 1e9:.0f} GB/s of "
                  f"true rows), XLA walk {x * 1e6:.0f} us (outputs differ "
                  f"by at most {gap:.4f})", flush=True)
        slope = (read[18000] - read[4096]) / (
            B * (18000 // ps - 4096 // ps))
        print(f"decode: a live page costs {slope * 1e6:.3f} us (its "
              f"{ps * Wp * 2} B at 819 GB/s: {ps * Wp * 2 / 819e3:.3f} us)",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
