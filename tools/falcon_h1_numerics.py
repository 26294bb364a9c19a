"""How far a hybrid (attention + state-space) configuration's SERVED logits
lie from its plain reference's at the published widths, kernels on and off:
the reading a tolerance rests on (``benchmark/run.py``'s comparison of margins
holds served tokens to 0.15 of the reference's top-1).

    chiprun -- python3 -m tools.falcon_h1_numerics [--seed N] [--backends auto,xla]
    JAX_PLATFORMS=cpu python3 -m tools.falcon_h1_numerics --small   # a rehearsal

The model's own programs (``prefill_chunk_paged`` with the states handed over
between two chunks, the second padded, then ``decode_step_paged``) on seeded
weights (``benchmark/weights.py`` by the configuration's view), a few slots;
the rows compared are each chunk's taken row and every decode step's, against
``benchmark/reference/<name>.py``'s one full forward: root mean square and
largest absolute gap of the logits, and the reference's own spread beside them.
Each backend's line ends with the paths its programs traced
(``attention_paths()``): the state-space mixer's decode row says ``one kernel,
the plane in place`` (``ops/ssm_update.py``) or ``in XLA``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="falcon-h1-34b-1chip")
    ap.add_argument("--seed", type=int, default=2147489999)
    ap.add_argument("--backends", default="auto,xla")
    ap.add_argument("--prompt", type=int, default=700)
    ap.add_argument("--decode", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--small", action="store_true",
                    help="tiny widths (benchmark/tests/tiny.py), any device")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, views
    from benchmark.run import model_factory
    from benchmark.weights import make_params
    from ray_dynamic_batching_tpu.ops import attention as attn_ops

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    if a.small:
        from benchmark.tests.tiny import tiny_cell

        cfg = tiny_cell(next(w["name"] for w in bench["workloads"]
                             if w["config"] == a.config)).config
        a.prompt = min(a.prompt, 90)
    else:
        if jax.devices()[0].platform != "tpu":
            print("no TPU: the published widths are read on the chip "
                  "(--small rehearses)", file=sys.stderr)
            return 1
        entry = next(c for c in bench["configs"] if c["name"] == a.config)
        cfg = json.loads((REPO / entry["file"]).read_text())
    prog, llm = cfg["program"], cfg["deployment"]["llm"]
    dtype = jnp.dtype(prog["dtype"])
    model = model_factory(prog, "numerics")(dtype=dtype)
    view, ref = views.get(cfg["view"]), reference.get(cfg["reference"])
    params = make_params(model, a.seed, dtype, getattr(view, "seeding", None))
    B, ps, W = a.slots, llm["page_size"], max(llm["prompt_buckets"])
    P, T = a.prompt, a.prompt + a.decode
    per_slot = -(-T // ps)
    rng = np.random.default_rng(a.seed)
    seqs = rng.integers(1, model.cfg.vocab_size, size=(B, T)).astype(np.int32)
    want = [np.asarray(ref.logits(view.view(params, cfg), seqs[b], cfg))
            for b in range(B)]
    print(f"reference: {B} sequences of {P} + {a.decode} tokens, chunks of "
          f"{W}; logits' spread {np.std(want[0][P - 1:]):.3f}", flush=True)
    for backend in a.backends.split(","):
        attn_ops.set_attention_backend(backend)
        attn_ops.clear_attention_paths()
        # Fresh callables a backend: jit's trace cache is keyed by the
        # function, and the backend it is traced under is not in the key
        # (one pair for both would run the FIRST backend's programs twice)
        chunk = jax.jit(
            lambda *args, **kw: model.prefill_chunk_paged(*args, **kw),
            donate_argnums=(3,))
        step = jax.jit(lambda *args: model.decode_step_paged(*args),
                       donate_argnums=(2,))
        cache = model.make_paged_cache(B, B * per_slot, ps, per_slot * ps)
        tables = np.arange(B * per_slot, dtype=np.int32).reshape(B, per_slot)
        cache = cache.replace(page_table=jnp.asarray(tables))
        sq, worst, rows = 0.0, 0.0, 0
        for start in range(0, P, W):
            take = min(W, P - start)
            width = next(w for w in sorted(llm["prompt_buckets"])
                         if w >= take)
            for r0 in range(0, B, 2):       # groups of two rows
                toks = np.zeros((2, width), np.int32)
                mask = np.zeros((2, width), np.int32)
                toks[:, :take], mask[:, :take] = (
                    seqs[r0:r0 + 2, start:start + take], 1)
                logits, cache = chunk(
                    params, jnp.asarray(toks), jnp.asarray(mask), cache,
                    jnp.asarray(tables[r0:r0 + 2]),
                    jnp.full((2,), start, jnp.int32),
                    jnp.full((2,), take - 1, jnp.int32),
                    state_slots=jnp.arange(r0, r0 + 2, dtype=jnp.int32))
                gap = np.asarray(logits, np.float32) - np.stack(
                    [want[b][start + take - 1] for b in (r0, r0 + 1)])
                sq, worst, rows = (sq + float((gap ** 2).mean(-1).sum()),
                                   max(worst, float(np.abs(gap).max())),
                                   rows + 2)
        cache = cache.replace(lengths=jnp.full((B,), P, jnp.int32))
        for t in range(P, T):
            logits, cache = step(params, jnp.asarray(seqs[:, t:t + 1]),
                                 cache, jnp.ones((B,), bool))
            gap = np.asarray(logits, np.float32) - np.stack(
                [want[b][t] for b in range(B)])
            sq, worst, rows = (sq + float((gap ** 2).mean(-1).sum()),
                               max(worst, float(np.abs(gap).max())), rows + B)
        said = sorted({r.describe() for r in attn_ops.attention_paths()})
        print(f"numerics: backend {backend}: rms {np.sqrt(sq / rows):.5f}, "
              f"largest gap {worst:.4f} over {rows} rows; paths {said}",
              flush=True)
    attn_ops.set_attention_backend("auto")
    return 0


if __name__ == "__main__":
    sys.exit(main())
