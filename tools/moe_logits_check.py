"""Served logits against the plain reference, for an expert configuration
under ``benchmark/configs/``, at the widths the file gives. By hand, on the
chip (or at ``--tiny`` widths on the CPU); outside any timed window.

    python3 -m tools.moe_logits_check --config olmoe-1b-7b-1chip --seed 7

Seeded weights (``benchmark/weights.py``) and a full batch of seeded
sequences go through the program's model as the engine drives it: prompts
prefilled through page tables in chunks whose last is padded
(``prefill_chunk_paged``'s arguments), then decoded token by token with
every slot active (``decode_step_paged``'s), teacher-forced, on the
dispatcher's own paths (paged and flash kernels, the grouped expert kernel).
A sample of the sequences goes through the reference's ONE full forward
(float32, precision "highest"), and the two are compared on LOGITS at every
position. Printed: the worst gap; the positions where the served and the
reference top-k expert SETS differ in some layer (a near-tie between the
k-th and the next gate, decided differently in bfloat16: the token then
goes through another expert, which is a different function, not an error of
arithmetic); the worst gap without those positions; and the benchmark's own
measure (``run.py::REF_TOL``): how far below the reference's top-1 the
served argmax lies in the reference's logits.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--prompt", type=int, default=200)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--sample", type=int, default=8,
                    help="sequences of the batch the reference computes")
    ap.add_argument("--tiny", action="store_true",
                    help="benchmark/tests/tiny.py's widths (CPU rehearsal)")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, views
    from benchmark.run import REF_TOL, model_factory
    from benchmark.weights import make_params
    from ray_dynamic_batching_tpu.models.decoder import PagedKVCache
    from ray_dynamic_batching_tpu.ops.attention import attention_paths
    from ray_dynamic_batching_tpu.ops.moe import moe_paths

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    if a.tiny:
        from benchmark.tests.tiny import tiny_cell

        cell = next(w["name"] for w in bench["workloads"]
                    if w["config"] == a.config)
        cfg = tiny_cell(cell).config
    else:
        entry = next(c for c in bench["configs"] if c["name"] == a.config)
        cfg = json.loads((REPO / entry["file"]).read_text())
    prog, llm = cfg["program"], cfg["deployment"]["llm"]
    dtype = jnp.dtype(prog["dtype"])
    model = model_factory(prog, "logits_check")(dtype=dtype)
    view, ref = views.get(cfg["view"]), reference.get(cfg["reference"])
    params = make_params(model, a.seed, dtype, getattr(view, "seeding", None))
    B, ps = int(llm["num_slots"]), int(llm["page_size"])
    P, W, n_dec = a.prompt, a.chunk, a.decode
    T = P + n_dec
    per_slot = -(-T // ps)
    print(f"device: {jax.devices()[0].device_kind!r}; {a.config}: "
          f"{model.cfg.num_layers} layers, {B} sequences of {P} + {n_dec} "
          f"tokens, chunks of {W}", flush=True)
    rng = np.random.default_rng(a.seed)
    seqs = rng.integers(1, model.cfg.vocab_size, size=(B, T)).astype(np.int32)
    pool = model.make_paged_cache(B, B * per_slot, ps, per_slot * ps)
    pool_k, pool_v = pool.k, pool.v
    tables = jnp.asarray(
        rng.permutation(B * per_slot).reshape(B, per_slot), jnp.int32)

    def forward(params, tokens, positions, k, v, tables, lengths):
        (logits, new), state = model.module.apply(
            params, tokens, positions, None,
            PagedKVCache(k=k, v=v, page_table=tables, lengths=lengths),
            scatter_writes=tokens.shape[1] > 1, page_table=tables,
            kv_lengths=lengths, mutable=["moe_routing"])
        # by layer NUMBER (the tree's own order is layer0, layer1, layer10..)
        picks = jnp.stack([
            state["moe_routing"][f"layer{i}"]["moe"]["top_idx"][0]
            for i in range(model.cfg.num_layers)])
        return logits.astype(jnp.float32), new.k, new.v, picks  # [L,B,T,k]

    step = jax.jit(forward, donate_argnums=(3, 4))   # the pool, in place
    sample = list(range(0, B, max(B // a.sample, 1)))[:a.sample]
    served = np.zeros((len(sample), T, model.cfg.vocab_size), np.float32)
    picked = [None] * T
    g = 4                                   # rows a chunk call
    for start in range(0, P, W):
        take = min(W, P - start)
        for r0 in range(0, B, g):
            rows = slice(r0, r0 + g)
            toks = np.zeros((g, W), np.int32)
            toks[:, :take] = seqs[rows, start:start + take]
            pos = jnp.asarray(start + np.arange(W)[None].repeat(g, 0))
            logits, pool_k, pool_v, picks = step(
                params, jnp.asarray(toks), pos, pool_k, pool_v, tables[rows],
                jnp.full((g,), start, jnp.int32))
            for i, b in enumerate(sample):
                if r0 <= b < r0 + g:
                    served[i, start:start + take] = np.asarray(
                        logits[b - r0, :take])
                    for t in range(take):
                        picked[start + t] = picked[start + t] or {}
                        picked[start + t][i] = np.asarray(
                            picks[:, b - r0, t])
    for t in range(P, T):
        lengths = jnp.full((B,), t, jnp.int32)
        logits, pool_k, pool_v, picks = step(
            params, jnp.asarray(seqs[:, t:t + 1]), lengths[:, None], pool_k,
            pool_v, tables, lengths)
        picked[t] = {i: np.asarray(picks[:, b, 0])
                     for i, b in enumerate(sample)}
        served[:, t] = np.asarray(logits[jnp.asarray(sample), 0])
    for line in sorted({f"{r.program or '<tool>'}: q{list(r.q_shape)} -> "
                        f"{r.describe()}" for r in attention_paths()}
                       | {f"<tool>: {m.rows} rows -> {m.describe()}"
                          for m in moe_paths()}):
        print("paths:", line, flush=True)

    weights = view.view(params, cfg)
    worst = worst_same = margin = 0.0
    differ = positions = 0
    by_layer = np.zeros(model.cfg.num_layers, np.int64)   # positions
    swapped = 0                                           # experts
    for i, b in enumerate(sample):
        routing = []
        want = np.asarray(ref.logits(weights, seqs[b], cfg, routing))
        routing = np.stack([np.asarray(r) for r in routing])   # [L, T, k]
        gap = np.abs(served[i] - want).max(axis=-1)            # [T]
        # experts the served top-k holds and the reference's does not
        off = np.asarray([
            [len(set(picked[t][i][layer]) - set(routing[layer, t]))
             for layer in range(routing.shape[0])] for t in range(T)])
        same = off.sum(axis=1) == 0
        by_layer += (off > 0).sum(axis=0)
        swapped += int(off.sum())
        top = want.max(axis=-1)
        got = np.take_along_axis(
            want, served[i].argmax(axis=-1)[:, None], axis=-1)[:, 0]
        positions += T
        differ += int((~same).sum())
        worst = max(worst, float(gap.max()))
        worst_same = max(worst_same, float(gap[same].max(initial=0.0)))
        margin = max(margin, float((top - got).max()))
        print(f"sequence {b}: worst gap {gap.max():.4f} (prefill "
              f"{gap[:P].max():.4f}, decode {gap[P:].max():.4f}); top-k sets "
              f"differ at {int((~same).sum())} of {T} positions; logits' "
              f"spread {want.std():.3f}", flush=True)
    k = routing.shape[-1]
    print(f"routing: of {positions} positions, those whose top-{k} set "
          f"differs from the reference's, by layer: {by_layer.tolist()}; "
          f"{swapped} of {positions * len(by_layer) * k} chosen experts "
          f"({100.0 * swapped / (positions * len(by_layer) * k):.2f}%) are "
          "not the reference's", flush=True)
    print(f"logits: {positions} positions of {len(sample)} sequences: worst "
          f"gap {worst:.4f}; top-k expert sets differ in some layer at "
          f"{differ} positions; worst gap without them {worst_same:.4f}; "
          f"served argmax below the reference's top-1 by at most "
          f"{margin:.4f} (run.py's REF_TOL {REF_TOL})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
