"""Served logits against the plain reference, for an expert configuration
under ``benchmark/configs/`` named by ``--config``, at the widths the file
gives. By hand, on the chip (or at ``--tiny`` widths on the CPU); outside any
timed window.

    python3 -m tools.moe_logits_check --config olmoe-1b-7b-1chip --seed 7
    python3 -m tools.moe_logits_check --config k-exaone-236b-ep8-1chip \
        --prompt 1100 --chunk 512 --decode 8 --sample 4

Seeded weights (``benchmark/weights.py``) and a full batch of seeded
sequences go through the program's model as the engine drives it: prompts
prefilled through page tables in chunks whose last is padded
(``prefill_chunk_paged``'s arguments), then decoded token by token with
every slot active (``decode_step_paged``'s), teacher-forced, on the
dispatcher's own paths (paged and flash kernels, the grouped expert kernel).
A sample of the sequences goes through the reference's ONE full forward
(float32, precision "highest"), and the two are compared on LOGITS at every
position. Printed: the worst gap; the positions where the served and the
reference top-k expert SETS differ in some expert layer (a near-tie between
the k-th and the next gate, decided differently in bfloat16: the token then
goes through another expert, which is a different function, not an error of
arithmetic); the worst gap without those positions; and the benchmark's own
measure (``run.py::REF_TOL``): how far below the reference's top-1 the
served argmax lies in the reference's logits.

Where the reference reports how far the held experts lie from the edge of
its chosen set (``logits(..., edges=)``: K-EXAONE's), also printed: that
distance at the positions where a swapped expert is held here, and for a row
of thresholds the share of positions the reference would excuse as undecided
(``reference_check.undecided_score_gap``) and what is left beyond ``REF_TOL``.

``--control`` serves a deliberately WRONG program against the same
reference, to show what the gap reads when something is wrong:
``drop_bias`` (the router's selection bias left out of the choice),
``drop_gate_scale`` (the gates not multiplied by the routed scaling factor),
``int8_pool`` (the KV pool in int8 codes), ``float8_reference`` (with
``--harness`` alone: the program served as it is and the REFERENCE given the
weights rounded to float8_e4m3, the nearest precision below the one the
configuration states: a comparison that passes it holds nothing; beside it
under ``--also``, the names of ``REFERENCE_CONTROLS``: a reference wrong on
purpose, e.g. ``unrotated_index_keys_reference`` for a selecting latent
configuration) or, for a model with an indexer,
``dense_attention`` (``index_topk`` as large as the cache: every query
attends every cached position, the selection switched off and nothing else).
For such a model the gap is also printed for the positions PAST
``index_topk`` alone, where the selection drops something: worst and root
mean square, the measure that tells a served path's rounding from a wrong
selection on every seed, against ``SELECTION_RMS_TOL`` (exit code 1 outside
it: what a control should give).

``--dtype float32`` runs the program itself in float32 (a CPU witness: what
is left of a gap is then arithmetic, not rounding); ``--seeding
q_norm/scale=3.0:0.3`` draws that leaf at another mean and std than the
view's (what a sharper or flatter attention does to both readings);
``--table-pages 144`` gives the slots the deployment's table width (the
programs the cell serves with).

``--harness SEED[,SEED..]`` is the HARNESS'S OWN comparison instead: the
configuration (wrong on purpose under ``--control``) deployed through
``benchmark.run.Deployed`` and judged by ``benchmark.run.reference_check``,
once a seed, each with its verdict; ``--few-programs`` warms one bucket and
one horizon (a control's programs are compiled for it alone). With
``--gap-sweep GAP[,GAP..]`` (and ``--prompt-lens`` for prompts beside the
configuration's) it reads instead what each ``undecided_score_gap`` would
excuse of the checked rows and what margin it would leave (:func:`gap_sweep`).
"""

from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
CONTROLS = ("none", "drop_bias", "drop_gate_scale", "int8_pool",
            "dense_attention", "float8_reference")
# With --harness, a hybrid (attention + state-space) configuration's: the
# program served as it is and the REFERENCE wrong on purpose, without one of
# a layer's two mixers, or with the state-space state not carried across
# the first chunk's edge (benchmark/reference/falcon_h1.py's keywords).
REFERENCE_CONTROLS = {
    "no_ssm_reference": {"without": ("ssm",)},
    "no_attention_reference": {"without": ("attention",)},
    "cut_state_reference": {"cut_state_at": 512},
    # a selecting latent configuration's (benchmark/reference/glm5.py): the
    # index keys left unrotated, a wrong indexer
    "unrotated_index_keys_reference": {"rotate_index_keys": False},
}
GAPS = (0.002, 0.004, 0.006, 2.0 ** -7, 0.012, 0.016)
# A selecting model's served logits past ``index_topk`` positions: the root
# mean square gap to the reference the served path must stay within. At the
# benchmark's seeding the served path reads 0.009-0.012 and dense attention
# in the selection's place 0.06-0.09 on every seed tried, chip and CPU
# (PERF.md, PR 35): half the tolerance and three to five times it.
SELECTION_RMS_TOL = 0.02


def seeding_with(seeding, overrides):
    """The view's seeding with ``PARENT/LEAF=MEAN:STD`` leaves drawn
    otherwise."""
    rules = {}
    for item in overrides:
        name, _, draw = item.partition("=")
        mean, _, std = draw.partition(":")
        rules[tuple(name.split("/"))] = (float(mean), float(std or 0.0))
    if not rules:
        return seeding

    def ask(names, shape):
        if tuple(names[-2:]) in rules:
            return rules[tuple(names[-2:])]
        return seeding(names, shape) if seeding else None
    return ask


def harness_checks(cfg, control: str, seeds, few_programs: bool,
                   also=()) -> int:
    """``run.py``'s deployment and its reference check, a seed at a time; a
    control changes what is SERVED and nothing the reference reads, but for
    ``float8_reference`` and ``also`` (``float8_reference`` or names of
    ``REFERENCE_CONTROLS``): those change the REFERENCE, and ``also``'s run
    one after another on the same deployment, after the control's own."""
    import copy
    import functools
    import gc
    import types

    import jax
    import numpy as np

    from benchmark import reference, run, weights

    make_params = weights.make_params
    failed = 0
    for n, seed in enumerate(seeds):
        c = copy.deepcopy(cfg)
        c["program"]["register_as"] += f"_{control}_{n}"
        llm, dc = c["deployment"]["llm"], c["program"]["decoder_config"]
        if few_programs:
            llm.update(prompt_buckets=[max(llm["prompt_buckets"])],
                       decode_horizon=1, ttft_horizon=1,
                       max_admissions_per_step=1)
        if control == "drop_gate_scale":
            dc["moe_gate_scale"] = 1.0
        elif control == "dense_attention":
            dc["index_topk"] = dc["max_seq_len"]
        elif control == "int8_pool":
            llm["quantize_kv"] = True
        biases = {}
        if control == "drop_bias":
            # served with a bias of zero; the reference keeps the one drawn
            def without(*args, **kw):
                def leaf(path, x):
                    if "selection_bias" not in jax.tree_util.keystr(path):
                        return x
                    biases[jax.tree_util.keystr(path)] = x
                    return jax.numpy.zeros_like(x)
                return jax.tree_util.tree_map_with_path(
                    leaf, make_params(*args, **kw))
            weights.make_params = without
        try:
            dep = run.Deployed(c, seed, jax.devices()[:1], {})
        finally:
            weights.make_params = make_params
        try:
            view = dep.view.view
            if biases:
                dep.view = types.SimpleNamespace(view=lambda params, config: (
                    view(jax.tree_util.tree_map_with_path(
                        lambda path, x: biases.get(
                            jax.tree_util.keystr(path), x), params), config)))
            # kept on the host, a leaf at a time: the chip does not hold
            # the weights twice beside a deployment that fills it
            served_view = dep.view
            def rounded(x):
                if not jax.numpy.issubdtype(x.dtype, jax.numpy.floating):
                    return np.asarray(x)
                f8 = lambda a: np.asarray(a.astype(  # noqa: E731
                    jax.numpy.float8_e4m3fn).astype(x.dtype))
                if not x.ndim:
                    return f8(x)
                # (a leaf of gigabytes, an untied head's, 128 MB at a time)
                step = -(-x.shape[0] // max(1, x.nbytes // 2 ** 27))
                return np.concatenate([
                    f8(x[a:a + step]) for a in range(0, x.shape[0], step)])

            float8_view = types.SimpleNamespace(view=lambda params, config: (
                jax.tree_util.tree_map(rounded, view(params, config))))
            if control == "float8_reference":
                dep.view = float8_view
            got = run.reference_check(dep, seed)
            print(f"harness: control {control} seed {seed}: ok={got['ok']} "
                  f"worst margin {got['worst_gap']:.4f} (tolerance "
                  f"{got['tol']})", flush=True)
            # ... and, on the SAME deployment, each wrong reference asked
            # for beside it (what is served does not change)
            module = reference.get(c["reference"])
            plain = module.logits
            for name in also:
                if name == "float8_reference":
                    dep.view = float8_view
                else:
                    module.logits = functools.partial(
                        plain, **REFERENCE_CONTROLS[name])
                try:
                    wrong = run.reference_check(dep, seed)
                finally:
                    module.logits, dep.view = plain, served_view
                print(f"harness: control {name} seed {seed}: "
                      f"ok={wrong['ok']} worst margin "
                      f"{wrong['worst_gap']:.4f} (tolerance {wrong['tol']})",
                      flush=True)
        finally:
            dep.close()
        failed += not got["ok"]
        del dep
        gc.collect()
        live = sum(x.nbytes for x in jax.live_arrays())
        print(f"harness: {live / 2**30:.2f} GiB of arrays alive after the "
              "deployment closed", flush=True)
    print(f"harness: control {control}: {failed} of {len(seeds)} seeds not "
          "correct", flush=True)
    return 0


def gap_sweep(cfg, seeds, gaps, prompt_lens) -> int:
    """What ``reference_check.undecided_score_gap`` excuses and what is left
    to check, a gap at a time: the configuration deployed through
    ``benchmark.run.Deployed`` once a seed, ``reference_check``'s own
    prompts (and ``prompt_lens`` more) served greedy, the reference
    teacher-forced with every row as computed and each expert layer's
    distances from the chosen set's edge (``edges=``). For each gap: the
    positions excused of those CHECKED (the served tokens' rows, all past
    the prompt), by expert layer and in all; the worst margin of the rest;
    and the same for the tokens a reference with its weights rounded to
    float8_e4m3 would have served, which a sound limit must refuse."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, run

    check = cfg["reference_check"]
    ref = reference.get(cfg["reference"])
    lens = [int(n) for n in check["prompt_lens"]] + list(prompt_lens)
    n_new = int(check["new_tokens"])
    tally = {g: dict(excused=0, rows=0, worst=0.0, low=0.0, fails=0)
             for g in gaps}
    for n, seed in enumerate(seeds):
        c = json.loads(json.dumps(cfg))
        c["program"]["register_as"] += f"_gaps_{n}"
        dep = run.Deployed(c, seed, jax.devices()[:1], {})
        read = []       # (prompt length, rows, sequence, served, logits, edge)
        try:
            weights = dep.view.view(dep.params, dep.config)
            rng = np.random.default_rng(int(seed) ^ 0x5EED)
            for L in lens:
                prompt = rng.integers(1, dep.vocab_size, size=L).tolist()
                _stream, fut = dep.submit(
                    {"tokens": prompt, "max_new_tokens": n_new})
                served = list(fut.result(timeout=600.0).tokens)
                rows = np.arange(L - 1, L - 1 + n_new)
                edges = []
                seq = (prompt + served)[:-1]
                logits = np.asarray(ref.logits(
                    weights, seq, dep.config, edges=edges))[rows]
                read.append((L, rows, seq, served, logits, np.stack(
                    [np.asarray(e) for e in edges])[:, rows]))
            # The weights again, rounded to float8_e4m3: through the host,
            # a leaf at a time (the chip does not hold them twice), and
            # put back once the deployment has let go of its own.
            low = jax.tree_util.tree_map(
                lambda x: np.asarray(
                    x.astype(jnp.float8_e4m3fn).astype(x.dtype))
                if jnp.issubdtype(x.dtype, jnp.floating) else np.asarray(x),
                weights)
            config = dep.config
        finally:
            dep.close()
        del dep, weights
        gc.collect()
        live = sum(x.nbytes for x in jax.live_arrays())
        print(f"gaps: {live / 2**30:.2f} GiB of arrays alive after the "
              "deployment closed", flush=True)
        low = jax.device_put(low)
        for L, rows, seq, served, logits, edge in read:
            lowered = np.asarray(ref.logits(
                low, seq, config, edges=[]))[rows].argmax(-1)
            top = logits.max(-1)
            margin = top - logits[np.arange(n_new), served]
            low_margin = top - logits[np.arange(n_new), lowered]
            for g in gaps:
                excused = edge.min(0) < g
                left = ~excused
                worst = float(margin[left].max()) if left.any() else 0.0
                worst_low = float(
                    low_margin[left].max()) if left.any() else 0.0
                t = tally[g]
                t["excused"] += int(excused.sum())
                t["rows"] += n_new
                t["worst"] = max(t["worst"], worst)
                t["low"] = max(t["low"], worst_low)
                t["fails"] += worst > run.REF_TOL
                by_layer = " ".join(f"{int((e < g).sum())}" for e in edge)
                print(f"gaps: seed {seed} prompt {L} (checked rows "
                      f"{rows[0]}-{rows[-1]}) gap {g:g}: excused "
                      f"{int(excused.sum())} of {n_new} (by expert layer "
                      f"{by_layer}); worst margin of the rest "
                      f"{worst:.4f}; float8 reference's tokens "
                      f"{worst_low:.4f} (tolerance {run.REF_TOL})",
                      flush=True)
        del low
        gc.collect()
    for g, t in tally.items():
        print(f"gaps: gap {g:g}: excused {t['excused']} of {t['rows']} "
              f"checked rows; worst margin served {t['worst']:.4f}, "
              f"{t['fails']} prompts not correct; worst margin of the float8 "
              f"reference's tokens {t['low']:.4f}", flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--prompt", type=int, default=200)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--sample", type=int, default=8,
                    help="sequences of the batch the reference computes")
    ap.add_argument("--slots", type=int, default=0,
                    help="sequences in the batch (default: the "
                         "configuration's slots)")
    ap.add_argument("--tiny", action="store_true",
                    help="benchmark/tests/tiny.py's widths (CPU rehearsal)")
    ap.add_argument("--control", default="none", choices=CONTROLS)
    ap.add_argument("--harness", default="", metavar="SEEDS",
                    help="run.py's own reference_check, once a seed")
    ap.add_argument("--few-programs", action="store_true")
    ap.add_argument("--also", default="", metavar="CONTROLS",
                    help="with --harness: wrong REFERENCES checked on the "
                         "same deployment (names of REFERENCE_CONTROLS)")
    ap.add_argument("--gap-sweep", default="", metavar="GAPS",
                    help="with --harness: what each undecided_score_gap "
                         "excuses and leaves, once a seed")
    ap.add_argument("--prompt-lens", default="", metavar="LENS",
                    help="with --gap-sweep: prompts beside the "
                         "configuration's own")
    ap.add_argument("--dtype", default="",
                    help="the program's type (default: the configuration's)")
    ap.add_argument("--seeding", action="append", default=[],
                    metavar="PARENT/LEAF=MEAN:STD")
    ap.add_argument("--table-pages", type=int, default=0,
                    help="table columns a slot (default: what the "
                         "sequences need)")
    a = ap.parse_args()

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, views
    from benchmark.run import REF_TOL, model_factory
    from benchmark.weights import make_params
    from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
    from ray_dynamic_batching_tpu.ops.attention import attention_paths
    from ray_dynamic_batching_tpu.ops.moe import moe_paths

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    if a.tiny:
        from benchmark.tests.tiny import tiny_cell

        cell = next(w["name"] for w in bench["workloads"]
                    if w["config"] == a.config)
        cfg = tiny_cell(cell).config
        # tiny.py cuts max_len to 256 and leaves the check's prompts
        cfg["reference_check"]["prompt_lens"] = [
            min(n, 150 + 80 * i) for i, n in enumerate(
                cfg["reference_check"]["prompt_lens"])]
    else:
        entry = next(c for c in bench["configs"] if c["name"] == a.config)
        cfg = json.loads((REPO / entry["file"]).read_text())
    if a.harness and a.gap_sweep:
        return gap_sweep(
            cfg, [int(x) for x in a.harness.split(",")],
            [float(x) for x in a.gap_sweep.split(",")],
            [int(x) for x in a.prompt_lens.split(",") if x])
    if a.harness:
        return harness_checks(cfg, a.control,
                              [int(x) for x in a.harness.split(",")],
                              a.few_programs,
                              [x for x in a.also.split(",") if x])
    prog, llm = cfg["program"], cfg["deployment"]["llm"]
    dtype = jnp.dtype(a.dtype or prog["dtype"])
    model = model_factory(prog, "logits_check")(dtype=dtype)
    view, ref = views.get(cfg["view"]), reference.get(cfg["reference"])
    params = make_params(model, a.seed, dtype, seeding_with(
        getattr(view, "seeding", None), a.seeding))
    if a.control == "drop_bias":
        model = CausalLM(dataclasses.replace(
            model.cfg, moe_selection_bias=False), name="no_bias", dtype=dtype)
    elif a.control == "drop_gate_scale":
        model = CausalLM(dataclasses.replace(
            model.cfg, moe_gate_scale=1.0), name="no_scale", dtype=dtype)
    elif a.control == "int8_pool":
        model = CausalLM(model.cfg, name="int8_pool", dtype=dtype,
                         kv_dtype=jnp.int8)
    elif a.control == "dense_attention":
        model = CausalLM(dataclasses.replace(
            model.cfg, index_topk=model.cfg.max_seq_len), name="dense",
            dtype=dtype)
    sparse = [i for i in range(model.cfg.num_layers)
              if model.cfg.layer_kind(i).sparse]
    B, ps = a.slots or int(llm["num_slots"]), int(llm["page_size"])
    P, W, n_dec = a.prompt, a.chunk, a.decode
    T = P + n_dec
    per_slot = max(-(-T // ps), a.table_pages)
    print(f"device: {jax.devices()[0].device_kind!r}; {a.config}: "
          f"{model.cfg.num_layers} layers ({len(sparse)} of experts), {B} "
          f"sequences of {P} + {n_dec} tokens, chunks of {W}; control "
          f"{a.control}", flush=True)
    rng = np.random.default_rng(a.seed)
    seqs = rng.integers(1, model.cfg.vocab_size, size=(B, T)).astype(np.int32)
    pool = model.make_paged_cache(B, B * per_slot, ps, per_slot * ps,
                                  widest_chunk=W)
    tables = jnp.asarray(
        rng.permutation(B * per_slot).reshape(B, per_slot), jnp.int32)

    def forward(params, tokens, positions, pool, tables, lengths):
        (logits, new), state = model.module.apply(
            params, tokens, positions, None,
            pool.replace(page_table=tables, lengths=lengths),
            scatter_writes=tokens.shape[1] > 1, page_table=tables,
            kv_lengths=lengths, mutable=["moe_routing"])
        # the expert layers, by layer NUMBER (the tree's own order is
        # layer0, layer1, layer10..)
        picks = jnp.stack([
            state["moe_routing"][f"layer{i}"]["moe"]["top_idx"][0]
            for i in sparse])
        return (logits.astype(jnp.float32),
                new.replace(page_table=pool.page_table,
                            lengths=pool.lengths), picks)   # [L, B, T, k]

    step = jax.jit(forward, donate_argnums=(3,))   # the pool, in place
    sample = list(range(0, B, max(B // a.sample, 1)))[:a.sample]
    served = np.zeros((len(sample), T, model.cfg.vocab_size), np.float32)
    picked = [None] * T
    g = min(4, B)                           # rows a chunk call
    for start in range(0, P, W):
        take = min(W, P - start)
        for r0 in range(0, B, g):
            rows = slice(r0, r0 + g)
            toks = np.zeros((g, W), np.int32)
            toks[:, :take] = seqs[rows, start:start + take]
            pos = jnp.asarray(start + np.arange(W)[None].repeat(g, 0))
            logits, pool, picks = step(
                params, jnp.asarray(toks), pos, pool, tables[rows],
                jnp.full((g,), start, jnp.int32))
            for i, b in enumerate(sample):
                if r0 <= b < r0 + g:
                    served[i, start:start + take] = np.asarray(
                        logits[b - r0, :take])
                    for t in range(take):
                        picked[start + t] = picked[start + t] or {}
                        picked[start + t][i] = np.asarray(
                            picks[:, b - r0, t])
    load = []   # a decode step: held share of its pairs, held experts hit
    for t in range(P, T):
        lengths = jnp.full((B,), t, jnp.int32)
        logits, pool, picks = step(
            params, jnp.asarray(seqs[:, t:t + 1]), lengths[:, None], pool,
            tables, lengths)
        picked[t] = {i: np.asarray(picks[:, b, 0])
                     for i, b in enumerate(sample)}
        allp = np.asarray(picks)[:, :, 0]                  # [L, B, k]
        lo = model.cfg.moe_first_expert
        mine = (allp >= lo) & (allp < lo + model.cfg.held_experts)
        load.append((float(mine.mean()), float(np.mean(
            [len(set(allp[layer][mine[layer]])) for layer in
             range(len(sparse))]))))
        served[:, t] = np.asarray(logits[jnp.asarray(sample), 0])
    for line in sorted({f"{r.program or '<tool>'}: q{list(r.q_shape)} -> "
                        f"{r.describe()}" for r in attention_paths()}
                       | {f"<tool>: {m.rows} rows -> {m.describe()}"
                          for m in moe_paths()}):
        print("paths:", line, flush=True)

    if load:
        print(f"load: over {len(load)} decode steps of {B} rows, "
              f"{100.0 * np.mean([s for s, _ in load]):.2f}% of the pairs "
              f"land on the {model.cfg.held_experts} held experts, "
              f"{np.mean([h for _, h in load]):.2f} of which a layer a "
              "step draws a row", flush=True)
    weights = view.view(params, cfg)
    worst = worst_same = margin = 0.0
    differ = positions = 0
    by_layer = np.zeros(len(sparse), np.int64)            # positions
    swapped = 0                                           # experts
    first, held = model.cfg.moe_first_expert, model.cfg.held_experts
    beyond = beyond_here = differ_here = 0   # positions past REF_TOL
    has_edges = "edges" in inspect.signature(ref.logits).parameters
    swapped_at = []    # positions with a held expert swapped: the least
    # distance of the reference's held experts from its edge, over layers
    by_gap = {g: [0, 0, 0] for g in GAPS}   # positions: excused; of the
    # others: beyond REF_TOL, with a held expert swapped
    edge = int(prog["decoder_config"].get("index_topk", 0))
    selection_ok = True
    past = []     # squared gaps at the positions past the selection's edge
    for i, b in enumerate(sample):
        routing, edges = [], []
        want = np.asarray(ref.logits(
            weights, seqs[b], cfg, routing,
            **({"edges": edges} if has_edges else {})))
        routing = np.stack([np.asarray(r) for r in routing])   # [L, T, k]
        gap = np.abs(served[i] - want).max(axis=-1)            # [T]
        # experts the served top-k holds and the reference's does not
        off = np.asarray([
            [len(set(picked[t][i][layer]) - set(routing[layer, t]))
             for layer in range(routing.shape[0])] for t in range(T)])
        same = off.sum(axis=1) == 0
        # ... and positions where a swapped expert (in or out) is HELD here:
        # only those change what this rank computes
        here = np.asarray([any(
            first <= e < first + held
            for layer in range(routing.shape[0])
            for e in set(picked[t][i][layer]) ^ set(routing[layer, t]))
            for t in range(T)])
        by_layer += (off > 0).sum(axis=0)
        swapped += int(off.sum())
        top = want.max(axis=-1)
        got = np.take_along_axis(
            want, served[i].argmax(axis=-1)[:, None], axis=-1)[:, 0]
        if has_edges:
            edges = np.stack([np.asarray(e) for e in edges])    # [L, T]
            here_at = np.asarray([[any(
                first <= e < first + held
                for e in set(picked[t][i][layer]) ^ set(routing[layer, t]))
                for t in range(T)] for layer in range(routing.shape[0])])
            swapped_at.extend(edges.min(axis=0)[here_at.any(axis=0)].tolist())
            for g, row in by_gap.items():
                excused = edges.min(axis=0) < g
                row[0] += int(excused.sum())
                row[1] += int(((top - got > REF_TOL) & ~excused).sum())
                row[2] += int((here_at.any(axis=0) & ~excused).sum())
        positions += T
        differ += int((~same).sum())
        worst = max(worst, float(gap.max()))
        worst_same = max(worst_same, float(gap[same].max(initial=0.0)))
        margin = max(margin, float((top - got).max()))
        if 0 < edge < T:
            past.append((served[i, edge:] - want[edge:]) ** 2)
            print(f"sequence {b}: past position {edge}: served argmax below "
                  f"the reference's top-1 by at most "
                  f"{(top - got)[edge:].max():.4f}; the reference's top-1 "
                  "leads its second by a median "
                  f"{np.median(top[edge:] - np.sort(want[edge:], -1)[:, -2]):.4f}",
                  flush=True)
        beyond += int((top - got > REF_TOL).sum())
        beyond_here += int(((top - got > REF_TOL) & here).sum())
        differ_here += int(here.sum())
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
          f"{margin:.4f} (run.py's REF_TOL {REF_TOL}); beyond it at "
          f"{beyond} positions ({100.0 * beyond / positions:.3f}%), "
          f"{beyond_here} of them among the {differ_here} where a swapped "
          "expert is held here", flush=True)
    if past:
        sq = np.concatenate(past)
        print(f"selection: the {sq.shape[0]} positions past {edge}, where a "
              f"query keeps {edge} of its cached positions: worst gap "
              f"{np.sqrt(sq.max()):.4f}, root mean square gap "
              f"{np.sqrt(sq.mean()):.5f} (control {a.control}, program in "
              f"{dtype.name}): "
              + ("WITHIN" if np.sqrt(sq.mean()) <= SELECTION_RMS_TOL
                 else "OUTSIDE") + f" the tolerance {SELECTION_RMS_TOL}",
              flush=True)
        selection_ok = bool(np.sqrt(sq.mean()) <= SELECTION_RMS_TOL)
    if swapped_at:
        q = np.quantile(swapped_at, [0.5, 0.9, 0.99, 1.0])
        print(f"undecided: at the {len(swapped_at)} positions where a swapped "
              "expert is held here, the reference's held experts lay this "
              f"near the chosen set's edge in some layer: median {q[0]:.5f}, "
              f"p90 {q[1]:.5f}, p99 {q[2]:.5f}, most {q[3]:.5f} (a gap above "
              "the most excuses them all)", flush=True)
    for g, (excused, left, swaps) in by_gap.items():
        if has_edges:
            print(f"undecided: gap {g:.5f}: {excused} of {positions} "
                  f"positions excused ({100.0 * excused / positions:.1f}%); "
                  f"of the others {left} beyond REF_TOL and {swaps} with a "
                  "swapped expert held here", flush=True)
    return 0 if selection_ok else 1


if __name__ == "__main__":
    sys.exit(main())
