"""LFM2's served bfloat16 path against the float32 reference at the published
widths, by DRAW OF THE WEIGHTS: what ``benchmark/views/lfm2.py``'s two draws
and ``reference_check.undecided_score_gap`` rest on (PERF.md, PR 50, second
round). By hand, on the chip (``--small`` rehearses the code on the CPU at
d 256, 8 layers); outside any timed window.

    python3 -m tools.lfm2_numerics --seed 7 --variants 1:1:1,0.25:0.25:1.6

A variant is ``conv_out:experts:qk[:o]``: the shares of their fan-in rule at
which the layers from ``views.lfm2.LATER_FROM`` on draw ``conv_out``, the
experts' ``wo`` and the attention layers' ``o`` (default: ``conv_out``'s),
and the mean of the q/k norms' gains. For each, seeded sequences go through
the engine's own ``prefill_chunk_paged`` (chunks of 512, the last padded)
and ``decode_step_paged`` (teacher-forced); the chunk program's last row and
every decode row are compared with ``benchmark/reference/lfm2.py`` on
LOGITS: their rms distance, the served argmax's margin under the reference's
best (``run.py::REF_TOL``), what each undecided gap leaves; and CONTROLS by
the reference alone: the tokens a reference WITHOUT attention, with the v
heads in the other order, without experts, without the selection bias, with
the taps reversed or in float8 weights would serve, and how many of them lie
beyond the tolerance (what a run's comparison of margins can see).

For the FIRST variant also (``--skip off,swap,cpu`` leaves them out): the
same weights with every Pallas kernel off (XLA paths on the chip), through
the plain path (no cache), in bfloat16 on the machine's CPU, and with every
conv layer an attention layer.

``--probe``: the decode step alone at the deployment's slots, 1,500
positions a slot: ms a substep with the dispatches chained (the device's
time) and with a sync after every step (plus one launch-and-wait round
trip). Run it in several fresh processes to see what moves between them.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
CONFIG = REPO / "benchmark" / "configs" / "lfm2-24b-a2b-ep8-1chip.json"
GAPS = (2.0 ** -7, 2.0 ** -8, 2.0 ** -9, 2.0 ** -10)


def load_config(small: bool):
    cfg = json.loads(CONFIG.read_text())
    if small:
        cfg["program"]["decoder_config"].update(
            vocab_size=4096, d_model=256, num_layers=8, num_heads=4,
            num_kv_heads=1, mlp_dim=128, dense_mlp_dim=512, max_seq_len=1024)
        cfg.update(num_attention_heads=4, num_key_value_heads=1,
                   layer_types=cfg["layer_types"][:8])
    # every row as computed: the excusing is done here, a gap at a time
    cfg["reference_check"].pop("undecided_score_gap", None)
    return cfg


def seeding_for(view, conv_out, experts, qk, o=None):
    """The view's seeding with its two draws set otherwise."""
    o = conv_out if o is None else o

    def ask(names, shape):
        later = view._layer(names) >= view.LATER_FROM
        if names[-2] == "moe" and names[-1] == "wo":
            return (0.0, (experts if later else 1.0) / math.sqrt(shape[1]))
        if names[-2] in ("conv_out", "o") and names[-1] == "kernel":
            share = conv_out if names[-2] == "conv_out" else o
            return (0.0, (share if later else 1.0)
                    / math.sqrt(math.prod(shape[:-1])))
        if names[-1] == "scale" and names[-2] in ("q_norm", "k_norm"):
            return (qk, 0.1)
        return view.seeding(names, shape)
    return ask


def probe(cfg, small: bool) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import views
    from benchmark.run import model_factory
    from benchmark.weights import make_params

    llm = cfg["deployment"]["llm"]
    B, ps = (4 if small else llm["num_slots"]), llm["page_size"]
    per_slot = 8 if small else llm["max_len"] // ps
    pages = B * per_slot if small else llm["kv_pool_pages"]
    start, N = (200, 16) if small else (1500, 200)
    model = model_factory(cfg["program"], "probe")(dtype=jnp.bfloat16)
    t0 = time.time()
    params = make_params(model, 2150012001, jnp.bfloat16,
                         views.get(cfg["view"]).seeding)
    rng = np.random.default_rng(1)
    tables = rng.permutation(B * per_slot).reshape(B, per_slot) % pages
    pool = model.make_paged_cache(
        B, pages, ps, per_slot * ps,
        widest_chunk=max(llm["prompt_buckets"])).replace(
        page_table=jnp.asarray(tables, jnp.int32))
    step = jax.jit(lambda p, t, c: model.decode_step_paged(
        p, t, c, jnp.ones((B,), bool))[:2], donate_argnums=(2,))
    toks = jnp.asarray(rng.integers(1, 1000, size=(B, 1)), jnp.int32)

    def run(n, sync):
        nonlocal pool
        pool = pool.replace(lengths=jnp.full((B,), start, jnp.int32))
        t = time.perf_counter()
        for _ in range(n):
            logits, pool = step(params, toks, pool)
            if sync:
                logits.block_until_ready()
        logits.block_until_ready()
        return 1000.0 * (time.perf_counter() - t) / n

    run(1, True)
    setup = time.time() - t0
    chained, synced = zip(*[(run(N, False), run(N // 2, True))
                            for _ in range(4)])
    print(f"probe: {jax.devices()[0].device_kind!r} set up in {setup:.1f}s; "
          f"{N} steps x 4: chained ms/substep "
          + " ".join(f"{x:.3f}" for x in chained) + "; synced ms/substep "
          + " ".join(f"{x:.3f}" for x in synced), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2150009001)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--prompt", type=int, default=1100)
    ap.add_argument("--decode", type=int, default=48)
    ap.add_argument("--variants", default="1:1:1,0.25:0.25:1.6")
    ap.add_argument("--skip", default="",
                    help="of controls,f8,off,swap,cpu")
    ap.add_argument("--probe", action="store_true")
    a = ap.parse_args()
    cfg = load_config(a.small)
    if a.probe:
        return probe(cfg, a.small)

    import traceback

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, views
    from benchmark.run import REF_TOL, model_factory
    from benchmark.weights import make_params
    from ray_dynamic_batching_tpu.ops import attention as att
    from ray_dynamic_batching_tpu.ops import moe as moeops

    def say(*xs):
        print(" ".join(str(x) for x in xs), flush=True)

    prog, skip = cfg["program"], set(a.skip.split(","))
    view, ref = views.get(cfg["view"]), reference.get(cfg["reference"])
    dtype = jnp.bfloat16
    model = model_factory(prog, "numerics")(dtype=dtype)
    B, P, n_dec, ps = a.slots, a.prompt, a.decode, 128
    W = 128 if a.small else 512
    T, V = P + n_dec, model.cfg.vocab_size
    per_slot = -(-T // ps)
    say(f"device {jax.devices()[0].device_kind!r}; {model.cfg.num_layers} "
        f"layers d {model.cfg.d_model}; {B} sequences of {P} + {n_dec}; "
        f"seed {a.seed}")
    rng = np.random.default_rng(a.seed)
    seqs = rng.integers(1, V, size=(B, T)).astype(np.int32)
    tables = rng.permutation(B * per_slot).reshape(B, per_slot).astype(
        np.int32)
    CHECK = np.arange(P - 1, T)     # the reference's rows `serve` returns

    def programs():
        g = 2

        def chunk(params, toks, mask, pool, tabs, starts, take, slots):
            return model.prefill_chunk_paged(
                params, toks, mask, pool, tabs, starts, take,
                state_slots=slots)[:2]

        def dec(params, toks, pool):
            return model.decode_step_paged(
                params, toks, pool, jnp.ones((B,), bool))[:2]

        cj = jax.jit(chunk, donate_argnums=(3,))
        dj = jax.jit(dec, donate_argnums=(2,))

        def serve(params):
            """[B, 1 + n_dec, V] float32: the chunk program's logits at the
            prompt's last row, then a decode step a token."""
            pool = model.make_paged_cache(
                B, B * per_slot, ps, per_slot * ps, widest_chunk=W).replace(
                page_table=jnp.asarray(tables))
            rows = np.zeros((B, 1 + n_dec, V), np.float32)
            for start in range(0, P, W):
                take = min(W, P - start)
                for r0 in range(0, B, g):
                    toks = np.zeros((g, W), np.int32)
                    toks[:, :take] = seqs[r0:r0 + g, start:start + take]
                    mask = np.zeros((g, W), np.int32)
                    mask[:, :take] = 1
                    logits, pool = cj(
                        params, jnp.asarray(toks), jnp.asarray(mask), pool,
                        jnp.asarray(tables[r0:r0 + g]),
                        jnp.full((g,), start, jnp.int32),
                        jnp.full((g,), take - 1, jnp.int32),
                        jnp.arange(r0, r0 + g, dtype=jnp.int32))
                    if start + take == P:
                        rows[r0:r0 + g, 0] = np.asarray(
                            logits.astype(jnp.float32))
            pool = pool.replace(lengths=jnp.full((B,), P, jnp.int32))
            for t in range(P, T):
                logits, pool = dj(params, jnp.asarray(seqs[:, t:t + 1]), pool)
                rows[:, 1 + t - P] = np.asarray(logits.astype(jnp.float32))
            return rows
        return serve

    def reference_rows(weights, sizes, which):
        outs, edges_all = [], []
        for b in which:
            edges = []
            outs.append(np.asarray(ref.logits(
                weights, seqs[b], sizes, edges=edges))[CHECK])
            edges_all.append(np.stack(
                [np.asarray(e) for e in edges])[:, CHECK].min(0)
                if edges else None)
        return np.stack(outs), edges_all

    def margins(want, tokens):
        return want.max(-1) - np.take_along_axis(
            want, tokens[..., None], -1)[..., 0]

    def report(tag, served, want, near):
        n, R, _ = want.shape
        margin = margins(want, served.argmax(-1))
        lead = want.max(-1) - np.sort(want, -1)[..., -2]
        say(f"{tag}: {n * R} rows: logits std {want.std():.3f}; |served - "
            f"reference| rms "
            f"{float(np.sqrt(((served - want) ** 2).mean())):.4f}; margin "
            f"worst {margin.max():.4f} p99 {np.percentile(margin, 99):.4f}; "
            f"rows beyond {REF_TOL}: {int((margin > REF_TOL).sum())}; served "
            f"token is the reference's best at "
            f"{100 * np.mean(margin == 0):.1f}%; its lead over its second: "
            f"median {np.median(lead):.3f}; first-token rows' worst "
            f"{margin[:, 0].max():.4f}, decode rows' {margin[:, 1:].max():.4f}")
        if near is not None:
            for g in GAPS:
                left = near >= g
                say(f"   gap {g:.5f}: rows left {int(left.sum())} of {n * R}; "
                    "worst margin of them "
                    f"{float(margin[left].max()) if left.any() else 0.0:.4f}")

    def plain(model_, params, toks):
        t = jnp.asarray(toks)
        pos = jnp.broadcast_to(jnp.arange(t.shape[1])[None], t.shape)
        return model_.module.apply(
            params, t, pos, None,
            token_mask=jnp.ones_like(t))[0].astype(jnp.float32)

    t00 = time.time()
    serve_on = programs()
    everyone, four, two = list(range(B)), list(range(min(B, 4))), [0, 1]
    variants = [tuple(float(x) for x in spec.split(":"))
                for spec in a.variants.split(",")]
    for n_variant, (conv_out, experts, qk, *o) in enumerate(variants):
        draw = seeding_for(view, conv_out, experts, qk, *o)
        params = make_params(model, a.seed, dtype, draw)
        served = serve_on(params)
        weights = view.view(params, cfg)
        want, near = reference_rows(weights, cfg, everyone)
        near = np.stack(near)
        say(f"== variant conv_out {conv_out} experts {experts} o "
            f"{o[0] if o else conv_out} qk {qk}")
        report("  served (kernels on)", served, want, near)
        left = near[:len(four)] >= 2.0 ** -8

        def control(name, tokens):
            m = margins(want[:len(four)], tokens)
            say(f"   control {name}: margin worst {m.max():.3f} median "
                f"{np.median(m):.3f}; rows beyond {REF_TOL}: "
                f"{100 * np.mean(m > REF_TOL):.1f}% of {m.size}; of the "
                f"{int(left.sum())} rows a gap 2^-8 leaves: "
                f"{100 * np.mean(m[left] > REF_TOL):.1f}%")

        def edited(key, fn):
            return {**weights, "layers": [
                {**layer, key: fn(layer[key])} if key in layer else layer
                for layer in weights["layers"]]}

        if "controls" not in skip:
            for name, w2, sizes in (
                    ("no attention (o = 0)",
                     edited("wo", jnp.zeros_like), cfg),
                    ("v heads in the other order",
                     edited("wv", lambda x: x[:, ::-1]), cfg),
                    ("no experts (scaling factor 0)", weights,
                     {**cfg, "routed_scaling_factor": 0.0}),
                    ("no selection bias",
                     edited("router_bias", jnp.zeros_like), cfg),
                    ("taps in the other order",
                     edited("taps", lambda x: x[::-1]), cfg)):
                control(name, reference_rows(w2, sizes, four)[0].argmax(-1))
        if n_variant == 0 and "off" not in skip:
            try:
                att.set_attention_backend("xla")
                moeops.set_moe_backend("xla")
                off = programs()(params)
                report("  served (kernels OFF: XLA paths)", off, want, near)
                say("   kernels on against off: rms "
                    f"{float(np.sqrt(((served - off) ** 2).mean())):.4f}")
            except Exception:
                say("kernels-off witness failed:\n" + traceback.format_exc())
            finally:
                att.set_attention_backend("auto")
                moeops.set_moe_backend("auto")
        if "f8" not in skip:
            # eager, a leaf at a time through the host: inside ONE jit XLA
            # drops the pair of converts (excess precision) and nothing is
            # rounded; and the chip does not hold the weights twice
            low = jax.tree_util.tree_map(
                lambda x: np.asarray(x.astype(jnp.float8_e4m3fn).astype(
                    x.dtype)) if jnp.issubdtype(x.dtype, jnp.floating)
                else np.asarray(x), weights)
            del weights, params
            low = jax.device_put(low)
            control("float8 weights",
                    reference_rows(low, cfg, four)[0].argmax(-1))
            del low
        params = weights = None
        say(f"   ({time.time() - t00:.0f}s so far)")

    # the first variant's draw through the plain path: as drawn, on the
    # machine's CPU, and with every conv layer an attention layer
    kept = {}

    def cpu_witness(p2):
        try:
            cpu = jax.devices("cpu")[0]
            att.set_attention_backend("xla")
            moeops.set_moe_backend("xla")
            p3 = jax.device_put(p2, cpu)
            with jax.default_device(cpu):
                m3 = model_factory(prog, "plaincpu")(dtype=dtype)
                got = np.asarray(jax.jit(lambda p, t: plain(m3, p, t))(
                    p3, seqs[:1]))[:, CHECK]
            report("== CPU bfloat16 witness (the chip's weights, plain "
                   "path, XLA:CPU) against the chip's float32 reference",
                   got, kept["want"][:1], None)
            say("   chip plain against cpu plain: rms "
                f"{float(np.sqrt(((kept['chip'][:1] - got) ** 2).mean())):.4f}")
        except Exception:
            say("cpu witness failed:\n" + traceback.format_exc())
        finally:
            att.set_attention_backend("auto")
            moeops.set_moe_backend("auto")

    if "swap" not in skip:
        for label, swap in (("as drawn (CCGC)", False),
                            ("every layer attention (GGGG)", True)):
            dc, sizes = dict(prog["decoder_config"]), dict(cfg)
            if swap:
                dc.update(layer_pattern="G", conv_kernel=0)
                sizes["layer_types"] = ["full_attention"] * len(
                    cfg["layer_types"])
            m2 = model_factory({**prog, "decoder_config": dc},
                               "plain" + "G" * swap)(dtype=dtype)
            p2 = make_params(m2, a.seed, dtype,
                             seeding_for(view, *variants[0]))
            fwd = jax.jit(lambda p, t, m2=m2: plain(m2, p, t))
            got = np.stack([np.asarray(fwd(p2, seqs[b:b + 1]))[0][CHECK]
                            for b in two])
            w2 = view.view(
                p2, {**sizes, "program": {**prog, "decoder_config": dc}})
            want = np.stack([np.asarray(ref.logits(
                w2, seqs[b], sizes, edges=[]))[CHECK] for b in two])
            report(f"== plain path, {label}", got, want, None)
            del w2
            if not swap and "cpu" not in skip:
                kept.update(chip=got, want=want)
                cpu_witness(p2)
            del p2
    say(f"done in {time.time() - t00:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
