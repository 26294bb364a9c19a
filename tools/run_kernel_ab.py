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

``--paged`` measures the PAGED kernel alone instead
(``ops/decode_attention.py::_paged_decode_attention``), at the stacked
pools of the benchmark's configurations, with an eighth, a half and
all of each slot's page table live: what a (slot, head block) grid step
costs before it folds a page (fixed) and what each live page its loop
walks adds, a two-parameter fit over the three live shares (an entry
past the length is never visited: it costs nothing). One jitted program
chains a call a layer, as a decode substep does, so the launch is paid
once a program and not once a kernel. Read every kernel PR's costs with
it (``PERF.md``); the file keeps the parent commit's rows under ``parent``
(since PR 48: gpt2-medium's lane-padded pool, a head a row, beside the two
heads a row the tool now builds by ``pool_heads_per_row``).
``--paged --pages-a-fold 1,2,4`` times each geometry whose head block is
NARROW (LFM2's 4 packed rows, MiMo's full layers' 4 heads) at each of those
widths of its fold (the kernel's picker, ``tile_math.paged_fold_pages``,
patched to ``n`` live pages an online-softmax update where the block is
narrow; a block of 8 heads keeps a page a fold and is timed once), a row of
costs a geometry a width; without it every geometry runs at the width its
shapes pick, which its rows name (``pages_a_fold``).

``--sparse`` measures a selecting layer's decode read (index scores, top-k
and attention over the selection) at the configuration that has an indexer,
at three cached lengths, into ``sparse_decode.json``: the two forms of
``ops/sparse_attention.py`` (the mask form it ships; the floor, which the
tool reaches by making the mask form's kernel decline), the mask form with
its tile view of a 4-head page refused (``mask_untiled``: PR 35's kernel,
the page relaid in every fold) and a GATHER form that lives here
(:func:`gather_form_decode`: the selected rows only; slower below ~30k
positions a slot, so the program does not carry it). ``page_us`` is the
slope of a form's three rows: microseconds a live page of a slot adds.
``--sparse --pages-a-fold 1,2,4`` times the mask form alone at each of those
widths of its fold (``mask_fold<n>``: the kernel's picker,
``sparse._fold_pages``, patched to ``n`` live pages an online-softmax
update) beside the floor they are compared with, and writes the rows and
their slopes under ``pages_a_fold`` of the file, leaving its other keys.

``--ssm`` measures a state-space mixer's decode row on the state plane
(``models/ssm.py``) at the configuration that has one, the plane at the
cell's own size (``SSM_GEOMETRY``: 1.6 GB), into ``ssm_update.json``: the XLA
form (``ssm.decode_update`` written into its layer of the plane: the update
one fusion, the read-out another that reads the state again) against the
kernel (``ops/ssm_update.py``: a tile read once and written once, the plane
in place) at each head block of ``--head-blocks 16,8`` (the kernel's picker,
``ssm_update._heads_block``, patched; without the option, the block its
shapes pick). One jitted program chains a call a layer on the donated plane,
as a decode substep does. A row says microseconds a layer and a (slot,
layer), and GB/s of the bytes the MODEL needs (a read and a write of a
layer's states: ``benchmark/ssm_counts.py``'s count).

Usage: python tools/run_kernel_ab.py [out_dir] [--iters N]
                                     [--paged|--sparse|--ssm]
                                     [--only tag1,tag2] [--out-name F]
                                     [--pages-a-fold n1,n2]
                                     [--head-blocks n1,n2]
Writes <out_dir>/<F> (default kernel_ab.json, paged_steps.json with
``--paged``, in profiles/tpu_v5e) and prints one JSON summary line.
``--only`` restricts to named geometries (a couple of geometries are ~2
compiles each — a short chip call). Exit 0 only when EVERY selected
geometry succeeded on a non-CPU backend.
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


# Paged geometries: the benchmark's configurations (benchmark/configs/),
# (tag, L layers, P pages, B slots, NP table entries, N, K, H, int8 pool).
PAGED_GEOMETRIES = [
    ("gpt2-medium", 24, 128, 16, 8, 16, 16, 64, False),
    ("mistral-7b-v0.3-1chip", 16, 160, 8, 32, 32, 8, 128, False),
    ("olmoe-1b-7b-1chip", 12, 256, 32, 8, 16, 16, 128, False),
    ("gpt2-medium-int8kv", 24, 128, 16, 8, 16, 16, 64, True),
    # the same pool read by a full layer's call and by a sliding layer's
    # (SLIDING below: its grid is the window's table columns wide, flat in
    # the slot's length)
    ("k-exaone-236b-ep8-1chip", 5, 2048, 64, 32, 64, 8, 128, False),
    ("k-exaone-236b-ep8-1chip-window128", 5, 2048, 64, 32, 64, 8, 128,
     False),
    # the two NARROW head blocks: LFM2's ten attention layers (GQA 32/8 x
    # 64, two heads a row: 4 rows x 128 lanes a position) and MiMo's two
    # full layers (4 KV heads, k 192 held as 256 lanes, v 128)
    ("lfm2-24b-a2b-ep8-1chip", 10, 2048, 64, 32, 32, 8, 64, False),
    ("mimo-v2-flash-ep16-1chip", 2, 5760, 40, 144, 64, 4, 192, False),
]
# Geometries whose every call is a sliding layer's, and their window.
SLIDING = {"k-exaone-236b-ep8-1chip-window128": 128}
# ... whose value rows are narrower than their key rows, and that width.
V_DIM = {"mimo-v2-flash-ep16-1chip": 128}
LIVE_SHARES = (0.125, 0.5, 1.0)
# ... whose live pages a slot are the cell's own (its shortest slots, its
# mean, a full table) and not LIVE_SHARES of the table.
LIVE_PAGES = {"lfm2-24b-a2b-ep8-1chip": (2, 8, 32),
              "mimo-v2-flash-ep16-1chip": (36, 72, 144)}
PAGE = 128

# ``--sparse``: a selecting layer's decode read (ops/sparse_attention.py) in
# each of its forms, at the configuration that has an indexer: (tag, L, P,
# B, NP, N, K, H, index heads, index head, topk), and the cached lengths
# the cell's slots decode at (its shortest, its mean, a full table).
SPARSE_GEOMETRY = ("keye-vl2-30b-ep8-1chip", 8, 3456, 24, 144, 32, 4, 128,
                   16, 64, 2048)
SPARSE_LENGTHS = (4608, 9216, 18300)
SPARSE_FORMS = ("floor", "mask", "mask_untiled", "gather")
FOLD_FORM = "mask_fold"     # + n: the mask form at n pages a fold

# ``--ssm``: the state plane of the configuration that has a state-space
# mixer, as its cell holds it: (tag, L layers, B slots, H heads, P a head,
# N state, G groups).
SSM_GEOMETRY = ("falcon-h1-34b-1chip", 6, 64, 32, 128, 256, 2)


def fold_widths(widths: str):
    """``--pages-a-fold``'s widths, ``"1,2,4"``, in the order given."""
    pages = [int(w) for w in widths.split(",")]
    if any(n < 1 for n in pages):
        raise SystemExit(f"--pages-a-fold: not widths: {widths!r}")
    return pages


def fold_forms(widths: str):
    """``--sparse --pages-a-fold``'s forms: the floor (every row's
    reference) and the mask form at each width."""
    return ("floor",) + tuple(f"{FOLD_FORM}{n}" for n in fold_widths(widths))


def sparse_case(seed: int, B: int, NP: int, P: int, length: int):
    """A page table and lengths with every slot at ``length`` cached
    positions, less a draw of up to a page from ``seed``; physical pages a
    permutation of the pool, entries past the length the sentinel ``P``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = length - rng.integers(0, PAGE, B)
    live = int(lengths.max()) // PAGE + 1
    pages = np.resize(rng.permutation(P), B * live).reshape(B, live)
    table = np.full((B, NP), P, np.int32)
    table[:, :live] = pages
    for b in range(B):
        table[b, lengths[b] // PAGE + 1:] = P
    return table, lengths.astype(np.int32)


def paged_case(seed: int, B: int, NP: int, P: int, share: float):
    """A page table and lengths with ``max(1, round(share * NP))`` live
    pages in every slot: the offset inside the last live page drawn from
    ``seed``, physical pages a permutation of the pool (cycled where the
    slots' live pages outnumber it), entries past the length the
    sentinel ``P``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    live = max(1, round(share * NP))
    lengths = (live - 1) * PAGE + rng.integers(0, PAGE - 1, B)
    pages = np.resize(rng.permutation(P), B * live).reshape(B, live)
    table = np.full((B, NP), P, np.int32)
    table[:, :live] = pages
    return table, lengths.astype(np.int32), live


def walk_costs_us(rows):
    """Microseconds a (slot, head block) grid step costs before it folds
    a page, and microseconds a live page adds, from one geometry's rows
    (a row: ``call_us`` of one kernel call, its grid ``steps``, the
    ``live_pages`` its loops walk between them): the least-squares line
    ``call_us = fixed * steps + page * live_pages`` through the rows. The
    call's own launch is in the fixed cost (a program chains a call a
    layer, so it is a layer's share of one launch)."""
    import numpy as np

    a = np.array([[r["steps"], r["live_pages"]] for r in rows], float)
    (fixed, page), *_ = np.linalg.lstsq(
        a, np.array([r["call_us"] for r in rows], float), rcond=None)
    return float(fixed), float(page)


def _time_paged(tag, L, P, B, NP, N, K, H, int8, iters, sliding=0,
                v_dim=0, pages=0, samples=5):
    """Rows (one a live share) of the paged kernel's time a call, with
    its worst gap to the gather path on the same inputs. ``sliding``: every
    call is a sliding layer's, over that window. ``v_dim``: the value
    rows' width where not ``H``. ``pages`` > 0: the kernel's picker patched
    to that many pages a fold where its head block is narrow."""
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.models.kv_state import (
        pool_head_dim,
        pool_heads_per_row,
        quantize_kv_rows,
    )
    from ray_dynamic_batching_tpu.ops import attention as attn
    from ray_dynamic_batching_tpu.ops import decode_attention as da
    from ray_dynamic_batching_tpu.ops import tile_math

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    # the pool as the engine would lay it out: gpt2-medium's two 64-wide
    # heads a row, [.., 8, 128]
    f = 1 if v_dim else pool_heads_per_row(
        H, K, jnp.int8 if int8 else jnp.bfloat16)
    shape = (L, P, PAGE, K // f)
    k = jax.random.normal(
        keys[0], shape + (pool_head_dim(H * f),), jnp.bfloat16)
    v = jax.random.normal(
        keys[1], shape + (pool_head_dim(v_dim or H * f),), jnp.bfloat16)
    q = jax.random.normal(keys[2], (B, 1, N, H), jnp.bfloat16)
    ks = vs = None
    if int8:
        k, ks = quantize_kv_rows(k)   # scales [L, P, ps, K]
        v, vs = quantize_kv_rows(v)

    def chain(layers):
        """A program of one call a layer of ``layers``, each layer's
        output the next one's query (one dependent chain, as the decode
        program's layers are). A fresh callable a call: jit's trace
        cache is keyed by the function, and the backend it is traced
        under is not part of the key."""
        def run(q, k, v, ks, vs, table, lengths):
            for layer in layers:
                q = attn.dot_product_attention(
                    q, k, v, page_table=table, kv_lengths=lengths,
                    layer=layer,
                    k_scale=None if ks is None else ks[layer],
                    v_scale=None if vs is None else vs[layer],
                    sliding=sliding, heads_per_row=f, v_dim=v_dim)
                if v_dim:   # a value row back to a query's width
                    q = jnp.concatenate([q, q[..., :H - v_dim]], axis=-1)
            return q
        return jax.jit(run)

    blocks = (K // f) // da._pick_heads_block(K // f)
    cases = []
    for share in ([n / NP for n in LIVE_PAGES[tag]] if tag in LIVE_PAGES
                  else LIVE_SHARES):
        table, lengths, live = paged_case(0, B, NP, P, share)
        cases.append((share, live, jnp.asarray(table), jnp.asarray(lengths)))
    # Each traced once: the table and the lengths are arguments.
    gather, kernel, program = chain([L - 1]), chain([L - 1]), chain(range(L))
    attn.set_attention_backend("xla")
    try:
        refs = [gather(q, k, v, ks, vs, table, lengths)
                for _, _, table, lengths in cases]
    finally:
        attn.set_attention_backend("auto")
    rows = []
    attn.set_attention_backend("pallas")
    picker = getattr(tile_math, "paged_fold_pages", None)  # none: a parent
    if pages:
        tile_math.paged_fold_pages = (
            lambda *a, narrow=False, **kw: pages if narrow else 1)
    da.clear_decode_paths()
    try:
        for (share, live, table, lengths), ref in zip(cases, refs):
            out = kernel(q, k, v, ks, vs, table, lengths)
            program(q, k, v, ks, vs, table, lengths).block_until_ready()
            took = []
            for _ in range(samples):
                t0 = time.perf_counter()
                for _ in range(iters):
                    res = program(q, k, v, ks, vs, table, lengths)
                res.block_until_ready()
                took.append(
                    (time.perf_counter() - t0) * 1e6 / (iters * L))
            walked = tile_math.window_table_width(sliding, 1, PAGE, NP)
            if sliding:    # the window's columns that hold a position
                live = int((live - tile_math.window_first_page(
                    lengths, sliding, PAGE)).sum()) / B
            rows.append({
                "geometry": tag, "live_share": share,
                "heads_per_row": f,
                # what the kernel's call traced (a parent has no field)
                "pages_a_fold": getattr(da.decode_paths()[-1], "pages", 1),
                "call_us": statistics.median(took),
                "call_us_min_max": [min(took), max(took)],
                "steps": B * blocks,
                "live_pages": round(B * blocks * live),
                "table_entries": B * blocks * walked,
                "max_abs_diff": float(jnp.max(jnp.abs(
                    out.astype(jnp.float32) - ref.astype(jnp.float32)))),
            })
    finally:
        attn.set_attention_backend("auto")
        if picker is not None:
            tile_math.paged_fold_pages = picker
    return rows


def paged_main(out_dir: str, out_name: str, iters: int, only,
               widths: str = "") -> int:
    import jax

    backend = jax.default_backend()
    geometries = [g for g in PAGED_GEOMETRIES if not only or g[0] in only]
    if not geometries:
        raise SystemExit(f"--only matched nothing: {only}")
    # a width a run of a geometry; 0: what its shapes pick
    runs = [(g, n) for g in geometries
            for n in (fold_widths(widths) if widths else [0])]
    record = {"backend": backend,
              "device_kind": jax.devices()[0].device_kind,
              "captured": time.strftime("%Y%m%dT%H%M%S"), "iters": iters,
              "geometries": []}
    ok = True
    seen = set()
    for g, n in runs:
        try:
            rows = _time_paged(*g, iters, SLIDING.get(g[0], 0),
                               V_DIM.get(g[0], 0), n)
            if (g[0], rows[0]["pages_a_fold"]) in seen:
                continue    # a block of 8 heads: a page a fold again
            seen.add((g[0], rows[0]["pages_a_fold"]))
        except Exception as exc:  # noqa: BLE001
            ok = False
            record["geometries"].append(
                {"geometry": g[0], "error": repr(exc)[:500]})
            print(f"{g[0]}: FAILED {exc!r}", file=sys.stderr, flush=True)
            continue
        # a sliding call walks its window's pages at every length:
        # nothing to tell a page's cost from a step's by
        fixed_us, page_us = ((None, None) if g[0] in SLIDING
                             else walk_costs_us(rows))
        record["geometries"].append({
            "geometry": g[0], "pages_a_fold": rows[0]["pages_a_fold"],
            "rows": rows, "fixed_step_us": fixed_us,
            "live_page_us": page_us})
        for r in rows:
            print(f"{g[0]}: {r['pages_a_fold']} pages a fold: "
                  f"live share {r['live_share']:.3f}: "
                  f"{r['call_us']:.1f} us a call "
                  f"({r['steps']} steps walk {r['live_pages']} of "
                  f"{r['table_entries']} table entries), "
                  f"max |kernel - gather| {r['max_abs_diff']:.2e}",
                  flush=True)
        if fixed_us is not None:
            print(f"{g[0]}: {rows[0]['pages_a_fold']} pages a fold: a "
                  f"(slot, head block) step {fixed_us:.3f} us, a live "
                  f"page {page_us:.3f} us", flush=True)
        ok = ok and all(r["max_abs_diff"] < 0.1 for r in rows)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, out_name)
    if os.path.exists(path):    # the parent commit's rows stay beside
        with open(path) as f:
            kept = json.load(f).get("parent")
        if kept is not None:
            record["parent"] = kept
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(json.dumps({
        "metric": "paged_decode_walk_us", "backend": backend,
        **{key: {f"{g['geometry']}@{g.get('pages_a_fold', 1)}":
                 g.get(f"{key}_us") for g in record["geometries"]}
           for key in ("fixed_step", "live_page")},
    }), flush=True)
    return 0 if ok and backend != "cpu" else 1


def gather_form_decode(q, k, v, page_table, kv_lengths, layer: int, select):
    """A selecting layer's decode read that touches the SELECTED rows only:
    ``k[layer, page_of(s), s % ps]`` gathered for each slot's top-k
    positions and folded by the slab path over ``[B, topk, K, H]``. Flat in
    the slot's length (4.0 ms a layer at the benchmark's widths on a v5e,
    the row gather 2.2 of it) where the mask form costs 0.42 ms + 0.12 a
    thousand positions: they cross near 30k positions a slot."""
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.models.decoder import paged_window_mask
    from ray_dynamic_batching_tpu.ops import attention as attn
    from ray_dynamic_batching_tpu.ops import sparse_attention as sparse

    P, ps = k.shape[1], k.shape[2]
    NP, H = page_table.shape[1], q.shape[-1]
    safe = jnp.minimum(page_table, P - 1)
    win = paged_window_mask(kv_lengths, NP * ps, 1)[:, 0]      # [B, 1, S]
    scores = sparse.index_scores(select.q, select.w,
                                 sparse._index_keys(select, layer, safe))
    # top_k is stable: of equal scores the lower position comes first.
    vals, pos = jax.lax.top_k(jnp.where(win, scores, -jnp.inf)[:, 0],
                              min(select.topk, NP * ps))
    page, off = jnp.take_along_axis(safe, pos // ps, axis=1), pos % ps
    return attn._dense_attention(
        q, k[layer, page, off][..., :H], v[layer, page, off][..., :H],
        causal=False, mask=(vals > -jnp.inf)[:, None, None, :], scale=None,
        k_scale=None, v_scale=None,
        declines=["A/B tool: the gather form"], gathered=True)


def _time_sparse(iters: int, forms=SPARSE_FORMS, samples: int = 5):
    """Rows (one a form a length) of a selecting layer's decode read: us a
    layer of a program that chains one read a layer (scores, top-k and
    attention together: what a decode substep pays a layer), and the worst
    gap to the floor on the same inputs."""
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.models.kv_state import pool_head_dim
    from ray_dynamic_batching_tpu.ops import attention as attn
    from ray_dynamic_batching_tpu.ops import sparse_attention as sparse

    tag, L, P, B, NP, N, K, H, n_index, Hi, topk = SPARSE_GEOMETRY
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    shape = (L, P, PAGE, K, pool_head_dim(H))
    k = jax.random.normal(keys[0], shape, jnp.bfloat16)
    v = jax.random.normal(keys[1], shape, jnp.bfloat16)
    q = jax.random.normal(keys[2], (B, 1, N, H), jnp.bfloat16)
    pool = jax.random.normal(
        keys[3], (L, P, PAGE, pool_head_dim(Hi)), jnp.bfloat16)
    q_i = jax.random.normal(keys[4], (B, 1, n_index, Hi), jnp.bfloat16)
    w_i = jax.random.normal(keys[5], (B, 1, n_index), jnp.bfloat16)

    def chain(form, layers):
        def run(q, k, v, pool, table, lengths):
            for layer in layers:
                select = sparse.Selection(q_i, w_i, pool, topk)
                if form == "gather":
                    q = gather_form_decode(q, k, v, table, lengths, layer,
                                           select)
                else:
                    q = attn.dot_product_attention(
                        q, k, v, page_table=table, kv_lengths=lengths,
                        layer=layer, select=select)
            return q
        return jax.jit(run)

    cases = [(n, *map(jnp.asarray, sparse_case(0, B, NP, P, n)))
             for n in SPARSE_LENGTHS]
    rows, refs = [], {}
    declines, page_fold = sparse._mask_form_declines, sparse._page_fold
    fold_pages = sparse._fold_pages
    for form in forms:
        if form == "floor":     # what a read the kernel declines takes
            sparse._mask_form_declines = lambda *a: "A/B tool: the floor"
        if form == "mask_untiled":  # the page as it lies: [ps, 4, H]
            sparse._page_fold = lambda *a: 1
        if form.startswith(FOLD_FORM):
            sparse._fold_pages = lambda *a, n=int(form[len(FOLD_FORM):]): n
        one, program = chain(form, [L - 1]), chain(form, range(L))
        try:
            for n, table, lengths in cases:
                out = one(q, k, v, pool, table, lengths)
                refs.setdefault(n, out)            # the floor comes first
                program(q, k, v, pool, table, lengths).block_until_ready()
                took = []
                for _ in range(samples):
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        res = program(q, k, v, pool, table, lengths)
                    res.block_until_ready()
                    took.append(
                        (time.perf_counter() - t0) * 1e6 / (iters * L))
                rows.append({
                    "geometry": tag, "form": form, "length": n,
                    "layer_us": statistics.median(took),
                    "layer_us_min_max": [min(took), max(took)],
                    "rows_live": int(lengths.sum()) + B,
                    "pages_live": int((lengths // PAGE + 1).sum()),
                    "rows_selected": int(jnp.minimum(
                        lengths + 1, topk).sum()),
                    "max_abs_diff": float(jnp.max(jnp.abs(
                        out.astype(jnp.float32)
                        - refs[n].astype(jnp.float32)))),
                })
        finally:
            sparse._mask_form_declines = declines
            sparse._page_fold = page_fold
            sparse._fold_pages = fold_pages
    return rows


def page_slopes_us(rows) -> dict:
    """Microseconds a live page of a slot adds to a layer's read, a form:
    the least-squares slope of ``layer_us`` over ``pages_live``."""
    out = {}
    for form in dict.fromkeys(r["form"] for r in rows):
        xs, ys = zip(*((r["pages_live"], r["layer_us"])
                       for r in rows if r["form"] == form))
        if len(set(xs)) > 1:
            out[form] = statistics.covariance(xs, ys) / statistics.variance(xs)
    return out


def sparse_main(out_dir: str, out_name: str, iters: int,
                widths: str = "") -> int:
    import jax

    backend = jax.default_backend()
    rows = _time_sparse(iters, fold_forms(widths) if widths else SPARSE_FORMS)
    slopes = page_slopes_us(rows)
    record = {"backend": backend,
              "device_kind": jax.devices()[0].device_kind,
              "captured": time.strftime("%Y%m%dT%H%M%S"), "iters": iters,
              "geometry": SPARSE_GEOMETRY[0], "rows": rows,
              "page_us": slopes}
    path = os.path.join(out_dir, out_name)
    if widths:      # the sweep is one key of the file; the rest stays
        kept = {}
        if os.path.exists(path):
            with open(path) as f:
                kept = json.load(f)
        record = dict(kept, pages_a_fold=record)
    for r in rows:
        print(f"{r['geometry']}: {r['form']} at {r['length']} positions: "
              f"{r['layer_us']:.1f} us a layer ({r['rows_selected']} of "
              f"{r['rows_live']} rows selected), max |form - floor| "
              f"{r['max_abs_diff']:.2e}", flush=True)
    for form, us in slopes.items():
        print(f"{SPARSE_GEOMETRY[0]}: {form}: {us:.3f} us a live page",
              flush=True)
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(json.dumps({
        "metric": "sparse_decode_layer_us", "backend": backend,
        "layer_us": {f"{r['form']}@{r['length']}": r["layer_us"]
                     for r in rows},
        "page_us": slopes}), flush=True)
    ok = all(r["max_abs_diff"] < 0.1 for r in rows)
    return 0 if ok and backend != "cpu" else 1


def _time_ssm(iters: int, blocks=(0,), geometry=SSM_GEOMETRY,
              samples: int = 5):
    """Rows of a decode row's time on the state plane: the XLA form, then
    the kernel at each head block of ``blocks`` (0: what the shapes pick),
    each with its worst gaps to the XLA form on the same inputs (``y``, the
    advanced states; a slot that does not advance and every other layer
    must come back bit for bit)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_dynamic_batching_tpu.models import ssm
    from ray_dynamic_batching_tpu.ops import attention as attn
    from ray_dynamic_batching_tpu.ops import ssm_update

    tag, L, B, H, P, N, G = geometry
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[1], (B, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[2], (B, H)) - 3.0)
    A = -jnp.exp(ssm.a_log_init(keys[3], (H,)))
    Bm, Cm = (jax.random.normal(k, (B, G, N), jnp.float32)
              for k in keys[4:6])
    everyone = jnp.ones((B,), jnp.int32)
    some = everyone.at[1::3].set(0)
    picker = ssm_update._heads_block

    def new_plane():
        return jax.random.normal(keys[0], (L, B, H, P, N), jnp.float32)

    def chain(layers, kernel: bool):
        """A program of one row a layer of ``layers`` on the donated plane,
        each layer's ``y`` the next one's ``x`` (one dependent chain, as
        the decode program's layers are)."""
        def run(plane, x, advance):
            for li in layers:
                if kernel:
                    y, plane = ssm_update.state_update(
                        x, dt, A, Bm, Cm, plane, li, advance)
                else:
                    y, S = ssm.decode_update(x, dt, A, Bm, Cm, plane[li],
                                             advance)
                    plane = plane.at[li].set(S)
                x = x + 1e-3 * y
            return plane, x, y
        return jax.jit(run, donate_argnums=(0,))

    def timed(program):
        plane = program(new_plane(), x, everyone)[0]
        plane.block_until_ready()
        took = []
        for _ in range(samples):
            t0 = time.perf_counter()
            for _ in range(iters):
                plane, out, _ = program(plane, x, everyone)
            out.block_until_ready()
            took.append((time.perf_counter() - t0) * 1e6 / (iters * L))
        return statistics.median(took), [min(took), max(took)]

    rows = []
    li = L // 2
    attn.set_attention_backend("xla")
    try:
        want_plane, _, want_y = chain([li], False)(new_plane(), x, some)
        want = np.asarray(want_plane[li]), np.asarray(want_y)
        del want_plane
        layer_us, spread = timed(chain(range(L), False))
    finally:
        attn.set_attention_backend("auto")
    rows.append({"form": "xla", "heads_a_tile": None,
                 "layer_us": layer_us, "layer_us_min_max": spread})
    attn.set_attention_backend("pallas")
    try:
        for hb in blocks:
            if hb:
                ssm_update._heads_block = lambda *a, hb=hb: hb
            try:
                got_plane, _, got_y = chain([li], True)(
                    new_plane(), x, some)
                got = np.asarray(got_plane[li])
                fresh = new_plane()     # a layer at a time: no gather
                kept = all(bool(jnp.array_equal(got_plane[i], fresh[i]))
                           for i in range(L) if i != li)
                del fresh
                still = np.asarray(some) == 0
                rows.append({
                    "form": "kernel",
                    "heads_a_tile": ssm_update._heads_block(H, G, P, N),
                    "max_abs_diff_y": float(np.abs(
                        np.asarray(got_y) - want[1]).max()),
                    "max_abs_diff_state": float(
                        np.abs(got - want[0]).max()),
                    "idle_slots_and_other_layers_bit_for_bit": kept and bool(
                        np.array_equal(got[still], want[0][still])),
                })
                del got_plane
                layer_us, spread = timed(chain(range(L), True))
                rows[-1].update(layer_us=layer_us, layer_us_min_max=spread)
            finally:
                ssm_update._heads_block = picker
    finally:
        attn.set_attention_backend("auto")
    need = 2 * B * H * P * N * 4      # a layer's states read and written
    for r in rows:
        r.update(geometry=tag, slot_layer_us=r["layer_us"] / B,
                 model_gb_per_s=need / r["layer_us"] / 1e3)
    return rows


def ssm_main(out_dir: str, out_name: str, iters: int, blocks) -> int:
    import jax

    backend = jax.default_backend()
    rows = _time_ssm(iters, blocks)
    record = {"backend": backend,
              "device_kind": jax.devices()[0].device_kind,
              "captured": time.strftime("%Y%m%dT%H%M%S"), "iters": iters,
              "geometry": SSM_GEOMETRY[0], "rows": rows}
    for r in rows:
        print(f"{r['geometry']}: {r['form']}"
              + (f" at {r['heads_a_tile']} heads a tile"
                 if r["heads_a_tile"] else "")
              + f": {r['layer_us']:.1f} us a layer, "
              f"{r['slot_layer_us']:.3f} us a (slot, layer), "
              f"{r['model_gb_per_s']:.1f} GB/s of the model's bytes"
              + (f", max |kernel - xla| y {r['max_abs_diff_y']:.2e} "
                 f"state {r['max_abs_diff_state']:.2e}"
                 if r["form"] == "kernel" else ""), flush=True)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, out_name)
    if os.path.exists(path):    # what a builder wrote beside the rows stays
        with open(path) as f:
            kept = json.load(f)
        record.update({k: kept[k] for k in ("note", "variants")
                       if k in kept})
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(json.dumps({
        "metric": "ssm_update_layer_us", "backend": backend,
        "layer_us": {f"{r['form']}@{r['heads_a_tile'] or 0}": r["layer_us"]
                     for r in rows}}), flush=True)
    ok = all(r["max_abs_diff_y"] < 1e-3 and r["max_abs_diff_state"] < 1e-3
             and r["idle_slots_and_other_layers_bit_for_bit"]
             for r in rows if r["form"] == "kernel")
    return 0 if ok and backend != "cpu" else 1


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
    widths = (sys.argv[sys.argv.index("--pages-a-fold") + 1]
              if "--pages-a-fold" in sys.argv else "")
    if "--sparse" in sys.argv:
        out_name = (sys.argv[sys.argv.index("--out-name") + 1]
                    if "--out-name" in sys.argv else "sparse_decode.json")
        return sparse_main(out_dir, out_name, iters, widths)
    if "--ssm" in sys.argv:
        out_name = (sys.argv[sys.argv.index("--out-name") + 1]
                    if "--out-name" in sys.argv else "ssm_update.json")
        blocks = ([int(n) for n in sys.argv[
            sys.argv.index("--head-blocks") + 1].split(",")]
            if "--head-blocks" in sys.argv else [0])
        return ssm_main(out_dir, out_name, iters, blocks)
    if "--paged" in sys.argv:
        only = (set(sys.argv[sys.argv.index("--only") + 1].split(","))
                if "--only" in sys.argv else None)
        out_name = (sys.argv[sys.argv.index("--out-name") + 1]
                    if "--out-name" in sys.argv else "paged_steps.json")
        return paged_main(out_dir, out_name, iters, only, widths)
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
            from ray_dynamic_batching_tpu.models.kv_state import (
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
