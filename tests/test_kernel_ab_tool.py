"""``tools/run_kernel_ab.py --paged``: the cases it times and the arithmetic
that turns three call times into a (slot, head block) step's fixed cost and
a live page's (ISSUE 33; a live and a dead grid step before it). The times
themselves come from the chip only."""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

_spec = importlib.util.spec_from_file_location(
    "run_kernel_ab",
    Path(__file__).resolve().parents[1] / "tools" / "run_kernel_ab.py")
ab = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ab)


@pytest.mark.parametrize("tag, L, P, B, NP, N, K, H, int8",
                         ab.PAGED_GEOMETRIES)
@pytest.mark.parametrize("share", ab.LIVE_SHARES)
def test_a_case_has_the_stated_share_of_every_table_live(
        tag, L, P, B, NP, N, K, H, int8, share):
    table, lengths, live = ab.paged_case(0, B, NP, P, share)
    assert live == max(1, round(share * NP))
    assert table.shape == (B, NP) and lengths.shape == (B,)
    # the kernel's own bound: pages up to the one the length lies in
    assert (lengths // ab.PAGE + 1 == live).all()
    assert (table[:, :live] < P).all() and (table[:, live:] == P).all()
    # the same seed gives the same case
    again = ab.paged_case(0, B, NP, P, share)
    assert (again[0] == table).all() and (again[1] == lengths).all()


def test_the_geometries_are_the_benchmarks_configurations():
    import json

    root = Path(__file__).resolve().parents[1]
    named = [g for g in ab.PAGED_GEOMETRIES
             if (root / "benchmark" / "configs" / f"{g[0]}.json").exists()]
    assert len(named) == 6
    for tag, L, P, B, NP, N, K, H, _ in named:
        cfg = json.loads((root / "benchmark" / "configs"
                          / f"{tag}.json").read_text())
        dec, llm = cfg["program"]["decoder_config"], cfg["deployment"]["llm"]
        # the layers that hold pages of this kind: LFM2's G of CCGC, MiMo's
        # full layers (its window layers keep a ring, 8 heads: not timed)
        pattern = dec.get("layer_pattern", "")
        paging = (sum(pattern[i % len(pattern)] == "G"
                      for i in range(dec["num_layers"]))
                  if "C" in pattern or "sliding_kv_heads" in dec
                  else dec["num_layers"])
        assert (L, N, K, H) == (paging, dec["num_heads"],
                                dec["num_kv_heads"],
                                dec.get("head_dim")
                                or dec["d_model"] // dec["num_heads"])
        assert ab.V_DIM.get(tag, 0) == dec.get("v_head_dim", 0)
        assert max(ab.LIVE_PAGES.get(tag, (NP,))) == NP
        assert (B, P) == (llm["num_slots"], llm["kv_pool_pages"])
        assert NP == -(-llm["max_len"] // llm["page_size"])
        assert llm["page_size"] == ab.PAGE


@pytest.mark.parametrize("fixed, page", [(0.4, 0.7), (1.9, 0.66)])
def test_walk_costs_fit_the_three_shares(fixed, page):
    rows = [{"call_us": 32 * fixed + live * page, "steps": 32,
             "live_pages": live} for live in (32, 128, 256)]
    got = ab.walk_costs_us(rows)
    assert got == pytest.approx((fixed, page))


# --- ``--paged --pages-a-fold``: the narrow arm at each width of its fold ------
def test_the_paged_sweep_patches_the_picker_and_puts_it_back(monkeypatch):
    """The sweep's rows on the CPU at tiny geometries (the kernel
    interpreted: no timing is read): a narrow block runs at each width
    given and says so, a block of 8 heads at a page a fold whatever is
    asked, a pool whose value rows are narrower answers as the blocked
    walk does, and the picker is ``tile_math``'s own again afterwards."""
    from ray_dynamic_batching_tpu.ops import tile_math

    picker = tile_math.paged_fold_pages
    monkeypatch.setitem(ab.LIVE_PAGES, "narrow", (1, 3, 6))
    monkeypatch.setitem(ab.LIVE_PAGES, "kinds", (2, 5))
    monkeypatch.setitem(ab.V_DIM, "kinds", 128)
    narrow = ("narrow", 2, 16, 2, 6, 8, 8, 64, False)   # 4 packed rows
    for pages in (1, 2, 4):
        rows = ab._time_paged(*narrow, 1, pages=pages, samples=1)
        assert [r["pages_a_fold"] for r in rows] == [pages] * 3
        assert [r["live_pages"] for r in rows] == [2, 6, 12]
        assert all(r["max_abs_diff"] < 2e-2 for r in rows)
        assert tile_math.paged_fold_pages is picker
    # what the shapes pick, and the cell's own lengths
    assert {r["pages_a_fold"] for r in ab._time_paged(
        *narrow, 1, samples=1)} == {2}
    rows = ab._time_paged("kinds", 2, 16, 2, 6, 16, 4, 192, False, 1,
                          v_dim=128, samples=1)
    assert [(r["pages_a_fold"], r["live_pages"]) for r in rows] == [
        (2, 4), (2, 10)]
    assert all(r["max_abs_diff"] < 2e-2 for r in rows)
    eight = ab._time_paged("eight", 2, 16, 2, 4, 8, 8, 128, False, 1,
                           pages=4, samples=1)
    assert {r["pages_a_fold"] for r in eight} == {1}


def test_the_paged_sweep_writes_a_row_of_costs_a_width(
        monkeypatch, tmp_path, capsys):
    import json

    def rows(tag, *_, pages=0, **kw):
        took = pages if tag == "narrow" and pages else 1
        return [{"geometry": tag, "live_share": s, "pages_a_fold": took,
                 "call_us": 4 * 0.1 + live * 0.5 / took, "steps": 4,
                 "live_pages": live, "table_entries": 64,
                 "max_abs_diff": 0.0}
                for s, live in ((0.125, 8), (0.5, 32), (1.0, 64))]

    monkeypatch.setattr(ab, "_time_paged", lambda tag, *a: rows(
        tag, pages=a[-1]))
    monkeypatch.setattr(ab, "PAGED_GEOMETRIES", [
        ("narrow", 2, 16, 4, 16, 8, 4, 128, False),
        ("eight", 2, 16, 4, 16, 8, 8, 128, False)])
    (tmp_path / "p.json").write_text(json.dumps({"parent": {"kept": 1}}))
    ab.paged_main(str(tmp_path), "p.json", 1, None, "1,2,4")
    record = json.loads((tmp_path / "p.json").read_text())
    assert record["parent"] == {"kept": 1}
    got = [(g["geometry"], g["pages_a_fold"], round(g["live_page_us"], 6))
           for g in record["geometries"]]
    # a block of 8 heads is timed once: it keeps a page a fold
    assert got == [("narrow", 1, 0.5), ("narrow", 2, 0.25),
                   ("narrow", 4, 0.125), ("eight", 1, 0.5)]
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["live_page"]["narrow@4"] == pytest.approx(0.125)


@pytest.mark.parametrize("tag, pages, low, high", [
    # PR 52: live pages an online-softmax update in the dense kernel's
    # narrow arm (the chain a page is what held 0.51 where the copy is 0.32)
    ("lfm2-24b-a2b-ep8-1chip", 1, 0.49, 0.53),
    ("lfm2-24b-a2b-ep8-1chip", 2, 0.33, 0.36),      # shipped
    ("lfm2-24b-a2b-ep8-1chip", 4, 0.33, 0.36),
    # ... MiMo's k page is relaid in every fold besides
    ("mimo-v2-flash-ep16-1chip", 1, 0.65, 0.70),
    ("mimo-v2-flash-ep16-1chip", 2, 0.50, 0.54),    # shipped
    ("mimo-v2-flash-ep16-1chip", 4, 0.50, 0.54),
])
def test_the_recorded_narrow_rows_fit_is_the_page_cost(tag, pages, low, high):
    """``walk_costs_us`` over the committed capture's rows of the two narrow
    geometries, a width (records, not timings taken here): the recorded
    fit, inside the band the chip read."""
    import json

    root = Path(__file__).resolve().parents[1]
    record = json.loads((root / "profiles" / "tpu_v5e"
                         / "paged_steps.json").read_text())
    (kept,) = [g for g in record["geometries"]
               if (g["geometry"], g.get("pages_a_fold")) == (tag, pages)]
    fixed, page = ab.walk_costs_us(kept["rows"])
    assert low < page < high
    assert (fixed, page) == pytest.approx(
        (kept["fixed_step_us"], kept["live_page_us"]))
    geometry = next(g for g in ab.PAGED_GEOMETRIES if g[0] == tag)
    B = geometry[3]
    assert [r["live_pages"] for r in kept["rows"]] == [
        B * n for n in ab.LIVE_PAGES[tag]]
    assert all(r["pages_a_fold"] == pages and r["max_abs_diff"] < 2e-2
               for r in kept["rows"])
    # two slots of two live pages: four pages a fold is the slower there
    if tag.startswith("lfm2") and pages == 4:
        (two,) = [g for g in record["geometries"]
                  if (g["geometry"], g.get("pages_a_fold")) == (tag, 2)]
        assert two["rows"][0]["call_us"] < 0.9 * kept["rows"][0]["call_us"]
        assert two["rows"][2]["call_us"] == pytest.approx(
            kept["rows"][2]["call_us"], rel=0.01)


# --- ``--sparse``: a selecting layer's decode read ----------------------------
@pytest.mark.parametrize("length", ab.SPARSE_LENGTHS)
def test_a_sparse_case_has_every_slot_near_the_stated_length(length):
    _, L, P, B, NP, *_ = ab.SPARSE_GEOMETRY
    table, lengths = ab.sparse_case(0, B, NP, P, length)
    assert table.shape == (B, NP) and lengths.shape == (B,)
    assert (lengths <= length).all() and (lengths > length - ab.PAGE).all()
    for b in range(B):
        live = lengths[b] // ab.PAGE + 1
        assert (table[b, :live] < P).all() and (table[b, live:] == P).all()
    again = ab.sparse_case(0, B, NP, P, length)
    assert (again[0] == table).all() and (again[1] == lengths).all()


def test_the_sparse_geometry_is_the_benchmarks_configuration():
    import json

    from ray_dynamic_batching_tpu.ops import sparse_attention as sparse

    root = Path(__file__).resolve().parents[1]
    tag, L, P, B, NP, N, K, H, n_index, Hi, topk = ab.SPARSE_GEOMETRY
    cfg = json.loads((root / "benchmark" / "configs"
                      / f"{tag}.json").read_text())
    dec, llm = cfg["program"]["decoder_config"], cfg["deployment"]["llm"]
    assert (L, N, K, H) == (dec["num_layers"], dec["num_heads"],
                            dec["num_kv_heads"], dec["head_dim"])
    assert (n_index, Hi, topk) == (dec["index_heads"], dec["index_head_dim"],
                                   dec["index_topk"])
    assert (B, P) == (llm["num_slots"], llm["kv_pool_pages"])
    assert NP == llm["max_len"] // llm["page_size"]
    assert max(ab.SPARSE_LENGTHS) < llm["max_len"]
    assert set(ab.SPARSE_FORMS) == {
        sparse.FORM_FLOOR, sparse.FORM_MASK, "mask_untiled", "gather"}
    sa = cfg["sa_config"]
    assert (sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
            sa["indexer_num_kv_heads"]) == (n_index, Hi, topk, 1)


@pytest.mark.parametrize("rows, form, low, high", [
    ("shipped_pr35", "mask", 0.62, 0.66),     # the page as it lies
    ("rows", "mask_untiled", 0.62, 0.66),     # ... measured again, PR 39
    ("rows", "mask", 0.44, 0.49),             # as whole (8, 128) tiles
    ("rows", "gather", -0.01, 0.01),          # flat in the length
    # PR 51: live pages an online-softmax update (the chain a page is what
    # held 0.47 where the copy is 0.32)
    ("pages_a_fold", "mask_fold1", 0.44, 0.49),
    ("pages_a_fold", "mask_fold2", 0.33, 0.37),
    ("pages_a_fold", "mask_fold4", 0.33, 0.37),     # shipped
])
def test_the_recorded_rows_slope_is_the_page_cost(rows, form, low, high):
    """``page_slopes_us`` over the committed capture: microseconds a live
    page of a slot adds to a layer's read, a form (records, not timings
    taken here)."""
    import json

    root = Path(__file__).resolve().parents[1]
    record = json.loads((root / "profiles" / "tpu_v5e"
                         / "sparse_decode.json").read_text())
    _, L, P, B, NP, *_ = ab.SPARSE_GEOMETRY
    pages = {n: int((ab.sparse_case(0, B, NP, P, n)[1] // ab.PAGE + 1).sum())
             for n in ab.SPARSE_LENGTHS}
    kept = record[rows]["rows"] if rows == "pages_a_fold" else record[rows]
    slopes = ab.page_slopes_us(
        [dict(r, pages_live=pages[r["length"]]) for r in kept])
    assert low < slopes[form] < high
    if rows == "rows":
        assert slopes[form] == pytest.approx(record["page_us"][form])
    if rows == "pages_a_fold":
        assert slopes[form] == pytest.approx(record[rows]["page_us"][form])


# --- ``--sparse --pages-a-fold``: the mask form at each width of its fold ------
def test_pages_a_fold_names_a_form_a_width():
    assert ab.fold_forms("1,2,4") == (
        "floor", "mask_fold1", "mask_fold2", "mask_fold4")
    assert ab.fold_forms("4") == ("floor", "mask_fold4")
    for bad in ("", "0,2", "two"):
        with pytest.raises((SystemExit, ValueError)):
            ab.fold_forms(bad)


def test_the_sweep_patches_the_picker_and_puts_it_back(monkeypatch):
    """The sweep's rows on the CPU at a tiny geometry (the kernel
    interpreted: no timing is read): every width's answer is the floor's,
    each form took the width its name says, and the picker is the
    module's own again afterwards."""
    from ray_dynamic_batching_tpu.ops import attention as attn
    from ray_dynamic_batching_tpu.ops import sparse_attention as sparse

    monkeypatch.setattr(ab, "SPARSE_GEOMETRY", (
        "tiny", 2, 16, 2, 4, 8, 4, 128, 2, 8, 40))
    monkeypatch.setattr(ab, "SPARSE_LENGTHS", (140, 500))
    picker, taken = sparse._fold_pages, []
    inner = sparse._sparse_paged_decode_attention
    monkeypatch.setattr(
        sparse, "_sparse_paged_decode_attention",
        lambda *a, **kw: taken.append(kw["pages"]) or inner(*a, **kw))
    attn.set_attention_backend("pallas")
    try:
        rows = ab._time_sparse(1, ab.fold_forms("1,2,4"), samples=1)
    finally:
        attn.set_attention_backend("auto")
    assert sparse._fold_pages is picker
    assert [(r["form"], r["length"]) for r in rows] == [
        (f, n) for f in ab.fold_forms("1,2,4") for n in (140, 500)]
    assert all(r["max_abs_diff"] < 2e-2 for r in rows)
    # traced once a form: the one-layer chain's read, the program's two
    assert taken == [1] * 3 + [2] * 3 + [4] * 3
    assert set(ab.page_slopes_us(rows)) == set(ab.fold_forms("1,2,4"))


def test_the_sweep_is_one_key_of_the_file(monkeypatch, tmp_path, capsys):
    import json

    rows = [{"geometry": "g", "form": f, "length": n, "layer_us": us,
             "pages_live": p, "rows_selected": 1, "rows_live": 2,
             "max_abs_diff": 0.0}
            for f, slope in (("floor", 0.25), ("mask_fold4", 0.4))
            for n, p, us in ((1, 100, 50 + 100 * slope),
                             (2, 300, 50 + 300 * slope))]
    monkeypatch.setattr(ab, "_time_sparse", lambda iters, forms: [
        r for r in rows if r["form"] in forms])
    (tmp_path / "s.json").write_text(json.dumps(
        {"note": "kept", "rows": ["as they were"]}))
    ab.sparse_main(str(tmp_path), "s.json", 3, "4")
    record = json.loads((tmp_path / "s.json").read_text())
    assert record["note"] == "kept" and record["rows"] == ["as they were"]
    sweep = record["pages_a_fold"]
    assert sweep["iters"] == 3 and len(sweep["rows"]) == 4
    assert sweep["page_us"] == pytest.approx(
        {"floor": 0.25, "mask_fold4": 0.4})
    assert "mask_fold4: 0.400 us a live page" in capsys.readouterr().out
