"""The dense paged kernel folds several live pages an online-softmax update
where its head block is NARROW (ISSUE 52, tier-1, the kernel interpreted):

- at LFM2's packed geometry (GQA 32/8 x 64, two heads a 128-lane row: 4
  rows a position, 32 query rows a block) and at MiMo's full layers' (4 KV
  heads, k rows 256 lanes, v rows 128, no sink) the kernel at 1, 2 and 4
  pages a fold gives float32 arithmetic's values and a page a fold's: a slot
  shorter than a group, a group exactly, a group and a page, a length at a
  page's last position, an idle slot, a spec window;
- a short last group's tail is never copied and never attended, whatever
  the ring held;
- the width is the shapes' own (``decode_attention._walk``) and the path
  record says it (``DecodePath.pages``, ``describe()``);
- a block of 8 heads, an int8 pool, and the narrow arm itself at a page a
  fold trace the PARENT's program, text for text
  (``tests/data/paged_kernel_digests.json``).
"""

import hashlib
import json
import re
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_dynamic_batching_tpu.models.kv_state import to_pool_rows
from ray_dynamic_batching_tpu.ops import decode_attention as da
from ray_dynamic_batching_tpu.ops import tile_math

PS, NP, POOL, LAYER = 128, 6, 14, 1
# name -> (query heads, KV heads, head width, v width, heads a pool row)
GEOMETRIES = {"lfm2": (32, 8, 64, 64, 2), "mimo_full": (64, 4, 192, 128, 1)}
# what the shapes pick (``tile_math.paged_fold_pages``)
PICKED = {"lfm2": 2, "mimo_full": 2}
# case -> (window rows, the slots' lengths); the table has 6 columns
CASES = {
    "shorter_than_a_group": (1, [130, 5]),          # 2 pages; 1 page
    "a_group_exactly": (1, [4 * PS - 3, 2 * PS - 1]),
    "a_group_and_a_page": (1, [4 * PS + 9, 2 * PS]),
    "a_pages_last_position": (1, [PS - 1, 3 * PS - 1]),
    "an_idle_slot": (1, [0, 6 * PS - 1]),           # ... beside a full table
    # rows astride a page's edge, and a group's (two rows: MiMo's 16 query
    # heads a KV head leave the flat form past 32 rows a head)
    "a_spec_window": (2, [PS - 1, 4 * PS - 1]),
}


def _inputs(geometry, case):
    """q, the k and v pools as the engine lays them out, the page table
    (entries past the last live page the sentinel) and the lengths; and k
    and v as plain ``[L, P, ps, K, width]`` float32 for the arithmetic."""
    N, K, H, Hv, f = GEOMETRIES[geometry]
    T, lengths = CASES[case]
    rng = np.random.default_rng(
        int(hashlib.sha256(f"{geometry}/{case}".encode()).hexdigest(), 16)
        % 2 ** 32)
    lengths = np.asarray(lengths)
    B = len(lengths)
    q = rng.standard_normal((B, T, N, H)).astype(np.float32)
    k = rng.standard_normal((2, POOL, PS, K, H)).astype(np.float32)
    v = rng.standard_normal((2, POOL, PS, K, Hv)).astype(np.float32)
    table = rng.permutation(POOL)[:B * NP].reshape(B, NP)
    sent = table.copy()
    for b in range(B):
        sent[b, min(NP, (lengths[b] + T - 1) // PS + 1):] = POOL
    if f > 1:
        row = jax.ShapeDtypeStruct((2, POOL, PS, K // f, 128), jnp.float32)
        k_pool, v_pool = (to_pool_rows(jnp.asarray(x), row) for x in (k, v))
    else:   # k rows 192 held as 256 lanes, v rows a lane tile
        k_pool = jnp.pad(jnp.asarray(k), [(0, 0)] * 4 + [(0, 256 - H)])
        v_pool = jnp.asarray(v)
    return (jnp.asarray(q), k_pool, v_pool, jnp.asarray(sent, jnp.int32),
            jnp.asarray(lengths, jnp.int32)), (q, k, v, table, lengths)


def _arithmetic(q, k, v, table, lengths):
    """Row t of slot b attends positions <= lengths[b] + t, a head at a
    time, in float64."""
    B, T, N, H = q.shape
    G = N // k.shape[3]
    out = np.zeros((B, T, N, v.shape[-1]))
    for b in range(B):
        for t in range(T):
            pos = np.arange(min(lengths[b] + t + 1, NP * PS))
            kk = k[LAYER, table[b, pos // PS], pos % PS].astype(np.float64)
            vv = v[LAYER, table[b, pos // PS], pos % PS].astype(np.float64)
            for n in range(N):
                s = kk[:, n // G] @ q[b, t, n].astype(np.float64) * H ** -0.5
                p = np.exp(s - s.max())
                out[b, t, n] = (p / p.sum()) @ vv[:, n // G]
    return out


def _kernel(monkeypatch, geometry, operands, pages):
    """The wrapper's own call with the picker saying ``pages`` (0: left to
    the shapes), and the path it recorded."""
    *_, Hv, f = GEOMETRIES[geometry]
    with monkeypatch.context() as picked:
        if pages:
            picked.setattr(
                tile_math, "paged_fold_pages",
                lambda *a, narrow=False, **kw: pages if narrow else 1)
        da.clear_decode_paths()
        why = []
        out = da.paged_decode_attention(
            *operands, layer=LAYER, interpret=True, why=why,
            heads_per_row=f, v_dim=Hv if f == 1 else 0)
        assert out is not None, why
        (path,) = da.decode_paths()
        da.clear_decode_paths()
    return np.asarray(out, np.float32), path


_A_PAGE_A_FOLD = {}


@pytest.mark.parametrize("pages", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_fold_of_several_pages_is_the_arithmetic(
        geometry, case, pages, monkeypatch):
    operands, plain = _inputs(geometry, case)
    got, path = _kernel(monkeypatch, geometry, operands, pages)
    assert (path.pages, path.form) == (pages, da.FORM_FLAT)
    assert f"{pages} page{'s' if pages > 1 else ''} a fold, a ring of " \
        f"{path.depth}" in path.describe()
    np.testing.assert_allclose(got, _arithmetic(*plain), rtol=2e-5,
                               atol=2e-5)
    # another order of rescaling, the same sum
    key = (geometry, case)
    if key not in _A_PAGE_A_FOLD:
        _A_PAGE_A_FOLD[key] = (got if pages == 1 else _kernel(
            monkeypatch, geometry, operands, 1)[0])
    np.testing.assert_allclose(got, _A_PAGE_A_FOLD[key], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_shapes_pick_the_width(geometry, monkeypatch):
    """Nothing patched: the wrapper asks ``_walk`` and the kernel traces
    what it says (two pages a fold for both, a ring of three groups:
    ``tile_math.PAGED_FOLD_MAX_PAGES``; four is the A/B tool's to ask)."""
    operands, plain = _inputs(geometry, "a_group_and_a_page")
    got, path = _kernel(monkeypatch, geometry, operands, 0)
    assert (path.pages, path.depth) == (PICKED[geometry], 3)
    np.testing.assert_allclose(got, _arithmetic(*plain), rtol=2e-5,
                               atol=2e-5)


def test_a_tail_past_the_last_live_page_reads_no_table_entry_and_no_page(
        monkeypatch):
    """A short last group: the table's entries past the live pages hold
    indices far outside the pool, the pool's other pages NaN. Neither is
    reached: no copy is started for a page past the slot's count (a copy
    of a NaN page into the ring would poison ``p @ v`` even behind the
    mask), and the part of the ring no copy has written is zeros."""
    operands, plain = _inputs("lfm2", "a_group_and_a_page")
    q, k_pool, v_pool, table, lengths = operands
    live = np.zeros(POOL, bool)
    for b, n in enumerate(np.asarray(lengths)):
        live[np.asarray(table)[b, :n // PS + 1]] = True
    poison = jnp.asarray(np.where(live, 0.0, np.nan),
                         jnp.float32)[None, :, None, None, None]
    wild = jnp.where(table == POOL, 2 ** 30, table)
    got, path = _kernel(monkeypatch, "lfm2", (
        q, k_pool + poison, v_pool + poison, wild, lengths), 4)
    assert path.pages == 4 and np.isfinite(got).all()     # asked: 4
    np.testing.assert_allclose(got, _arithmetic(*plain), rtol=2e-5,
                               atol=2e-5)


def test_a_window_rows_bound_past_the_tables_end_stops_at_the_table(
        monkeypatch):
    """The spec window's last rows may point past the capacity; the walk
    of single pages stops at the table's end and the group's must too (its
    tail there lies under those rows' bound)."""
    N, K, H, Hv, f = GEOMETRIES["lfm2"]
    operands, plain = _inputs("lfm2", "a_spec_window")
    q, k_pool, v_pool, table, _ = operands
    # five live pages of six (a group and a page); a row at the capacity
    lengths = jnp.asarray([5 * PS - 2, NP * PS - 1], jnp.int32)
    full = jnp.asarray(plain[3], jnp.int32)
    outs = [_kernel(monkeypatch, "lfm2",
                    (q, k_pool, v_pool, full, lengths), n)[0]
            for n in (1, 4)]
    np.testing.assert_allclose(outs[1], outs[0], rtol=2e-5, atol=2e-5)
    want = _arithmetic(*plain[:4], np.asarray(lengths))
    np.testing.assert_allclose(outs[1], want, rtol=2e-5, atol=2e-5)


# --- a page a fold is the parent's program ---------------------------------
# sha256 of the traced text (``jax.make_jaxpr`` of the wrapper's call: the
# kernel's own jaxpr, its grid, ring and scratch shapes are in it) at the
# geometries that keep a page a fold, taken from the parent commit (06bf403)
# with ``_kernel_text`` below. name -> (N, K, H, window, sliding, kind)
TEXTS = {
    "mistral_8x128": (32, 8, 128, 1, 0, ""),
    "olmoe_two_blocks_of_8": (16, 16, 128, 1, 0, ""),
    "gpt2m_two_heads_a_row": (16, 16, 64, 1, 0, "packed"),
    "kexaone_window_128": (64, 8, 128, 1, 128, ""),
    "a_spec_window_of_5": (32, 8, 128, 5, 0, ""),
    "gpt2m_int8_pool": (16, 16, 64, 1, 0, "int8"),
    "four_heads_int8_per_head": (32, 4, 128, 1, 0, "int8"),
    "mimo_window_layer_with_a_sink": (64, 8, 192, 1, 128, "kinds"),
    # the narrow arm itself, its picker held at a page a fold
    "four_rows_at_a_page_a_fold": (32, 4, 128, 1, 0, "one_page"),
    "mimo_full_at_a_page_a_fold": (64, 4, 192, 1, 0, "kinds_one_page"),
}
PARENT = json.loads((Path(__file__).resolve().parent / "data"
                     / "paged_kernel_digests.json").read_text())


def _kernel_text(name: str) -> str:
    N, K, H, T, sliding, kind = TEXTS[name]
    B, pages, L = 3, 8, 2
    sds = jax.ShapeDtypeStruct
    f = 2 if kind == "packed" else 1
    wide = 256 if "kinds" in kind else 128
    dtype = jnp.int8 if kind == "int8" else jnp.bfloat16
    k = sds((L, pages, PS, K // f, wide), dtype)
    v = sds((L, pages, PS, K // f, 128), dtype)
    scales = sds((pages, PS, K), jnp.float32) if kind == "int8" else None
    sink = sds((N,), jnp.float32) if kind == "kinds" else None

    def call(q, k, v, table, lengths, scales, sink):
        return da.paged_decode_attention(
            q, k, v, table, lengths, layer=LAYER, interpret=False,
            sliding=sliding, k_scale=scales, v_scale=scales, sink=sink,
            heads_per_row=f, v_dim=128 if "kinds" in kind else 0)

    picker = getattr(tile_math, "paged_fold_pages", None)
    if "one_page" in kind:
        tile_math.paged_fold_pages = lambda *a, **kw: 1
    try:
        text = str(jax.make_jaxpr(call)(
            sds((B, T, N, H), jnp.bfloat16), k, v, sds((B, NP), jnp.int32),
            sds((B,), jnp.int32), scales, sink))
    finally:
        if picker is not None:
            tile_math.paged_fold_pages = picker
        da.clear_decode_paths()
    # no object's address, no source line
    return re.sub(r" at 0x[0-9a-f]+|\S+\.py:\d+", "", text)


@pytest.mark.parametrize("name", sorted(TEXTS))
def test_a_page_a_fold_traces_the_parents_program(name):
    text = _kernel_text(name)
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[name]


if __name__ == "__main__":     # python -m tests.test_paged_fold, in a tree
    print(json.dumps({n: hashlib.sha256(_kernel_text(n).encode()).hexdigest()
                      for n in sorted(TEXTS)}, indent=1))
