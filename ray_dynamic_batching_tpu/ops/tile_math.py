"""Shared TPU tile-padding / VMEM-footprint math.

Single source of truth for the padded-footprint model used by BOTH the
runtime KV-tile picker (``ops/decode_attention.py::_pick_sb``) and the
static ``vmem-budget`` checker (``tools/lint``). PR 1 fixed a real bug
where the hand-computed double-buffered footprint undercounted lane
padding (H=64 geometries looked ~2x smaller than their true in-VMEM
size and busted the per-core budget); keeping one implementation here is
what stops the static model and the runtime picker from drifting apart
the same way.

The model (Mosaic's VMEM tiling rules):

- a block's SUBLANE (second-to-last) dim pads up to the dtype's tile
  height — f32 8, bf16 16, int8 32 (``SUBLANE_PACK``);
- its LANE (last) dim pads up to a multiple of 128;
- leading dims multiply unpadded;
- Pallas double-buffers streamed blocks (``DOUBLE_BUFFER``), so the
  in-flight footprint of a grid step is twice the padded block sum (the
  paged kernel copies its pages itself: ``paged_walk_depth`` of them).

Deliberately dependency-free (no jax import): the linter loads this
module standalone so ``python -m tools.lint`` stays fast and runs in
environments without an accelerator stack.
"""

from __future__ import annotations

from typing import Sequence

# Dtype tile height by itemsize: sublane packing halves as elements
# shrink, so SUBLANE_PACK[itemsize] * itemsize == 32 bytes for every
# supported dtype. (That identity is why f32 is the worst-case itemsize
# for a padded footprint: ceil(n/8) >= ceil(n/16) >= ceil(n/32).)
SUBLANE_PACK = {4: 8, 2: 16, 1: 32}

LANE = 128

# Pallas pipelines the next tile's HBM->VMEM copy behind the current
# tile's compute: two buffers per streamed block are resident at once.
DOUBLE_BUFFER = 2

# Per-grid-step VMEM ceiling for a kernel call's streamed blocks:
# footprints count the FULLY padded tiles (sublane AND 128-lane dims)
# double-buffered, so the budget honestly bounds their in-VMEM bytes.
# 15 MB keeps whisper's only legal decode tile (whole S=448, ~14.7 MB
# true) while rejecting the H=64 whole-S tiles the old raw-H budget
# wrongly accepted (~16.8 MB true).
VMEM_BLOCK_BUDGET_BYTES = 15 * 1024 * 1024

# The scoped-VMEM limit every kernel asks Mosaic for
# (``CompilerParams.vmem_limit_bytes``). The block budget above counts
# streamed blocks only; the q/out blocks, the f32 online-softmax scratch
# and the compiler's own temporaries ride alongside, so the limit is
# STATED, with room for them, instead of inherited from whatever the
# compiler defaults to on a given chip — the budget and the limit are one
# decision. A v5e core holds 128 MiB of VMEM (``pltpu.get_tpu_info()``).
# Measured on a v5e, libtpu 0.0.34: the tile at the budget's edge (sb=896
# of S=1792, 14.1 MiB streamed) compiles under this limit, and also under
# the default one.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def sublane_pack(itemsize: int) -> int:
    """Dtype tile height (rows) for an itemsize; unknown itemsizes get
    the f32 pack (f32 is the worst case per byte, see SUBLANE_PACK)."""
    return SUBLANE_PACK.get(itemsize, 8)


def pad_lane(n: int) -> int:
    """Lane (last) dim padded up to a multiple of 128."""
    return -(-n // LANE) * LANE


def pad_sublane(n: int, itemsize: int) -> int:
    """Sublane (second-to-last) dim padded up to the dtype tile height."""
    pack = sublane_pack(itemsize)
    return -(-n // pack) * pack


def padded_block_bytes(block_shape: Sequence[int], itemsize: int) -> int:
    """True in-VMEM bytes of ONE BlockSpec block: both trailing dims
    padded (sublane to the dtype tile height, lane to 128), leading dims
    multiplied unpadded. A 1-D block is a single lane row (sublane 1)."""
    dims = [int(d) for d in block_shape]
    if not dims:
        return itemsize
    lane = pad_lane(dims[-1])
    sub = pad_sublane(dims[-2] if len(dims) >= 2 else 1, itemsize)
    lead = 1
    for d in dims[:-2]:
        lead *= d
    return lead * sub * lane * itemsize


def pages_for(length: int, page_size: int) -> int:
    """Pages needed to hold ``length`` cached positions (ceil division).
    Shared by the engine's allocator bookkeeping and the sim's occupancy
    accounting so the two can never disagree about footprint."""
    if length <= 0:
        return 0
    return -(-int(length) // int(page_size))


def shard_heads(num_kv_heads: int, tp: int) -> int:
    """Per-shard KV-head count under ``tp``-way tensor-parallel head
    sharding: K/tp when tp divides K, else K — an indivisible head axis
    REPLICATES instead of sharding (``parallel/mesh._feasible_spec``),
    so every shard still streams the full head set. Shared by the
    runtime kernel guards (a head-sharded paged kernel's VMEM bytes
    divide by the TP degree) and the standalone-loaded vmem-budget
    lint model, with an agreement pin test so the two cannot drift."""
    tp = int(tp or 1)
    if tp > 1 and num_kv_heads % tp == 0:
        return num_kv_heads // tp
    return num_kv_heads


def spec_scratch_pages(length: int, spec_window: int,
                       page_size: int, capacity: int) -> int:
    """Pages a speculative verify round needs a slot's table to cover:
    the round writes the ``spec_window`` (= spec_tokens + 1) positions
    ``[length, length + spec_window)``, clamped to the slot's logical
    ``capacity``. Shared by the engine's scratch-page reservation
    (``DecodeEngine._reserve_spec_scratch``) and the admission headroom
    rule (``pages_for(len + spec_tokens + 1)``), so the two can never
    disagree about a round's page demand."""
    return pages_for(min(int(length) + int(spec_window), int(capacity)),
                     page_size)


def lane_aligned_page(page_size: int) -> bool:
    """A KV page is tile-legal iff its size is a LANE multiple: the int8
    scale tile streams as [1, kb, page_size] with the page as its lane
    dim, so an unaligned page silently pads every scale tile in VMEM."""
    return page_size > 0 and page_size % LANE == 0


# The flat-heads form of a decode grid step's body
# (``ops/decode_attention.py::_accumulate_tile``) scores all kb heads'
# query rows against the whole [sb * kb] tile, so its two f32
# intermediates (masked scores, probabilities) are [kb * rows, sb * kb]:
# kb times a per-head body's. Measured on a v5e (PERF.md, PR 31) the
# flat form is the cheaper at every row count tried, 1 to 32 rows a
# head over a 128-position page of 8 heads (2 MiB of score tiles at 32
# rows: 1.32 us a step against 2.05 per head); past that nothing is
# measured and the tiles keep growing, so the per-head form, whose
# tiles are [rows, sb], takes over.
FLAT_SCORE_MAX_BYTES = 2 * 1024 * 1024


def _flat_score_bytes(sb: int, kb: int, rows: int) -> int:
    return 2 * padded_block_bytes((kb * rows, sb * kb), 4)


def flat_heads(kb: int, rows: int, sb: int) -> bool:
    """Whether a decode grid step folds its [sb, kb, H] tile with ALL kb
    heads in one contraction (True) or one head at a time — decided from
    the static shape alone, for the kernels, the VMEM model below and
    the ``decode_paths()`` record alike. ``kb`` must be a multiple of 8:
    then the tile's trailing (kb, H) dims are whole (8, 128) tiles and
    [sb * kb, H] is the same bytes, no relayout; a narrower head block
    (K < 8) has no such view. ``rows`` is a head's query rows, Tq * G."""
    return (kb % 8 == 0
            and _flat_score_bytes(sb, kb, rows) <= FLAT_SCORE_MAX_BYTES)


def flat_score_bytes(sb: int, kb: int, rows: int) -> int:
    """In-VMEM bytes of the flat-heads form's two f32 intermediates (0
    for the per-head form, whose [rows, sb] tiles are a few registers);
    at most ``FLAT_SCORE_MAX_BYTES`` by the rule above."""
    return _flat_score_bytes(sb, kb, rows) if flat_heads(kb, rows, sb) else 0


# The most pages the paged kernel's walk holds in VMEM at once: the one
# being folded and the copies in flight behind it. Measured on a v5e
# (PERF.md, PR 33): a ring of two, a grid pipeline's double buffer, keeps
# ONE copy in flight and leaves the DMA's issue latency in every page
# (0.82 us a live page against a 0.64 us copy, what the grid-walk kernel's
# live step cost too); a ring of three reads 0.69; four reads the same.
PAGED_WALK_MAX_DEPTH = 3


def paged_walk_depth(
    page_size: int,
    kb: int,
    H: int,
    kv_itemsize: int,
    with_scales: bool = False,
    window: int = 1,
    G: int = 1,
    pages: int = 1,
) -> int:
    """Items of K and V pages (and their scale rows) the paged kernel's
    page walk keeps in its own VMEM scratch, an item ``pages`` pages (one
    but for a narrow head block: :func:`paged_fold_pages`): the most, up
    to ``PAGED_WALK_MAX_DEPTH``, that :func:`paged_tile_bytes` prices
    within ``VMEM_BLOCK_BUDGET_BYTES``, and never fewer than
    ``DOUBLE_BUFFER`` (a page folding, a page arriving: where even that
    does not fit, the kernel's guard declines)."""
    for depth in range(PAGED_WALK_MAX_DEPTH, DOUBLE_BUFFER, -1):
        if paged_tile_bytes(page_size, kb, H, kv_itemsize, with_scales,
                            window, G, depth, pages
                            ) <= VMEM_BLOCK_BUDGET_BYTES:
            return depth
    return DOUBLE_BUFFER


def paged_tile_bytes(
    page_size: int,
    kb: int,
    H: int,
    kv_itemsize: int,
    with_scales: bool = False,
    window: int = 1,
    G: int = 1,
    depth: int = DOUBLE_BUFFER,
    pages: int = 1,
) -> int:
    """VMEM footprint of one PAGED decode-attention grid step whose page
    walk keeps a ring of ``depth`` items of ``pages`` pages in scratch it
    fills itself. The
    default is the least ring (a page folding, a page arriving): what
    must fit for the kernel to engage at all, so what its runtime guard
    declines by; :func:`paged_walk_depth` is the ring it then takes. The
    model the guard budgets against and the static ``vmem-budget``
    checker holds the call to (the paged analogue of
    :func:`decode_tile_bytes`):

    - K and V page tiles [1, page_size, kb, H] at the cache itemsize
      (trailing dims (kb, H), same padding story as the slab tile);
    - optional K/V scale tiles [1, kb, page_size] f32 (page_size is the
      LANE dim — hence :func:`lane_aligned_page`), in the flat-heads
      form one [1, page_size * kb] lane row each;
    - the flat-heads form's two f32 score tiles
      (:func:`flat_score_bytes`; ``window`` rows x ``G`` decide the form,
      :func:`flat_heads`), one set whatever the ring, as large as a page;
    - NO mask tile: validity is computed in-kernel from the prefetched
      per-slot lengths, so the paged path streams no mask at all.

    ``window`` > 1 (the speculative-verify Tq == k+1 window) adds the
    SCRATCH-HEADROOM term: the q/out blocks ([1, kb, window*G, H]) and
    the f32 online-softmax accumulator ([kb, window*G, H] VMEM scratch)
    grow with the window's row count, and for decode's Tq == 1 they are
    the small riders the base model documents away — a wide window makes
    them first-class.

    ``pages`` > 1 (a narrow head block's fold of several live pages,
    :func:`paged_fold_pages`) multiplies the ring and adds that fold's
    two f32 score tiles [kb * rows, pages * page_size * kb]; at a page a
    fold they are riders too (128 KB at 32 rows), as they always were.
    """
    rows = int(window) * max(1, int(G))
    flat = flat_heads(kb, rows, page_size)
    kv = 2 * padded_block_bytes((1, page_size, kb, H), kv_itemsize)
    scale_shape = (1, 1, page_size * kb) if flat else (1, kb, page_size)
    scale_b = 2 * padded_block_bytes(scale_shape, 4) if with_scales else 0
    total = depth * pages * (kv + scale_b)
    if pages > 1:
        total += 2 * padded_block_bytes(
            (kb * rows, pages * page_size * kb), 4)
    if window > 1:
        qo = 2 * padded_block_bytes((1, kb, rows, H), kv_itemsize)
        acc = padded_block_bytes((kb, rows, H), 4)  # f32 scratch, single
        total += DOUBLE_BUFFER * qo + acc
    return total + flat_score_bytes(page_size, kb, rows)


def decode_tile_bytes(
    sb: int,
    kb: int,
    H: int,
    kv_itemsize: int,
    with_mask: bool,
    with_scales: bool = False,
    window: int = 1,
    rows: int = 0,
) -> int:
    """Double-buffered VMEM footprint of one decode-attention grid
    step's streamed blocks — the exact model ``_pick_sb`` budgets
    against (and the static checker re-evaluates):

    - K and V tiles [1, sb, kb, H] at the cache itemsize (trailing dims
      (kb, H): kb pads to the dtype tile height, H to 128 lanes — the
      H=64 lane padding PR 1's fix made honest);
    - optional mask tile [1, window, sb] int8 (window <= 8 pads to the
      int8 tile height 32; sb is the lane dim);
    - optional K/V scale tiles [1, kb, sb] f32.

    ``rows`` (a head's query rows, Tq * G) lets the model follow the
    body's form (:func:`flat_heads`): the flat form streams its mask and
    scale tiles in its own column order ([1, window, sb * kb] and one
    [1, sb * kb] lane row) and carries its two f32 score tiles. 0 is the
    per-head model, whatever the shape.
    """
    flat = rows > 0 and flat_heads(kb, rows, sb)
    cols = sb * kb if flat else sb
    kv = 2 * padded_block_bytes((1, sb, kb, H), kv_itemsize)
    mask_b = padded_block_bytes((1, window, cols), 1) if with_mask else 0
    scale_b = (2 * padded_block_bytes((1, 1 if flat else kb, cols), 4)
               if with_scales else 0)
    return DOUBLE_BUFFER * (kv + mask_b + scale_b) + (
        flat_score_bytes(sb, kb, rows) if flat else 0)


def window_table_width(sliding: int, rows: int, page_size: int,
                       n_entries: int) -> int:
    """Page-table columns a paged decode scan CAN walk: all ``n_entries``
    of a full layer (``sliding`` 0); for a sliding layer the most pages
    that the ``sliding + rows - 1`` positions its ``rows`` window rows
    attend between them can touch (window 128 on 128-position pages, one
    row: 2). Static: the most :func:`live_pages` counts at any length, the
    gather fallback's view, the denominator of the engine's live share."""
    if sliding <= 0:
        return n_entries
    span = sliding + rows - 1
    return min(n_entries, (span + page_size - 2) // page_size + 1)


def window_first_page(lengths, sliding: int, page_size: int):
    """The table column of the oldest position a sliding layer's scan
    attends: its first window row sits at position ``lengths`` and sees
    back to ``lengths - sliding + 1``. Plain arithmetic, so that the
    kernel's wrapper (traced), the engine's page counts (numpy) and a
    test (ints) share the one rule."""
    oldest = lengths - (sliding - 1)
    return (oldest > 0) * (oldest // page_size)


def _at_most(x, cap):
    """``min(x, cap)`` in plain arithmetic (ints, numpy arrays and a
    kernel's traced scalars alike)."""
    return x - (x > cap) * (x - cap)


def live_pages(lengths, rows: int, sliding: int, page_size: int,
               n_entries: int):
    """The page-table columns a paged decode scan walks for a slot of
    ``lengths`` cached positions, as ``(first, count)``: the ONE rule of
    the kernel's loop, the engine's ``kv_pages_live`` counter and the
    tests. Window row t of ``rows`` attends positions <= ``lengths`` + t,
    so the last column is that of position ``lengths + rows - 1``, held
    inside the table's ``n_entries``; the first is column 0, or, for a
    layer ``sliding`` over a window, :func:`window_first_page`'s. Position
    0 is always within the bound: ``count`` >= 1 (an idle slot walks its
    first page), and <= :func:`window_table_width`. Plain arithmetic, as
    :func:`window_first_page` is."""
    last = _at_most(lengths + (rows - 1),
                    n_entries * page_size - 1) // page_size
    if sliding <= 0:
        return 0, last + 1
    first = _at_most(window_first_page(lengths, sliding, page_size), last)
    return first, last + 1 - first


def moe_tile_cols(k_dim: int, n_dim: int, n_weights: int,
                  itemsize: int) -> int:
    """Output-column tile of the grouped expert matmul
    (``ops/moe.py::_grouped_matmul``): the widest of 512, 256, 128 that
    divides ``n_dim`` and whose weight blocks, ``n_weights`` of
    [k_dim, tile] double-buffered (the blocks a grid step streams; the row
    tile and the f32 products ride alongside, as q and the scratch do in
    the decode kernels), fit ``VMEM_BLOCK_BUDGET_BYTES``. At a contracted
    width of 2,048 a gated step's two blocks of 512 are 8 MiB; at 6,144
    they would be 24 of the 32 MiB limit, so that step takes 256 (12 MiB).
    A wide tile matters: a block's rows are ``tile`` contiguous elements
    in HBM, and a step moves megabytes against its fixed cost."""
    for tn in (512, 256):
        if n_dim % tn == 0 and (
                DOUBLE_BUFFER * n_weights
                * padded_block_bytes((k_dim, tn), itemsize)
                <= VMEM_BLOCK_BUDGET_BYTES):
            return tn
    return 128


# --- a narrow head block's page as whole (8, 128) tiles -------------------
# (Appended BELOW everything a kernel of ``ops/decode_attention.py`` traces:
# a Mosaic module carries the source lines of ``live_pages`` and its
# helpers, and a line moved above them compiles every model's programs
# again.)
def page_view_fold(kb: int, page_size: int) -> int:
    """``f``, the positions whose ``kb`` heads make up ONE (8, 128) tile of
    a page [page_size, kb, H] with a head block NARROWER than 8. The pool
    lies in HBM a (kb, 128) tile a position, ``f = 8 // kb`` positions'
    tiles in a row, so [page_size // f, kb * f, H] is the same bytes (the
    compiler takes the pool's reshape as a bitcast), arrives in VMEM as
    whole tiles, and flattens to [page_size * kb, H] with no relayout, as
    an 8-head block does (:func:`flat_heads`). 1 where there is no such
    view: 8 heads or more, a width that does not divide 8, a page that
    ``f`` does not."""
    if 0 < kb < 8 and 8 % kb == 0 and page_size % (8 // kb) == 0:
        return 8 // kb
    return 1


def sparse_tile_bytes(
    page_size: int,
    kb: int,
    H: int,
    kv_itemsize: int,
    G: int,
    n_entries: int,
    fold: int = 1,
    flat: bool = True,
    depth: int = DOUBLE_BUFFER,
    pages: int = 1,
) -> int:
    """VMEM footprint of one grid step of the sparse mask-form kernel
    (``ops/sparse_attention.py::_sparse_paged_decode_attention``), the
    tiles AS THEY LIE: a ring of ``depth`` slots of ``pages`` K and V
    pages in scratch, each page [page_size // fold, kb * fold, H]
    (``fold`` > 1: the view above, whole tiles where [page_size, kb, H]
    pads ``kb`` up to the dtype's tile height); the slot's selection
    [n_entries // pages, pages * cols] int32, a pipelined block; and,
    where the fold is ``flat`` (every head in one contraction), its two
    f32 score tiles [kb * G, pages * cols]. The padding is
    :func:`padded_block_bytes`'s, the one the ``vmem-budget`` rule prices
    a scratch shape by (bf16's 8-row tiles count as 16: never under)."""
    cols = pages * (page_size * kb if flat else page_size)
    ring = 2 * padded_block_bytes(
        (1, pages * page_size // fold, kb * fold, H), kv_itemsize)
    sel = DOUBLE_BUFFER * padded_block_bytes(
        (1, n_entries // pages, cols), 4)
    scores = 2 * padded_block_bytes((kb * G, cols), 4) if flat else 0
    return depth * ring + sel + scores


def sparse_walk_depth(page_size: int, kb: int, H: int, kv_itemsize: int,
                      G: int, n_entries: int, fold: int = 1,
                      flat: bool = True, pages: int = 1) -> int:
    """:func:`paged_walk_depth` for the sparse kernel's ring, priced by
    :func:`sparse_tile_bytes`."""
    for depth in range(PAGED_WALK_MAX_DEPTH, DOUBLE_BUFFER, -1):
        if sparse_tile_bytes(page_size, kb, H, kv_itemsize, G, n_entries,
                             fold, flat, depth, pages
                             ) <= VMEM_BLOCK_BUDGET_BYTES:
            return depth
    return DOUBLE_BUFFER


# The most live pages the sparse kernel folds in ONE online-softmax
# update. A page's fold is a serial chain (score product, running max,
# exp, value product, rescale of the accumulator) that the next page's
# waits on; with a narrow head block the chain, not the page's copy, sets
# the pace, and folding several pages an update pays it once for all of
# them (the latent kernel's ``FOLD_PAGES``; the chip's readings at 1, 2
# and 4 pages of 4 heads: PERF.md, PR 51).
SPARSE_FOLD_MAX_PAGES = 4


def sparse_fold_pages(page_size: int, kb: int, H: int, kv_itemsize: int,
                      G: int, n_entries: int, fold: int = 1,
                      own: bool = True) -> int:
    """Live pages the sparse kernel folds an update, from the shapes
    alone: the largest of 4, 2, 1 that divides the table's ``n_entries``
    (the selection is handed in a row a fold) and whose ring still takes
    a depth past ``DOUBLE_BUFFER`` within ``VMEM_BLOCK_BUDGET_BYTES``
    (:func:`sparse_tile_bytes`). Only where the fold is ``own``, the flat
    form for a narrow head block (``decode_attention._fold_flat``: a
    column is position ``c // kb`` of head ``c % kb`` however many pages
    lie one after another); the shared forms keep a page a fold."""
    return _fold_pages(
        SPARSE_FOLD_MAX_PAGES if own else 1,
        lambda pages: not n_entries % pages and sparse_tile_bytes(
            page_size, kb, H, kv_itemsize, G, n_entries, fold, True,
            DOUBLE_BUFFER + 1, pages) <= VMEM_BLOCK_BUDGET_BYTES)


def _fold_pages(most: int, fits) -> int:
    """The ONE rule of the page walks' fold width: the largest of ``most``
    and its halves down to 1 live page an update that ``fits`` (a ring of
    them past the double buffer within the VMEM budget, by the kernel's
    own model)."""
    pages = most
    while pages > 1 and not fits(pages):
        pages //= 2
    return pages


# The most live pages the DENSE paged kernel's narrow arm folds in one
# update. Measured on a v5e at LFM2's 4 rows x 128 lanes a position, 64
# slots (PERF.md, PR 52; profiles/tpu_v5e/paged_steps.json): a live page
# 0.509 | 0.345 | 0.340 us at 1 | 2 | 4 pages a fold against a 0.32 us
# copy, so two pages already pay the chain; a call over slots of TWO live
# pages 96.8 | 68.2 | 82.4 us (a group of four folds two dead pages'
# columns and its first fold waits for more copies), over 8 and 32 live
# pages 196.5 | 197.5 and 729.4 | 729.9 at 2 | 4: four is never faster
# and a fifth slower at the lengths a slot starts at.
PAGED_FOLD_MAX_PAGES = 2


def paged_fold_pages(page_size: int, kb: int, H: int, kv_itemsize: int,
                     window: int = 1, G: int = 1,
                     narrow: bool = False) -> int:
    """Live pages the DENSE paged kernel folds an update, from the shapes
    alone (:func:`_fold_pages`, priced by :func:`paged_tile_bytes`): 2
    (``PAGED_FOLD_MAX_PAGES``) where its head block is ``narrow``
    (``decode_attention._narrow_fold``: fewer than 8 rows a position, all
    of K, a bf16 pool), whose fold's serial chain and not the page's copy
    set the pace, unless even two pages' ring of three busts the budget;
    1 for a block of 8 heads (a page 0.69 us against its 0.64 us copy:
    finished) and for an int8 pool."""
    return _fold_pages(
        PAGED_FOLD_MAX_PAGES if narrow else 1,
        lambda pages: paged_tile_bytes(
            page_size, kb, H, kv_itemsize, False, window, G,
            DOUBLE_BUFFER + 1, pages) <= VMEM_BLOCK_BUDGET_BYTES)


def ssm_update_tile_bytes(hb: int, P: int, N: int) -> int:
    """VMEM footprint of one grid step of the state-space update kernel
    (``ops/ssm_update.py``): ``hb`` heads' float32 states ``[hb, P, N]``
    in and out, each double-buffered, with the ``[hb, P]`` rows of ``dt x``
    and ``y`` beside them."""
    state = padded_block_bytes((hb, P, N), 4)
    rows = padded_block_bytes((hb, P), 4)
    return 2 * DOUBLE_BUFFER * (state + rows)


def ssm_update_heads(group_heads: int, P: int, N: int) -> int:
    """Heads a tile of the state-space update kernel, from the shapes
    alone: a whole group (its heads share ONE row of ``B`` and of ``C``),
    halved while it is even and its tiles pass ``VMEM_BLOCK_BUDGET_BYTES``.
    Measured on a v5e at Falcon-H1's 64 slots x 32 heads x [128, 256]
    float32 (PERF.md, PR 54; profiles/tpu_v5e/ssm_update.json): a layer
    830.3 | 829.5 | 850.4 us at 16 | 8 | 4 heads a tile (2 | 1 | 0.5 MB)
    against 1,186.1 in XLA, and 828.5 with the tile's two copies alone and
    nothing computed: the copies set the pace, a step's ~0.35 us shows only
    below 1 MB, and the whole group is never slower."""
    hb = group_heads
    while hb % 2 == 0 and ssm_update_tile_bytes(
            hb, P, N) > VMEM_BLOCK_BUDGET_BYTES:
        hb //= 2
    return hb
