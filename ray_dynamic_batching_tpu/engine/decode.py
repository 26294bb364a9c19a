"""Continuous-batching decode engine — slot-based KV-cache serving.

New capability relative to the reference, which serves single-shot vision
models only (SURVEY.md §7 stage 7; the reference's executor takes one batch,
runs one forward, returns — ``293-project/src/scheduler.py:435-472``).
Autoregressive decode for the BASELINE.json GPT-2/Llama configs needs a
different hot loop: requests *join and leave* a long-running batch between
steps (Orca-style continuous batching).

TPU-first design — everything is static-shape so exactly TWO kinds of
compiled programs serve the whole stream:

- ``chunk_prefill[g, W]``: one per (group width, chunk width). Runs the
  next ``<=W`` prompt tokens of up to ``g`` admissions and scatters their
  k/v straight through each admission's page-table row into the shared
  page pool (``engine/paging.py``: one pool of lane-aligned pages behind
  per-slot page tables, prefix and session reuse by page reference), and
  returns the first sampled token of every row whose prompt ends in this
  chunk.
- ``decode_step``: one program for all ``num_slots`` slots, every step.
  Inactive slots are masked, their scatters dropped. Greedy sampling happens
  *in-program* (argmax over vocab) so only ``[B]`` token ids — not ``[B, V]``
  logits — cross the device→host boundary per step.

The pool is **donated** through both programs, so XLA updates it in
place in HBM — zero realloc, zero copy per token (SURVEY.md §7 hard part (e)).
Admission between steps pulls from the shared :class:`RequestQueue`, keeping
the Nexus staleness-discard and SLO accounting on the decode path too.

Two throughput levers on the hot loop:

- **Decode horizon**: when the batch is full (or nothing is waiting), the
  engine runs ``decode_horizon`` steps in ONE compiled ``lax.scan`` program
  per host round-trip, so the per-token device→host sync (the dominant
  non-FLOP cost of continuous batching) is amortized h-fold. Slots that hit
  EOS mid-horizon produce discarded tokens for the remainder — bounded waste
  traded for sync amortization. With free slots and a non-empty queue the
  engine drops to single steps so admissions stay prompt.
- **Token-budgeted chunked admission**: EVERY admission is a chunk train
  — the prompt split into compiled ``<=C``-token chunk programs whose
  k/v scatter straight through the slot's page table (pages granted per
  chunk from the shared allocator, CoW-borrowed prefix pages skipped) —
  and the engine's own step loop spends at most
  ``prefill_token_budget`` tokens advancing pending trains between
  decode turns. A burst of arrivals therefore never stalls an active
  slot behind a serial prefill train (head-of-line blocking): the stall
  bound is ONE chunk program per decode turn, regardless of how much
  prefill is queued. The final chunk program samples the first token
  in-program, so TTFT ends at a ``[B]`` ids fetch — never a logits
  round-trip.

Streaming: requests carrying a :class:`~.request.TokenStream` get every
token pushed as it reaches the host, before the sequence finishes (ref
generator batches, ``serve/batching.py:209-276``).
"""

from __future__ import annotations

import collections
import contextlib
import heapq
import itertools
import math
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import (
    Any,
    Callable,
    Dict,
    Iterator,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from ray_dynamic_batching_tpu.engine.request import (
    BadRequest,
    Request,
    RequestDropped,
    now_ms,
)
from ray_dynamic_batching_tpu.engine.paging import (
    HostSpillTier,
    PageAllocator,
    PagedPrefixCache,
    PagedSessionCache,
    PageEventJournal,
    digest_chain,
    table_array,
)
from ray_dynamic_batching_tpu.engine.pagefabric import (
    PREFIX,
    STREAM,
    PageParcel,
    export_prefix_parcel,
    export_stream_parcel,
)
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.models.causal_lm import merge_routing_counters
from ray_dynamic_batching_tpu.models.kv_state import (
    commit_row,
    refuse_unsupported,
    ring_table,
)
from ray_dynamic_batching_tpu.ops import jit_model, tile_math
from ray_dynamic_batching_tpu.ops.tile_math import (
    lane_aligned_page,
    pages_for,
    spec_scratch_pages,
)
from ray_dynamic_batching_tpu.utils.compile_ledger import (
    PHASE_WARMUP,
    get_ledger,
    instrument,
)
from ray_dynamic_batching_tpu.profiles.table import bucket_up
from ray_dynamic_batching_tpu.utils.concurrency import OrderedLock
from ray_dynamic_batching_tpu.utils.logging import get_logger
from ray_dynamic_batching_tpu.utils import metrics as m
from ray_dynamic_batching_tpu.utils.tracing import link_to as _link_to
from ray_dynamic_batching_tpu.utils.tracing import tracer as _tracer

logger = get_logger("decode")

TOKENS_TOTAL = m.Counter(
    "rdb_decode_tokens_total", "Generated tokens", tag_keys=("model",)
)
DECODE_STEPS = m.Counter(
    "rdb_decode_steps_total", "Decode steps executed", tag_keys=("model",)
)
PREFILLS_TOTAL = m.Counter(
    "rdb_decode_prefills_total", "Prompts prefilled", tag_keys=("model",)
)
TTFT_MS = m.Histogram(
    "rdb_decode_ttft_ms", "Time to first token", tag_keys=("model",)
)
TTFT_QUEUE_MS = m.Histogram(
    "rdb_decode_ttft_queue_ms",
    "Arrival->dequeue share of TTFT (includes waiting out in-flight scans)",
    tag_keys=("model",),
)
TTFT_PREFILL_MS = m.Histogram(
    "rdb_decode_ttft_prefill_ms",
    "Dequeue->first-token share of TTFT",
    tag_keys=("model",),
)
ACTIVE_SLOTS = m.Gauge(
    "rdb_decode_active_slots", "Slots currently decoding", tag_keys=("model",)
)


@dataclass
class DecodeResult:
    """Fulfilled into the request future when a sequence finishes."""

    tokens: List[int]
    finish_reason: str            # "eos" | "length" | "capacity"
    ttft_ms: float
    total_ms: float


@dataclass
class _Slot:
    request: Optional[Request] = None
    generated: List[int] = field(default_factory=list)
    max_new_tokens: int = 0
    prefill_done_ms: float = 0.0
    last_token: int = 0
    stop: frozenset = frozenset()  # per-request stop token ids
    session_id: Optional[str] = None        # store row on finish
    prompt_tokens: Optional[np.ndarray] = None  # session history head
    # Physical page ids in logical order; the first ``shared_pages`` of
    # them are borrowed (refcounted) from a prefix/session entry and are
    # never written by this slot.
    pages: List[int] = field(default_factory=list)
    shared_pages: int = 0

    @property
    def free(self) -> bool:
        return self.request is None


@dataclass
class _ChunkTrain:
    """One admission mid-chunked-prefill: the unit the token-budget
    scheduler advances between decode turns. The train HOLDS its slot
    (``_free_slots`` excludes it) and the pages granted so far
    (``opts['_pages']``: CoW-borrowed head + per-chunk grants);
    ``pos`` is the next global position to prefill, ``base`` the first
    position this train computes (positions below it were seeded from
    borrowed prefix/session pages)."""

    req: Request
    prompt: np.ndarray
    opts: Dict
    slot_idx: int
    C: int                 # chunk width (compiled program shape)
    pos: int = 0           # next global position to prefill
    base: int = 0          # first computed position (CoW/session skip)
    total: int = 0         # prompt length (prefill ends here)
    started_ms: float = 0.0


class _IssuedTurn(NamedTuple):
    """A decode scan dispatched and not fetched yet: what
    ``DecodeEngine._complete_turn`` needs to fetch and harvest it, as taken
    at the dispatch (the loop runs admission and the next chunk group's
    dispatch between the two)."""

    packed: Any                     # device array: tokens, advanced, lengths
    seq: int                        # the program's number (``_note_issue``)
    h: int
    t_dispatch: float
    t_issued: float
    queued_behind: int
    active_at_dispatch: np.ndarray  # the mask the scan ran with
    prev_tokens: np.ndarray         # draft catch-up window head
    trains: int
    kv_pages_live: float
    kv_rows: Tuple[int, int]
    kv_full_pages_live: int = 0
    kv_latent_rows: int = 0
    requests: Tuple = ()            # each slot's tenant at the dispatch
    ahead: bool = False             # issued before the scan ahead was fetched


class _IssuedGroup(NamedTuple):
    """A chunk group dispatched and not completed yet (``finals``: rows that
    end their prompt, whose first tokens ``first`` carries)."""

    first: Any                      # device array: first tokens (+ routing)
    seq: int
    trains: List[_ChunkTrain]
    finals: List[Tuple[int, _ChunkTrain]]
    group: int                      # compiled rows (>= len(trains))
    t_dispatch: float
    t_issued: float
    queued_behind: int
    active: int
    pending: int
    state_resets: int = 0           # rows that began their prompt (conv)
    state_carries: int = 0          # rows begun from a carried state


class _ChunkFields(NamedTuple):
    """A chunk group's ONE int32 ``[group, cols]`` buffer cut into its
    fields, a row a train (``DecodeEngine._cut_chunk_group``: on the host
    numpy views to fill, in the program slices of the upload). The two
    float32 fields lie in the buffer as their bits."""

    tokens: Any      # [g, W]
    mask: Any        # [g, W] 1 on a row's real tokens
    table: Any       # [g, NP] the row's page-table row
    ring: Any        # [g, NP] its slot's ring table; None without rings
    meta_i: Any      # [g, 6] slot-or-sentinel, start, take_idx, top_k,
    #                  seed, new_len (+ 1, a model with a conv state a
    #                  slot: the row's slot, final chunk or not)
    meta_f: Any      # [g, 2] float32: temperature, top_p
    bias_ids: Any    # [g, E]
    bias_vals: Any   # [g, E] float32


class Turn(NamedTuple):
    """One device dispatch of the engine, as the engine thread saw it: a
    record of ``DecodeEngine.turns``. ``kind`` is ``"turn"`` (a decode or
    speculative scan) or ``"chunk"`` (a chunk group). Stamps are ``now_ms()``
    on the engine thread, in order: ``t_dispatch``, ``t_issued`` (the
    jitted call returned), ``t_fetched`` (the result reached the host;
    0.0 where nothing was fetched — a non-final chunk — so the device may
    still be running it), ``t_done`` (harvest / registration done). The
    ring is in ``t_dispatch`` order; a scan is fetched LAST (the loop issues
    the next chunk group behind it first), so a record's ``t_dispatch`` may
    precede the previous record's ``t_fetched``. ``queued_behind`` counts
    the programs issued before this one whose completion the host had not
    seen at ``t_dispatch`` (a fetch of program k proves every program
    issued up to k done; 0: the device was known empty). The host gap
    before a dispatch with ``queued_behind`` 0 is its ``t_dispatch`` less
    the previous record's ``t_fetched``: host time with an empty device.
    The load the
    engine stood under — ``trains`` at the dispatch; ``queue_len``,
    ``pages_allocated`` and ``positions_cached`` as the record is written,
    at ``t_done`` — is what :func:`summarize_turns` shows beside each of
    the longest gaps.

    An expert model's decode scans and paged chunk groups also carry their
    routing, counted on the device over REAL tokens only (active slots, a
    chunk's unpadded tokens) and fetched with the tokens: ``moe_rows``
    (token-expert pairs routed, all layers and substeps), ``moe_experts_hit``
    (experts with at least one real row, summed over layers and substeps)
    and ``moe_max_rows`` (the most rows one expert took in one layer of one
    substep). Where the model holds one rank's share of its experts these
    three count the experts HELD here, and ``moe_pairs`` all real pairs
    wherever they were routed. All 0 for a dense model and for a chunk
    group that finished no prompt (nothing of it was fetched).

    A scan carries ``kv_pages_live``: the sum over ALL slots,
    from their cached lengths at the dispatch, of the page-table entries
    that hold a position the scan's first substep may attend (an idle slot
    counts its page 0, as the paged kernel does): the part of the table the
    kernel's scan has to compute. Where layers differ (sliding-window
    layers walk and find live the columns of their window only) it is the
    mean over layers. 0 for a chunk group.

    A scan of a model whose layers SELECT what they attend (an indexer,
    ``ops/sparse_attention.py``) also carries ``kv_rows_live``, the sum over
    all slots and selecting layers of the positions the scan's first substep
    may attend (the cached length and the token being written; from the
    host's lengths, as ``kv_pages_live`` is), and ``kv_rows_selected``, the
    sum of ``min(that, index_topk)``: the rows its attention folds. Both 0
    for any other model.

    A scan of a model with state BY LAYER KIND (``PagedKVCache.ring_k``)
    also carries ``kv_full_pages_live``: the pool's pages, summed over all
    slots, that a FULL layer's scan walks in its first substep (the
    allocator's pages are the full layers' alone). A sliding layer's ring
    is as many pages a slot whatever the traffic, so it is no counter: the
    engine says it once (``snapshot()["kv_pool"]["ring_pages_per_slot"]``,
    the gauge ``rdb_decode_kv_pool_bytes{kind}``). 0 for any other model.

    A scan of a model with a LATENT pool (``PagedKVCache.latent``) also
    carries ``kv_latent_rows``: the pool rows, summed over all slots and
    layers, that its first substep's scans read (whole live pages, by
    ``tile_math.live_pages``' rule, from the host's lengths). 0 for any
    other model.

    A chunk group of a model with CONV layers (``PagedKVCache.conv_state``)
    also carries ``state_resets``, its rows that began their prompt (the
    program zeroed their slots' states), and ``state_carries``, its rows
    that began from the state an earlier chunk of their train left. Their
    sum is the chunks run. Both 0 for a scan and for any other model.

    A scan of a HYBRID model (``PagedKVCache.ssm_state``) carries
    ``ssm_state_bytes``: the bytes of state-space state its substeps had to
    move, active slots x layers x a slot's matrices (in the plane's own
    dtype) x 2 (read and written) x substeps. 0 for a chunk group and for
    any other model.

    Where the engine thread's time went (:func:`thread_parts`: the records
    are appended in ``t_done`` order, so consecutive ``t_done``s tile the
    thread). ``t_fetch`` is ``now_ms()`` immediately before the one fetch
    (0.0 exactly where ``t_fetched`` is), and ``ready_at_fetch`` the
    result's ``is_ready()`` just before it: True, the device had finished
    and waited for the host (the fetch is a copy); False, the host waited.
    ``idle_ms``: the wall time inside ``rdb.engine.idle_wait`` since the
    previous record (else 0.0). ``cpu_ms``: what the thread's CPU clock
    (``time.thread_time()``) moved since the previous record was written;
    0.0 where another thread wrote that one (the baseline is a thread's).
    A READING, in no share: where that clock moves in 10 ms ticks it
    charges a burst of a millisecond 0.6 to 8.6 times its length, and only
    a stall of seconds is told by it (on the CPU, or off it). ``seq``: the
    program's number (:meth:`DecodeEngine._note_issue`), which its
    ``.dispatch`` and ``.fetch`` phases carry under a profiler session.

    ``ahead``: a scan dispatched BEFORE the scan in front of it was fetched,
    its pending tokens read on the device from that scan's last substep
    (:meth:`DecodeEngine._horizon_ahead`). ``wasted_substeps``: the substeps
    such a scan ran for slots whose tenant had ended in the scan in front
    (an EOS or a stop id nobody knew of at the dispatch): rows x substeps,
    their tokens discarded."""

    kind: str
    t_dispatch: float
    t_issued: float
    t_fetched: float
    t_done: float
    substeps: int           # decode substeps (0 for a chunk)
    tokens: int             # prefill tokens (0 for a turn)
    active: int             # decoding slots at dispatch
    trains: int             # chunk trains pending at dispatch
    queue_len: int          # requests queued at t_done
    pages_allocated: int    # pages of the pool held at t_done
    positions_cached: int   # sum of the slots' cached lengths at t_done
    after_idle: bool        # an idle wait lay between this and the last
    moe_rows: int = 0
    moe_experts_hit: int = 0
    moe_max_rows: int = 0
    kv_pages_live: float = 0
    moe_pairs: int = 0
    kv_rows_live: int = 0
    kv_rows_selected: int = 0
    queued_behind: int = 0
    kv_full_pages_live: int = 0
    kv_latent_rows: int = 0
    state_resets: int = 0
    state_carries: int = 0
    ssm_state_bytes: int = 0
    t_fetch: float = 0.0
    ready_at_fetch: bool = False
    idle_ms: float = 0.0
    cpu_ms: float = 0.0
    seq: int = 0
    ahead: bool = False
    wasted_substeps: int = 0


# An engine's prompt buckets where its builder names none.
DEFAULT_PROMPT_BUCKETS = (16, 32, 64, 128)

# Holds the benchmark's 51 s window at 320 dispatches a second. The cells'
# highest rates an engine were 100/s (chat), 129/s (long-context) and 134/s
# (x4: 2,756 records in 20.6 s; PR 48); chat's rose to 114/s with scans
# issued ahead (PR 56: the tile is shorter) and x4's engines are never idle.
# A cell that dispatches faster wraps it: readers give nothing (``dropped``).
_TURN_RING = 16384
# Scans are issued ahead while fewer than this share of the engine's recent
# scan fetches (a moving average over about ``_READY_TURNS`` of them:
# ``DecodeEngine._note_ready``) found their result ready. The chip read
# (PR 56) 0.000-0.004 of the fetches ready in every one-chip cell, in either
# order (the device is the pace), and on four engine threads under one
# interpreter lock 0.16-0.18 in today's order, 0.93-0.95 with scans ahead.
AHEAD_READY_MAX = 1 / 32
_READY_TURNS = 128
# A record whose thread was blocked in its fetch, or busy on the host's side,
# for longer than this is logged as it is written
# (``DecodeEngine._log_dispatch``).
_STALL_WARN_MS = 1000.0
_ENGINE_ORDINAL = itertools.count()   # numbers the engines of a process

STARTUP_PROGRAM = "rdb.startup.warmup.program"
STARTUP_BUILD = "rdb.startup.engine_build"
STARTUP_DEPLOY = "rdb.startup.deploy"
# What the compile ledger leaves on a start-up span it charged.
_STARTUP_COMPILE_MS = ("trace_ms", "lower_ms", "backend_ms")


def startup_rows(spans: Sequence[Any]) -> List[Dict[str, Any]]:
    """Start-up spans (``utils/tracing.py``: ``Tracer.startup``) as rows
    for an operator, oldest first: ``id`` / ``parent``, ``name``, the
    span's attributes (``replica``, ``program``, ``key``, and what the
    compile ledger charged it: ``trace_ms``, ``lower_ms``, ``backend_ms``,
    ``cache``, ...), ``start_ms`` from the first row's start, ``dur_ms``,
    and ``self_ms``: the time no child span covers."""
    spans = sorted(spans, key=lambda sp: sp.start_ms)
    covered: Dict[int, float] = collections.defaultdict(float)
    for sp in spans:
        if sp.parent_id is not None:
            covered[sp.parent_id] += sp.duration_ms()
    t0 = spans[0].start_ms if spans else 0.0
    return [dict(sp.attributes, id=sp.span_id, parent=sp.parent_id,
                 name=sp.name, start_ms=sp.start_ms - t0,
                 dur_ms=sp.duration_ms(),
                 self_ms=sp.duration_ms() - covered[sp.span_id])
            for sp in spans]


def startup_sums(rows: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Where a start's seconds went, from :func:`startup_rows` (the one
    definition of the benchmark's ``startup_*`` metrics): over the warmed
    programs' spans, ``trace_lower_s`` (Python, paid at every start),
    ``backend_s`` (a cache read when warm, XLA and Mosaic when cold) and
    ``first_run_s`` (their ``run_ms``: executable load, uploads, the first
    execution), which three are those spans' time; ``engine_build_s``;
    and, under a ``rdb.startup.deploy`` span (``deploy_s``; else None),
    ``unaccounted_s``: the deploy less those four (controller, router,
    gaps between replicas, the spans' self time). ``cache_hits`` /
    ``cache_misses`` count over every row."""
    programs = [r for r in rows if r["name"] == STARTUP_PROGRAM]
    out: Dict[str, Any] = {
        "trace_lower_s": sum(r.get("trace_ms", 0.0) + r.get("lower_ms", 0.0)
                             for r in programs) / 1000.0,
        "backend_s": sum(r.get("backend_ms", 0.0)
                         for r in programs) / 1000.0,
        "first_run_s": sum(r.get("run_ms", 0.0) for r in programs) / 1000.0,
        "engine_build_s": sum(r["dur_ms"] for r in rows
                              if r["name"] == STARTUP_BUILD) / 1000.0,
        "cache_hits": int(sum(r.get("cache_hits", 0) for r in rows)),
        "cache_misses": int(sum(r.get("cache_misses", 0) for r in rows)),
        "deploy_s": None, "unaccounted_s": None,
    }
    deploys = [r for r in rows if r["name"] == STARTUP_DEPLOY]
    if deploys:
        out["deploy_s"] = sum(r["dur_ms"] for r in deploys) / 1000.0
        out["unaccounted_s"] = out["deploy_s"] - (
            out["trace_lower_s"] + out["backend_s"] + out["first_run_s"]
            + out["engine_build_s"])
    return out


def summarize_turns(turns: Sequence[Turn], num_slots: int, dropped: int = 0,
                    span_ms: Optional[float] = None,
                    longest: int = 8,
                    table_entries: int = 0,
                    full_table_entries: int = 0) -> Dict[str, Any]:
    """Turn records summed, for an operator asking a slow replica where
    its time goes (the one definition of every quantity read from the
    ring): dispatches and scans held (and ``dropped`` by the bounded ring),
    decode substeps per scan, mean slot occupancy over the scans' substeps,
    and the host gaps — a dispatch's ``t_dispatch`` less the previous
    record's ``t_fetched``, where that one was fetched, no idle wait lay
    between and the dispatch found nothing queued on the device
    (``queued_behind`` 0): host time with an empty device.
    ``overlapped_dispatch_share`` is the dispatches with ``queued_behind``
    above 0 over all dispatches: how often the host's work for a program
    hid behind another's run. ``host_gap_share`` is the gaps'
    sum over ``span_ms`` (default: first dispatch to last ``t_done``),
    ``host_gap_ms`` their median, p99, maximum and sum with its split:
    ``harvest`` (fetch -> the earlier record's work done) and ``feed`` (from
    there to the dispatch: fabric, admit, prefill scheduling, the turn's
    preparation). ``longest_gaps`` lists the largest with that split and
    the load the engine stood under: chunk trains pending at the dispatch
    that ended the gap; queued requests, pages allocated and positions
    cached as the earlier record was written, inside the gap. An expert
    model's records add ``moe_rows_per_expert`` (rows routed over experts
    hit: how many rows share one read of an expert's weights) and
    ``moe_imbalance`` (the most rows one expert took in a layer of a
    substep, over that mean), over the experts held here, and
    ``moe_held_rows_share``: the routed pairs that landed on them, of all
    real pairs (1 where every expert is held; an even router gives a rank
    its share of the experts). With ``table_entries`` (a slot's; where
    layers differ, the mean over layers of the columns each walks) they
    add ``kv_pages_live`` and ``kv_pages_scanned``, each scan weighed by its
    substeps (live page-table entries; slots x ``table_entries``), and their
    ratio ``kv_live_page_share``: how much of the grid the paged kernel
    walks holds KV a slot attends. A selecting model's scans add
    ``kv_rows_live``, ``kv_rows_selected`` (each weighed by its substeps)
    and ``kv_selected_row_share``: of the cached rows a query could attend,
    the share its indexer keeps. With ``full_table_entries`` (a model with
    state by layer kind: a slot's table width) they add
    ``kv_full_pages_live`` and ``kv_full_live_page_share``, the same two of
    the FULL layers' pages alone. A latent model's scans add
    ``kv_latent_rows`` (each weighed by its substeps): the pool rows its
    decode scans read. A model with conv layers adds ``state_resets``,
    ``state_carries`` and ``state_carried_chunk_share``: of the chunks run,
    those that began from a carried state. A hybrid model's scans add
    ``ssm_state_bytes``, the state-space state they had to read and write.
    Two records or more add where the engine thread's time went
    (:func:`thread_tiling`: ``thread_ms``, the three ``thread_*_share``,
    ``fetch_found_ready_share``, ``longest_records``). Scans add
    :func:`ahead_counters`: ``scans_issued_ahead_share`` and what such scans
    wasted. ``overlapped_dispatch_share`` counts a scan issued ahead too."""
    scans = [t for t in turns if t.kind == "turn"]
    out: Dict[str, Any] = {"dispatches": len(turns), "scans": len(scans),
                           "dropped": dropped, **ahead_counters(scans)}
    live = sum(t.kv_pages_live * t.substeps for t in scans)
    if live and table_entries:
        out["kv_pages_live"] = live
        out["kv_pages_scanned"] = num_slots * table_entries * sum(
            t.substeps for t in scans)
        out["kv_live_page_share"] = live / out["kv_pages_scanned"]
    full_live = sum(t.kv_full_pages_live * t.substeps for t in scans)
    if full_live and full_table_entries:
        out["kv_full_pages_live"] = full_live
        out["kv_full_live_page_share"] = full_live / (
            num_slots * full_table_entries * sum(
                t.substeps for t in scans))
    latent_rows = sum(t.kv_latent_rows * t.substeps for t in scans)
    if latent_rows:
        out["kv_latent_rows"] = latent_rows
    resets = sum(t.state_resets for t in turns)
    carries = sum(t.state_carries for t in turns)
    if resets or carries:
        out.update(state_resets=resets, state_carries=carries,
                   state_carried_chunk_share=carries / (resets + carries))
    ssm_bytes = sum(t.ssm_state_bytes for t in scans)
    if ssm_bytes:
        out["ssm_state_bytes"] = ssm_bytes
    rows_live = sum(t.kv_rows_live * t.substeps for t in scans)
    if rows_live:
        out["kv_rows_live"] = rows_live
        out["kv_rows_selected"] = sum(
            t.kv_rows_selected * t.substeps for t in scans)
        out["kv_selected_row_share"] = out["kv_rows_selected"] / rows_live
    hit = sum(t.moe_experts_hit for t in turns)
    if hit:
        out["moe_rows_per_expert"] = sum(t.moe_rows for t in turns) / hit
        out["moe_imbalance"] = (max(t.moe_max_rows for t in turns)
                                / out["moe_rows_per_expert"])
    pairs = sum(t.moe_pairs for t in turns)
    if pairs:
        out["moe_held_rows_share"] = sum(t.moe_rows for t in turns) / pairs
    if len(turns) < 2:
        return out
    substeps = sum(t.substeps for t in scans)
    if substeps:
        out["substeps_per_dispatch"] = substeps / len(scans)
        out["mean_occupancy"] = sum(
            t.active * t.substeps for t in scans
        ) / (num_slots * substeps)
    gaps = [
        (cur.t_dispatch - prev.t_fetched, prev, cur)
        for prev, cur in zip(turns, turns[1:])
        if prev.t_fetched and not cur.after_idle and not cur.queued_behind
    ]
    out["overlapped_dispatch_share"] = sum(
        1 for t in turns if t.queued_behind) / len(turns)
    if span_ms is None:
        span_ms = turns[-1].t_done - turns[0].t_dispatch
    if span_ms > 0:
        out["host_gap_share"] = sum(g for g, _, _ in gaps) / span_ms
    if gaps:
        g = [x for x, _, _ in gaps]
        harvest = [prev.t_done - prev.t_fetched for _, prev, _ in gaps]
        feed = [cur.t_dispatch - prev.t_done for _, prev, cur in gaps]
        out["host_gap_ms"] = {
            "n": len(g), "p50": float(np.percentile(g, 50)),
            "p99": float(np.percentile(g, 99)), "max": max(g),
            "sum": sum(g), "harvest_sum": sum(harvest),
            "feed_sum": sum(feed),
            "harvest_p50": float(np.percentile(harvest, 50)),
            "feed_p50": float(np.percentile(feed, 50)),
        }
    out["longest_gaps"] = [
        {"gap_ms": round(g, 3),
         "harvest_ms": round(prev.t_done - prev.t_fetched, 3),
         "feed_ms": round(cur.t_dispatch - prev.t_done, 3),
         "after": prev.kind, "before": cur.kind,
         "at_ms": round(cur.t_dispatch, 3),
         "trains": cur.trains, "queue_len": prev.queue_len,
         "pages_allocated": prev.pages_allocated,
         "positions_cached": prev.positions_cached}
        for g, prev, cur in heapq.nlargest(
            longest, gaps, key=lambda g: g[0])
    ]
    out.update(thread_tiling(turns, longest))
    return out


def ahead_counters(scans: Sequence[Turn]) -> Dict[str, Any]:
    """:func:`summarize_turns`' keys for scans issued AHEAD of an unfetched
    one (``Turn.ahead``): ``scans_issued_ahead_share`` of the scans;
    ``ahead_wasted_substeps``, the slot-substeps such scans ran for tenants
    that had already ended (``Turn.wasted_substeps``); and
    ``ahead_wasted_substep_share``, those over all slot-substeps run
    (``active`` x ``substeps``). Nothing without a scan."""
    if not scans:
        return {}
    wasted = sum(t.wasted_substeps for t in scans)
    ran = sum(t.active * t.substeps for t in scans)
    return {
        "scans_issued_ahead_share": sum(
            1 for t in scans if t.ahead) / len(scans),
        "ahead_wasted_substeps": wasted,
        "ahead_wasted_substep_share": wasted / ran if ran else 0.0,
    }


# Speculation observability (ISSUE 13 satellite): accepted + rejected ==
# drafted is a per-round conservation invariant pinned in tier-1
# (tests/test_spec_paged.py). The ``paged`` tag is always "true": kept so
# the series operators' dashboards already select on do not change name.
SPEC_ROUNDS = m.Counter(
    "rdb_decode_spec_rounds_total", "Speculative verify rounds",
    tag_keys=("model", "paged"),
)
SPEC_ACCEPTED = m.Counter(
    "rdb_decode_spec_accepted_total", "Draft tokens accepted by verify",
    tag_keys=("model", "paged"),
)
SPEC_DRAFTED = m.Counter(
    "rdb_decode_spec_drafted_total", "Draft tokens proposed to verify",
    tag_keys=("model", "paged"),
)
SPEC_REJECTED = m.Counter(
    "rdb_decode_spec_rejected_total", "Draft tokens rejected by verify",
    tag_keys=("model", "paged"),
)
SPEC_ACCEPTANCE = m.Gauge(
    "rdb_decode_spec_acceptance",
    "Rolling draft-token acceptance rate (accepted/drafted, bounded "
    "window)", tag_keys=("model", "paged"),
)
PREFIX_HITS = m.Counter(
    "rdb_decode_prefix_hits_total", "Prompt-prefix KV cache hits",
    # granularity is always "page" (longest shared page-prefix); the tag
    # stays so the series keeps its name.
    tag_keys=("model", "granularity"),
)
PREFIX_MISSES = m.Counter(
    "rdb_decode_prefix_misses_total", "Prompt-prefix KV cache misses",
    tag_keys=("model", "granularity"),
)
KV_PAGES_FREE = m.Gauge(
    "rdb_decode_kv_pages_free", "Free pages in the paged KV pool",
    tag_keys=("model",),
)
KV_POOL_BYTES = m.Gauge(
    "rdb_decode_kv_pool_bytes",
    "Resident bytes of the KV state of one kind of layer (a model with "
    "state by layer kind): 'full' the paged pool, 'ring' the sliding "
    "layers' rings, 'state' the states a slot (conv, state-space); set "
    "once, at build",
    tag_keys=("model", "kind"),
)
KV_PAGE_OCCUPANCY = m.Gauge(
    "rdb_decode_kv_page_occupancy",
    "Allocated fraction of the paged KV pool", tag_keys=("model",),
)
PAGE_EVICTIONS = m.Counter(
    "rdb_decode_page_evictions_total",
    "Slots capacity-finished to reclaim pages (over-subscribed pool)",
    tag_keys=("model",),
)
# Token-budget prefill scheduler (ISSUE 15): chunk programs dispatched,
# trains parked on page starvation, and the live pending-train depth.
PREFILL_CHUNKS = m.Counter(
    "rdb_decode_prefill_chunks_total",
    "Chunk programs dispatched by the token-budget prefill scheduler",
    tag_keys=("model",),
)
PREFILL_STARVED = m.Counter(
    "rdb_decode_prefill_starved_total",
    "Chunk dispatches deferred by page starvation (train parked)",
    tag_keys=("model",),
)
PREFILL_PENDING = m.Gauge(
    "rdb_decode_prefill_pending_trains",
    "Chunk trains awaiting prefill budget", tag_keys=("model",),
)


def run_chunked(chunk_fn, params, prompt, C, row, between=None):
    """Host loop driving a compiled chunk program over a prompt on a row
    cache: full-width chunks, right-padded tail, optional ``between``
    callback after every non-final chunk (the decode-interleave hook).
    Returns (last_logits, row)."""
    L = int(prompt.size)
    n_chunks = (L + C - 1) // C
    last = None
    for ci in range(n_chunks):
        piece = prompt[ci * C : (ci + 1) * C]
        tokens = np.zeros((1, C), dtype=np.int32)
        mask = np.zeros((1, C), dtype=np.int32)
        tokens[0, : piece.size] = piece
        mask[0, : piece.size] = 1
        last, row = chunk_fn(
            params,
            jnp.asarray(tokens),
            jnp.asarray(mask),
            row,
            jnp.int32(ci * C),
            jnp.int32(piece.size - 1),
        )
        if ci < n_chunks - 1 and between is not None:
            between()
    return last, row


SESSION_HITS = m.Counter(
    "rdb_decode_session_hits_total", "Session KV continuations",
    tag_keys=("model",),
)
SESSION_MISSES = m.Counter(
    "rdb_decode_session_misses_total",
    "Session requests without reusable KV", tag_keys=("model",),
)


def require_paged(paged: bool) -> None:
    """The one check of the legacy ``paged`` key (ROADMAP D15): the
    benchmark's configurations still pass ``"paged": true`` to
    ``LLMDeployment`` and ``DecodeEngine``, so the key stays in both
    signatures with one legal value."""
    if not paged:
        raise ValueError(
            "paged=False: the slab KV cache was removed — the paged pool "
            "with chunked admission is the engine, not an option of it; "
            "drop the key (or pass paged=True)"
        )


class DecodeEngine:
    """Continuous-batching executor for one CausalLM on one chip/mesh slice.

    ``model`` must provide the decode interface of
    :class:`~ray_dynamic_batching_tpu.models.causal_lm.CausalLM`:
    ``make_cache``, ``prefill``, ``decode_step``, and ``cfg``.
    """

    @_tracer().startup(STARTUP_BUILD)
    def __init__(
        self,
        model: Any,
        params: Any,
        queue: RequestQueue,
        num_slots: int = 8,
        max_len: int = 256,
        prompt_buckets: Optional[Sequence[int]] = None,
        eos_token_id: Optional[int] = None,
        default_max_new_tokens: int = 64,
        idle_wait_s: float = 0.005,
        sample_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
        decode_horizon: int = 8,
        ttft_horizon: Optional[int] = None,
        max_admissions_per_step: int = 2,
        prefix_cache_size: int = 0,
        session_cache_size: int = 0,
        draft_model: Optional[Any] = None,
        draft_params: Optional[Any] = None,
        spec_tokens: int = 4,
        quantize_weights: bool = False,
        device: Optional[jax.Device] = None,
        mesh: Optional[Any] = None,
        base_seed: int = 0,
        paged: bool = True,
        page_size: int = 128,
        kv_pool_pages: Optional[int] = None,
        host_spill_pages: int = 0,
        prefill_token_budget: Optional[int] = None,
    ):
        from ray_dynamic_batching_tpu.utils import compile_cache

        require_paged(paged)
        compile_cache.enable()  # prefill/decode compiles become disk hits
        self.model = model
        self.device = device
        self.mesh = mesh
        # What this model's kind of KV state cannot serve is refused here,
        # when the engine is built, from the state's own table (ROADMAP
        # D10; models/kv_state.py::CANNOT).
        cfg = model.cfg
        refuse_unsupported(
            cfg, model.name,
            prefix_cache_size=prefix_cache_size,
            session_cache_size=session_cache_size,
            host_spill_pages=host_spill_pages,
            draft_model=draft_model is not None, mesh=mesh is not None,
            kv_dtype=model.kv_dtype)
        if draft_model is not None and mesh is not None:
            # Loud, like the draft-model conflict ISSUE 13 lifted (and
            # the PR 10 TP-paged pattern): the spec verify window would
            # need the scratch-page scatter AND the staircase kernel
            # runnable per-shard under shard_map — neither is wired yet,
            # and a silent plain fallback would mislabel every A/B
            # capture stamped from the config. Checked BEFORE any
            # sharding work so a misconfigured replica fails in
            # microseconds, not after a multi-GB param reshard.
            raise ValueError(
                "speculative decoding over a TP-mesh paged pool is not "
                "supported yet: run paged+spec on single-chip replicas, "
                "or drop the draft model for mesh slices"
            )
        # Weight-only int8: decode streams the whole weight set per step,
        # so weight BYTES set tokens/s; kernels live in HBM as int8 and
        # dequantize inside each program (convert+scale fused into the
        # consuming matmul by XLA).
        self.quantized = bool(quantize_weights)
        if self.quantized:
            if mesh is not None:
                raise ValueError(
                    "quantize_weights with a TP mesh is not supported yet: "
                    "sharding rules key on kernel paths, which quantization "
                    "rewrites into QTensor q/scale leaves"
                )
            from ray_dynamic_batching_tpu.models.quant import quantize_tree

            # Idempotent: a pre-quantized tree (the deployment quantizes
            # ONCE and hands the same tree to every length-bucket engine)
            # passes through shared, no fresh int8 copy per engine.
            params = quantize_tree(params)
        if mesh is not None:
            # TP-sharded replica (BASELINE.json config 4): params sharded by
            # the model's Megatron-style rules, KV cache sharded over kv
            # heads (paged_cache_pspec), decode collectives ride ICI via
            # GSPMD — the serving analogue of the reference's NCCL allreduce swap.
            from ray_dynamic_batching_tpu.parallel.mesh import shard_params

            params = shard_params(mesh, model, params)
        elif device is not None:
            # Chip pinning (placement-group bundle): params live on the
            # reserved chip; every dispatch runs under default_device so the
            # cache and all uploads land there too.
            params = jax.device_put(params, device)
        self.params = params
        self.queue = queue
        self.num_slots = num_slots
        self.max_len = max_len
        self.prompt_buckets = sorted(prompt_buckets or DEFAULT_PROMPT_BUCKETS)
        self.prompt_buckets = [b for b in self.prompt_buckets if b <= max_len]
        self.eos_token_id = eos_token_id
        self.default_max_new_tokens = default_max_new_tokens
        self.idle_wait_s = idle_wait_s
        # Legacy whole-batch override; when None the parametric per-request
        # sampler (temperature / top-k / seed) runs in-program.
        self._sample_custom = sample_fn
        self.base_seed = int(base_seed)

        self._slots = [_Slot() for _ in range(num_slots)]
        # Host mirror of per-slot cache lengths (updated from each scan's
        # packed result): drives the page-headroom math and the
        # kv_occupancy() residency metric.
        self._len_host = np.zeros((num_slots,), dtype=np.int32)
        # --- paged KV pool (ISSUE 7 tentpole) ---------------------------
        # All slots are backed by one pool of lane-aligned pages gathered
        # through per-slot page tables, so HBM occupancy follows cached
        # tokens (freed at EOS mid-cycle) and prefix/session reuse
        # shares pages copy-on-write instead of copying rows.
        self.page_size = int(page_size)
        if not lane_aligned_page(self.page_size):
            raise ValueError(
                f"page_size {self.page_size} must be a 128-lane "
                "multiple (ops/tile_math.lane_aligned_page): the int8 "
                "scale tile streams the page as its lane dim"
            )
        # Logical per-slot capacity: whole pages covering max_len.
        # The engine still enforces max_len; the partial last page is
        # headroom that is never attended past max_len.
        self._n_table_entries = pages_for(max_len, self.page_size)
        self._paged_capacity = self._n_table_entries * self.page_size
        full_backing = num_slots * self._n_table_entries
        self.num_pages = int(kv_pool_pages or full_backing)
        # The pool may be over-subscribed (num_pages < full backing:
        # the occupancy win) but must hold at least one slot's worth
        # or nothing can ever decode.
        if self.num_pages < self._n_table_entries:
            raise ValueError(
                f"kv_pool_pages {self.num_pages} cannot back even one "
                f"slot ({self._n_table_entries} pages at page_size "
                f"{self.page_size}, max_len {max_len})"
            )
        # Allocator event journal (bounded ring): alloc/free land
        # from the allocator itself, CoW borrows / cache reclaims /
        # capacity evictions from their decision sites below —
        # rendered as Perfetto instant events + a page-occupancy
        # counter track by utils/trace_export, surfaced by
        # ``snapshot()``.
        self._page_journal = PageEventJournal()
        self._allocator = PageAllocator(self.num_pages,
                                        journal=self._page_journal)
        self._table_host = np.full(
            (num_slots, self._n_table_entries), self.num_pages,
            dtype=np.int32,
        )
        self._table_dirty = True
        if mesh is not None:
            # TP serving slice over the paged pool (ROADMAP item 2):
            # pages shard on the kv-head dim (codes + scales planes
            # included); the page table, lengths, and the host-side
            # free-list allocator stay replica-global — page indices
            # are shard-invariant. The decode kernel runs per-shard head
            # slices under the mesh (ops/attention.tensor_parallel
            # -> paged_decode_attention's shard_map wrapper); the
            # CPU/XLA gather fallback partitions from the pool's
            # NamedSharding under plain GSPMD, so both read paths
            # stay token-exact vs the single-chip pool.
            from ray_dynamic_batching_tpu.parallel.mesh import (
                make_sharded_paged_cache,
            )

            self._cache = make_sharded_paged_cache(
                mesh, model, num_slots, self.num_pages,
                self.page_size, self._paged_capacity,
            )
        else:
            with self._device_ctx():
                self._cache = self._put(model.make_paged_cache(
                    num_slots, self.num_pages, self.page_size,
                    self._paged_capacity,
                    widest_chunk=max(self.prompt_buckets, default=None),
                ))
        # Pages in a slot's ring (0: one pool for every layer), sized for
        # the widest chunk a program writes at once.
        self._ring_pages = self._cache.ring_pages
        # Each layer's sliding window (0: a full layer), and the table
        # columns a slot's decode scan walks, averaged over layers.
        self._layer_windows: Tuple[int, ...] = model.layer_windows
        self._layer_table_widths = [
            tile_math.window_table_width(
                w, 1, self.page_size, self._n_table_entries)
            for w in self._layer_windows]
        self._table_walked = (sum(self._layer_table_widths)
                              / max(1, len(self._layer_table_widths)))
        # Positions a selecting layer's indexer keeps a query (0: no layer
        # selects), and how many layers select.
        self._index_topk = int(getattr(cfg, "index_topk", 0) or 0)
        self._select_layers = (
            sum(1 for i in range(cfg.num_layers) if cfg.layer_kind(i).select)
            if self._index_topk else 0)
        # Latent layers (0: k/v pairs), and how the state lies on the
        # device, once, for snapshot().
        self._latent_layers = self._cache.latent_layers
        # A state a slot beside the pages (a model with conv layers): the
        # chunk program is told each row's slot, and the ring counts the
        # chunks that reset a state and those that carried one.
        self._slot_state = self._cache.conv_state is not None
        # ... of which a hybrid model's state-space matrices are read and
        # written by every decode substep of every active slot: the bytes
        # that is a slot (0: no such plane).
        ssm = self._cache.ssm_state
        self._ssm_step_bytes = 0 if ssm is None else (
            2 * ssm.dtype.itemsize * math.prod(ssm.shape) // ssm.shape[1])
        self._pool_stats = self._cache.describe(cfg)
        for kind, n in self._pool_stats.get("bytes_by_kind", {}).items():
            KV_POOL_BYTES.set(n, tags={"model": model.name, "kind": kind})
        self._tokens = np.zeros((num_slots, 1), dtype=np.int32)
        self._active_mask = np.zeros((num_slots,), dtype=bool)
        # Per-slot sampling params (temperature 0 == greedy).
        self._temps = np.zeros((num_slots,), dtype=np.float32)
        self._topk = np.zeros((num_slots,), dtype=np.int32)
        self._topp = np.ones((num_slots,), dtype=np.float32)
        self._seeds = np.zeros((num_slots,), dtype=np.int32)
        # Per-slot presence/frequency penalties over GENERATED tokens
        # (repetition control; the prompt is not counted — documented
        # variant of the OpenAI semantics). Counts live ON DEVICE so the
        # horizon scan updates them in-carry without host syncs.
        self._pres = np.zeros((num_slots,), dtype=np.float32)
        self._freq = np.zeros((num_slots,), dtype=np.float32)
        V = getattr(getattr(model, "cfg", None), "vocab_size", 0)
        # Under a TP mesh the counts shard over the vocab axis, like the
        # logits they penalize. The layout is DECLARED here and held by
        # _pin_counts in the two programs that return counts: left to
        # GSPMD, the fresh array is replicated and every program hands
        # back a vocab-sharded one, so the first live decode dispatch
        # compiled again (a steady-state violation on the 8-device CPU
        # cluster with llama_tiny at tp=2).
        self._counts_sharding = None
        counts_shape = (num_slots, max(V, 1))
        if mesh is not None:
            from jax.sharding import PartitionSpec

            from ray_dynamic_batching_tpu.parallel.mesh import (
                feasible_sharding,
            )

            self._counts_sharding = feasible_sharding(
                mesh, PartitionSpec(None, "tp"), counts_shape
            )
            self._counts = jax.device_put(
                jnp.zeros(counts_shape, jnp.int32), self._counts_sharding
            )
        else:
            with self._device_ctx():
                self._counts = self._put(jnp.zeros(counts_shape, jnp.int32))
        # Per-slot sparse logit bias (OpenAI-style logit_bias; banned
        # tokens ride as -inf bias): fixed K entries keep shapes static,
        # padding rows are (id 0, value 0) — an add of 0, not a mask.
        self.max_bias_entries = 16
        self._bias_ids = np.zeros((num_slots, self.max_bias_entries),
                                  dtype=np.int32)
        self._bias_vals = np.zeros((num_slots, self.max_bias_entries),
                                   dtype=np.float32)

        self.decode_horizon = max(1, int(decode_horizon))
        # Bound on admission latency while slots are free: an arrival during
        # a compiled scan cannot be admitted until the scan returns, so the
        # idle-queue horizon caps TTFT at ttft_horizon * per-step latency
        # instead of decode_horizon * per-step latency (~4x shorter by
        # default). Full horizon still runs when the batch is full, where
        # admission is impossible anyway and throughput is the constraint.
        if ttft_horizon is None:
            ttft_horizon = max(1, self.decode_horizon // 4)
        self.ttft_horizon = min(max(1, int(ttft_horizon)),
                                self.decode_horizon)
        self.max_admissions_per_step = max(1, int(max_admissions_per_step))
        # --- token-budget chunked admission (ISSUE 15 tentpole) ---------
        # Every admission is a chunk train (pages-direct chunk k/v,
        # first-token fusion). ``prefill_token_budget`` is the
        # most prefill tokens one scheduler round may spend between
        # decode turns; clamped to >= one chunk width so a full-width
        # chunk can always dispatch (otherwise nothing would ever
        # admit). With the default budget of exactly one chunk, no
        # running stream ever waits more than ONE chunk program between
        # its turns — the stall bound tier-1 pins.
        _chunk_w = self.prompt_buckets[-1] if self.prompt_buckets \
            else max_len
        self.prefill_token_budget = max(
            int(prefill_token_budget or _chunk_w), _chunk_w
        )
        self._trains: List[_ChunkTrain] = []   # FIFO (arrival order)
        self._train_slots: set = set()
        # The always-on turn ring: one ``Turn`` per device dispatch,
        # written on the engine thread. The stall-bound pin, the flight
        # recorder's ``decode.turn`` span, the scan wait of
        # ``_ttft_parts``, ``snapshot()["turns"]`` and the benchmark's
        # engine metrics all read it; ``reset_ttft_window`` clears it.
        self.turns: collections.deque = collections.deque(
            maxlen=_TURN_RING
        )
        self.turns_dropped = 0
        self._last_scan: Optional[Turn] = None  # newest "turn" record
        self._idled = False
        # Since the previous record: the wall time inside idle waits; and
        # the CPU clock of the thread that wrote it (``Turn.cpu_ms``).
        self._idle_ms = 0.0
        self._cpu_mark = 0.0
        self._cpu_thread: Optional[int] = None
        # Programs issued, and the newest of them a fetch has proven done:
        # their difference at a dispatch is its ``Turn.queued_behind``.
        self._programs_issued = 0
        self._programs_seen = 0
        # What ``_iterate`` has issued and not completed: the scan (ONE may
        # stay in flight between iterations), the chunk groups behind it.
        self._issued_turn: Optional[_IssuedTurn] = None
        self._issued_groups: List[_IssuedGroup] = []
        # The newest scan's last tokens, on the device (``_decode_impl``).
        self._carry_sharding = None if mesh is None else self._put(
            np.zeros((), np.int32)).sharding
        with self._device_ctx():
            self._carry = self._put(np.zeros((num_slots,), np.int32))
        # The share of recent scan fetches that found their result ready
        # (``_note_ready``): above ``AHEAD_READY_MAX`` the host is the pace.
        self._ready_share = 0.0
        # Prefill tokens spent behind the scan in flight: the one budget
        # of a turn, so the pump before the NEXT scan spends only the rest.
        self._prefill_spent = 0
        # Every phase span names its engine, by the process's count of
        # engines and the chip it is pinned to: all Python threads of a
        # process share one line name in the profiler's trace, and a
        # reader nests only one thread's spans.
        # (No "#", "," or "=": a TraceAnnotation's own separators.)
        self._phase_tag = f"{self.model.name}:{next(_ENGINE_ORDINAL)}" + (
            "" if device is None else f"@{device.id}"
        )
        # TTFT decomposition: (queue_wait, scan_wait, prefill) per admission
        # over a rolling window — queue_wait is arrival->dequeue (slot
        # starvation + waiting out in-flight scans), scan_wait the portion
        # of that spent inside the scan that was running at arrival, and
        # prefill is dequeue->first token. Consumed by ttft_breakdown();
        # the bench LLM row publishes it so an on-chip run shows where the
        # TTFT milliseconds live (BASELINE.json north star: p50 < 150 ms).
        self._ttft_parts: collections.deque = collections.deque(maxlen=1024)
        # Prompt-prefix KV reuse (0 = off) by page REFERENCE: longest
        # shared page-prefix, copy-on-write at the partial boundary page.
        self.paged_prefix: Optional[PagedPrefixCache] = None
        if prefix_cache_size > 0 and self.prompt_buckets:
            self.paged_prefix = PagedPrefixCache(
                prefix_cache_size, self.page_size, self._allocator
            )
        # HBM -> host-RAM spill tier (0 = off): prefix-cache entries shed
        # under pool pressure spill their page CONTENTS to host RAM and
        # reload on the next matching prompt — hot system prompts survive
        # pool churn instead of recomputing (ISSUE 11; pointless without
        # a prefix cache to spill from).
        self.host_spill: Optional[HostSpillTier] = None
        if host_spill_pages > 0 and self.paged_prefix is not None:
            self.host_spill = HostSpillTier(
                host_spill_pages, self._read_pages, self._write_pages,
                journal=self._page_journal,
            )
        # Multi-turn session KV continuation (0 = off): the store pins
        # the finished slot's pages (O(1), no row copy).
        self.paged_sessions: Optional[PagedSessionCache] = None
        if session_cache_size > 0:
            self.paged_sessions = PagedSessionCache(
                session_cache_size, self.page_size, self._allocator
            )
        # The draft model's long-fill programs (chunk, commit) by chunk
        # width: compiled at the first admission (_draft_long_fill).
        self._draft_fill_fns: Dict[int, Tuple[Callable, Callable]] = {}
        # An expert model's decode and paged chunk programs also return
        # their routing counters (``Turn``'s ``moe_*``); a dense model's
        # programs are built without the argument and do not change.
        self._moe_kw: Dict[str, bool] = (
            {"moe_counters": True}
            if getattr(model, "has_experts", False) else {})
        # Donations: cache (arg 1) and counts (arg 8 — params=0,
        # cache=1, step_state=2, horizon=3, samp_f=4, samp_i=5,
        # bias_ids=6, bias_vals=7, counts=8).
        self._decode_fn = instrument("decode_step", jax.jit(
            self._decode_impl, donate_argnums=(1, 8), static_argnums=(3,)
        ))
        # Pages-direct chunk program (chunked paged admission): one jit,
        # retraced per (group, width) shape; the pool cache (arg 2) is
        # donated across chunks.
        self._chunk_paged_fn = instrument("chunk_prefill", jax.jit(
            self._chunk_group_paged_impl, donate_argnums=(2,)
        ))
        # Speculative decoding (greedy rows only): a small draft proposes
        # spec_tokens continuations per slot, the target verifies the whole
        # window in ONE forward, and the accepted prefix + the target's
        # correction land at once — n tokens per target dispatch instead of
        # one, with EXACT greedy equivalence (rejected tails are garbage
        # past ``lengths``, the same invariant every other path relies on).
        self.draft_model = draft_model
        self.spec_tokens = max(1, int(spec_tokens))
        self._dcache = None
        # Rolling (accepted, drafted) pairs per spec round: feeds the
        # rdb_decode_spec_acceptance gauge, spec_acceptance(), the bench
        # row's acceptance stamp, and the sim's profiled-acceptance
        # input. Bounded so a long-lived engine tracks the incident, not
        # the healthy morning.
        self._spec_acc_window: collections.deque = collections.deque(
            maxlen=512
        )
        # Per-round scratch bookkeeping (paged spec): slot -> (first
        # table index, scratch page ids). ALWAYS resolved (spliced or
        # freed) before the round's harvest, so no scratch page can
        # outlive its round or leak through a finish.
        self._spec_scratch: Dict[int, Tuple[int, List[int]]] = {}
        if draft_model is not None:
            if draft_params is None:
                raise ValueError("draft_model requires draft_params")
            if mesh is not None:
                from ray_dynamic_batching_tpu.parallel.mesh import (
                    shard_params as _shard,
                )

                draft_params = _shard(mesh, draft_model, draft_params)
            elif device is not None:
                draft_params = jax.device_put(draft_params, device)
            self.draft_params = draft_params
            with self._device_ctx():
                # Headroom past max_len: the draft drafts spec_tokens+1
                # ahead of the verified length near the end of the cache.
                # The draft cache is a slab of rows, not pages: the
                # shared pool's pages are target-geometry tensors (K, H
                # of the big model), so the small draft would need a
                # second pool of its own shape for a footprint that is a
                # rounding error next to the target's — the TARGET-side
                # KV of drafted tokens is what pages (scratch pages,
                # spliced on accept).
                self._dcache = self._put(draft_model.make_cache(
                    num_slots, max_len + self.spec_tokens + 1
                ))
            self._spec_fn = instrument("spec_verify", jax.jit(
                self._spec_impl, donate_argnums=(1, 2)
            ))
            self._draft_catchup_fn = instrument("draft_catchup", jax.jit(
                self._draft_catchup_impl, donate_argnums=(1,)
            ))
        def _reset_counts(counts, slot, first_tok):
            # Fresh tenant: zero the reused row, then count the PREFILL-
            # sampled first token (the scan only counts tokens it samples
            # itself — without this, the first token repeats once free).
            counts = jax.lax.dynamic_update_slice(
                counts,
                jnp.zeros((1, counts.shape[1]), jnp.int32),
                (slot, 0),
            )
            return self._pin_counts(counts.at[slot, first_tok].set(1))

        self._zero_counts_fn = instrument(
            "zero_counts", jax.jit(_reset_counts, donate_argnums=(0,))
        )
        # Device copies of the per-slot sampling arrays: they change only
        # at admission/finish, but _step dispatches every few ms — without
        # the cache every dispatch re-uploads seven small host arrays
        # (temps/topk/topp/seeds/bias/pres/freq): seven host->device
        # transfers of per-step overhead for values that did not change.
        self._sampling_dev = None
        # Installed by a colocation executor: called between chunk
        # dispatches of long admissions so co-tenants aren't stalled.
        self.interleave_hook: Optional[Callable[[], None]] = None
        # Requests mid-admission (dequeued, not yet slotted) — see _admit.
        self._admitting = 0
        self._admitting_batch: List[Request] = []
        # --- page-fabric mailboxes (live migration + prefix push) ---
        # Slots are engine-thread-owned; the controller/courier request
        # work through these thread-safe mailboxes and the loop services
        # them between decode turns (_service_fabric). The lock reuses
        # the "allocator" rank (100) — its reserved purpose — and must
        # NEVER be held across queue (80) or request-fulfil (90) calls:
        # _service_fabric pops under the lock into locals, releases,
        # then processes.
        self._fabric_lock = OrderedLock("allocator")
        self._migrate_out_q: List[Tuple[str, Callable[[PageParcel], bool]]] = []
        self._push_out_q: List[Tuple[bytes, Callable[[PageParcel], bool]]] = []
        self._parcel_in_q: List[PageParcel] = []
        self.migrated_out = 0
        self.migrated_in = 0
        self.pushes_out = 0
        self.pushes_in = 0
        self._thread: Optional[threading.Thread] = None
        self._run = threading.Event()
        self.steps = 0
        self.completed = 0
        # Progress heartbeat for replica health checks: refreshed only by
        # SUCCESSFUL loop iterations, so a perpetually-failing _step (device
        # OOM, corrupt params) reads as a stall even though the thread lives.
        self.last_heartbeat = time.monotonic()
        _tracer().open_startup().attributes.update(
            replica=self._phase_tag, slots=num_slots, pages=self.num_pages,
            pool_bytes=self._pool_stats["resident_bytes"])

    def _phase(self, name: str, **attrs: Any):
        """One engine-loop phase on the profiler's clock
        (:meth:`~utils.tracing.Tracer.phase`), named for this engine."""
        return _tracer().phase(name, replica=self._phase_tag, **attrs)

    def _log_dispatch(self, kind: str, t_dispatch: float, t_issued: float,
                      t_fetched: float, substeps: int, tokens: int,
                      active: int, trains: int,
                      moe: Sequence[int] = (0, 0, 0, 0),
                      kv_pages_live: float = 0,
                      kv_rows: Tuple[int, int] = (0, 0),
                      queued_behind: int = 0,
                      kv_full_pages_live: int = 0,
                      kv_latent_rows: int = 0,
                      state_turns: Tuple[int, int] = (0, 0),
                      fetch: Tuple[float, bool] = (0.0, False),
                      seq: int = 0, ahead: Tuple[bool, int] = (False, 0)
                      ) -> Turn:
        """Append this dispatch's record to the turn ring (its work on the
        host is done: ``t_done`` is now). ``moe``: the dispatch's routing
        counters as fetched (``Turn``'s ``moe_*`` fields);
        ``kv_pages_live``: :meth:`_kv_pages_live` as the scan was
        dispatched; ``kv_rows``: :meth:`_kv_rows` then; ``queued_behind``:
        and ``seq``: :meth:`_note_issue` then; ``fetch``: ``Turn``'s
        ``t_fetch`` and ``ready_at_fetch``; ``ahead``: ``Turn``'s ``ahead``
        and ``wasted_substeps``. The thread's CPU clock is read here (once a
        record), and a record that stalled is logged."""
        cpu, me = time.thread_time(), threading.get_ident()
        same_thread = me == self._cpu_thread
        cpu_ms = (cpu - self._cpu_mark) * 1000.0 if same_thread else 0.0
        self._cpu_mark, self._cpu_thread = cpu, me
        rec = Turn(
            kind, t_dispatch, t_issued, t_fetched, now_ms(),
            substeps, tokens, active, trains, len(self.queue),
            self._allocator.allocated_pages,
            int(self._len_host.sum()), self._idled,
            *(int(c) for c in moe[:3]), kv_pages_live, int(moe[3]),
            *kv_rows, queued_behind, kv_full_pages_live, kv_latent_rows,
            *state_turns,
            active * substeps * self._ssm_step_bytes if kind == "turn" else 0,
            *fetch, self._idle_ms, cpu_ms, seq, *ahead,
        )
        if same_thread and self.turns:
            parts = thread_parts(self.turns[-1], rec)
            if max(parts.blocked, parts.host) > _STALL_WARN_MS:
                logger.warning(
                    "%s: a %s record took %.0f ms of the engine thread: "
                    "blocked in its fetch %.0f, idle %.0f, the host's side "
                    "%.0f (its CPU clock moved %.0f) "
                    "(substeps=%d queued_behind=%d)",
                    self.model.name, kind, parts.wall, parts.blocked,
                    parts.idle, parts.host, parts.cpu, substeps,
                    queued_behind)
        if len(self.turns) == self.turns.maxlen:
            self.turns_dropped += 1
        self.turns.append(rec)
        if kind == "turn":
            self._last_scan = rec
        self._idled = False
        self._idle_ms = 0.0
        return rec

    def _note_issue(self) -> Tuple[int, int]:
        """Number the program about to be dispatched; returns (its number,
        the programs issued before it that no fetch has proven done)."""
        behind = self._programs_issued - self._programs_seen
        self._programs_issued += 1
        return self._programs_issued, behind

    def _note_fetched(self, seq: int) -> None:
        """Program ``seq``'s result reached the host: the device runs its
        programs in order, so every one issued up to it is done."""
        self._programs_seen = max(self._programs_seen, seq)

    def _kv_pages_live(self, window: int = 1) -> int:
        """Page-table entries, summed over all slots, that the scan about
        to be dispatched walks in its first substep: the kernel's own
        loop bounds (``tile_math.live_pages``: up to the column of the
        last position a ``window``'s last row attends, for a sliding
        layer from the column of its window's oldest position); where
        layers differ the count is the mean over layers."""
        live = {w: int(tile_math.live_pages(
            self._len_host, window, w, self.page_size,
            self._n_table_entries)[1].sum())
            for w in set(self._layer_windows)}
        if len(live) == 1:
            return live[self._layer_windows[0]]
        return sum(live[w] for w in self._layer_windows) / max(
            1, len(self._layer_windows))

    def _kv_full_pages_live(self) -> int:
        """``Turn.kv_full_pages_live`` of the scan about to be dispatched;
        0 where the model has one pool for every layer."""
        if not self._ring_pages:
            return 0
        return int(tile_math.live_pages(
            self._len_host, 1, 0, self.page_size,
            self._n_table_entries)[1].sum())

    def _kv_rows(self, window: int = 1) -> Tuple[int, int]:
        """(rows live, rows selected) of the scan about to be dispatched,
        summed over all slots and selecting layers (``Turn.kv_rows_live``);
        (0, 0) where no layer selects."""
        if not self._index_topk:
            return 0, 0
        rows = np.minimum(self._len_host.astype(np.int64) + window,
                          self._paged_capacity)
        return (int(rows.sum()) * self._select_layers,
                int(np.minimum(rows, self._index_topk).sum())
                * self._select_layers)

    def _device_ctx(self):
        """The scope everything this engine allocates, traces and
        dispatches runs under: ``jax.default_device`` for a pinned chip,
        the :func:`~ops.attention.tensor_parallel` slice for a TP mesh —
        baked into every program traced inside it, so each Pallas kernel
        runs per head shard under ``shard_map`` (GSPMD cannot partition a
        ``pallas_call``) — and nothing for an unpinned engine."""
        import contextlib

        if self.mesh is not None:
            from ray_dynamic_batching_tpu.ops.attention import (
                tensor_parallel,
            )

            return tensor_parallel(self.mesh)
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def _put(self, tree):
        """Place a leaf (or whole tree) of the engine's PERSISTENT device
        state — the KV cache, the draft cache, the token counts — where
        this engine's programs keep it: replicated over the mesh slice,
        committed to the pinned chip, or (unpinned) uncommitted on the
        default device. Every program hands those trees back committed
        to the engine's placement, and jit keys its executables on each
        argument's sharding AND committed-ness — so a leaf swapped in
        from the host (the page-table refresh, the lengths reset) must
        arrive placed the same way or the first live dispatch after
        warmup lowers and compiles the program again. Per-dispatch
        arguments need none of this: warmup and serving both build them
        uncommitted under :meth:`_device_ctx`."""
        if self.mesh is not None:
            from ray_dynamic_batching_tpu.parallel.mesh import replicate

            return replicate(self.mesh, tree)
        if self.device is not None:
            return jax.device_put(tree, self.device)
        return jax.tree_util.tree_map(jnp.asarray, tree)

    def resident_devices(self) -> set:
        """Devices holding this engine's params and KV cache — what a
        placement check compares with the chips the replica reserved."""
        out: set = set()
        for leaf in jax.tree_util.tree_leaves((self.params, self._cache)):
            out |= leaf.devices()
        return out

    def _pin_counts(self, counts):
        """Inside a program: hold the returned token counts to their
        declared mesh layout (no-op off-mesh)."""
        if self._counts_sharding is None:
            return counts
        return jax.lax.with_sharding_constraint(
            counts, self._counts_sharding
        )

    # --- compiled programs -------------------------------------------------
    def _mp(self, params):
        """Model-ready params: dequantize INSIDE the program when the
        resident tree is int8 (no-op otherwise)."""
        if not self.quantized:
            return params
        from ray_dynamic_batching_tpu.models.quant import dequantize_tree

        return dequantize_tree(
            params, getattr(self.model, "dtype", jnp.bfloat16)
        )

    @staticmethod
    def _apply_bias(logits, bias_ids, bias_vals):
        """Sparse per-row logit bias: logits[b, ids[b, j]] += vals[b, j].
        Padding entries are (0, 0.0) — a no-op add. Runs before BOTH
        greedy argmax and sampling so biased greedy stays deterministic
        (the speculative verify path applies the same bias)."""
        B = logits.shape[0]
        rows = jnp.arange(B)[:, None]
        return logits.at[rows, bias_ids].add(
            bias_vals.astype(logits.dtype)
        )

    def _sample_tokens(self, logits, temps, topk, seeds, tok_idx,
                       bias_ids=None, bias_vals=None, topp=None):
        """In-program per-request sampling: temperature 0 → greedy argmax;
        otherwise top-k-masked categorical, keyed by (base_seed, request
        seed, TOKEN INDEX within the request) — so a request's stream is
        reproducible regardless of slot, batch neighbors, or how much
        traffic the engine served before it, and no two positions of one
        request reuse a key. One compiled program covers every sampling
        configuration; a ``lax.cond`` skips the full-vocab sort + draws at
        RUNTIME when the whole batch is greedy (the default hot path).

        logits [B, V]; temps [B] f32; topk [B] i32; seeds [B] i32;
        tok_idx [B] i32 (index of the token being sampled per request).
        """
        logits = logits.astype(jnp.float32)
        if bias_ids is not None:
            # Before BOTH built-in and custom samplers: a ban the caller
            # was told is enforced must bind regardless of sampler.
            logits = self._apply_bias(logits, bias_ids, bias_vals)
        if self._sample_custom is not None:
            return self._sample_custom(logits).astype(jnp.int32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        if topp is None:
            topp = jnp.ones(logits.shape[:1], jnp.float32)

        def draw(args):
            lg, tm, tk, tp, sd, ti = args
            V = lg.shape[-1]
            # top-k mask (k<=0 means no truncation)
            k_eff = jnp.where(tk > 0, jnp.minimum(tk, V), V)
            sorted_desc = -jnp.sort(-lg, axis=-1)
            kth = jnp.take_along_axis(
                sorted_desc, (k_eff - 1)[:, None], axis=-1
            )
            masked = jnp.where(lg < kth, -jnp.inf, lg)
            scaled = masked / jnp.maximum(tm, 1e-6)[:, None]
            # top-p (nucleus): keep the smallest prefix of the sorted
            # distribution whose mass reaches p; the cutoff token itself
            # stays (cum - prob < p). p >= 1 or <= 0 disables. The sorted
            # view derives from the top-k sort above (mask + positive
            # scale are monotone) — no second full-vocab sort.
            p_eff = jnp.where((tp > 0.0) & (tp < 1.0), tp, 1.0)[:, None]
            ranks = jnp.arange(V)[None, :]
            sorted_scaled = jnp.where(
                ranks < k_eff[:, None], sorted_desc, -jnp.inf
            ) / jnp.maximum(tm, 1e-6)[:, None]
            probs = jax.nn.softmax(sorted_scaled, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep_sorted = (cum - probs) < p_eff
            # Smallest KEPT logit value = the nucleus threshold per row.
            kept_min = jnp.min(
                jnp.where(keep_sorted, sorted_scaled, jnp.inf), axis=-1,
                keepdims=True,
            )
            scaled = jnp.where(scaled < kept_min, -jnp.inf, scaled)
            base = jax.random.PRNGKey(self.base_seed)

            def one(seed, idx, row):
                key = jax.random.fold_in(jax.random.fold_in(base, seed), idx)
                return jax.random.categorical(key, row)

            return jax.vmap(one)(sd, ti, scaled).astype(jnp.int32)

        sampled = jax.lax.cond(
            jnp.any(temps > 0.0),
            draw,
            lambda args: greedy,
            (logits, temps, topk, topp, seeds, tok_idx),
        )
        return jnp.where(temps > 0.0, sampled, greedy)

    def _chunk_group_widths(self, W: int) -> Tuple[int, ...]:
        """The layout of a chunk group's one int32 buffer ``[g, cols]``, a
        row = ``tokens[W] | mask[W] | table[NP] | ring table[NP] (0 wide
        unless the model keeps rings) | meta_i[6, or 7 where the model
        keeps a conv state a slot] | meta_f[2] | bias_ids[E] |
        bias_vals[E]``: every width a shape the engine holds."""
        NP, E = self._n_table_entries, self.max_bias_entries
        return (W, W, NP, NP if self._ring_pages else 0,
                6 + self._slot_state, 2, E, E)

    def _cut_chunk_group(self, packed) -> _ChunkFields:
        """The fields of a chunk group's buffer
        (:meth:`_chunk_group_widths`; ``W`` follows from the buffer's
        width). The float32 fields are the values whose bits the buffer
        holds: views of the host's numpy buffer (to fill), a bitcast of
        the program's upload."""
        W = (packed.shape[1] - sum(self._chunk_group_widths(0))) // 2
        cuts, at = [], 0
        for width in self._chunk_group_widths(W):
            cuts.append(packed[:, at:at + width])
            at += width
        tokens, mask, table, ring, meta_i, meta_f, bias_ids, bias_vals = cuts
        if isinstance(packed, np.ndarray):
            meta_f, bias_vals = (
                a.view(np.float32) for a in (meta_f, bias_vals))
        else:
            meta_f, bias_vals = (
                jax.lax.bitcast_convert_type(a, jnp.float32)
                for a in (meta_f, bias_vals))
        return _ChunkFields(tokens, mask, table,
                            ring if self._ring_pages else None,
                            meta_i, meta_f, bias_ids, bias_vals)

    def _new_chunk_group(self, group: int,
                         W: int) -> Tuple[np.ndarray, _ChunkFields]:
        """A chunk group's host buffer and its fields as views to fill,
        every row one that writes nothing: zero tokens under a zero mask,
        an all-sentinel table (every page write drops), the sentinel
        slot's ring and lengths entry, greedy. Warm-up uploads it as it
        stands; a served group overwrites its rows."""
        packed = np.zeros((group, sum(self._chunk_group_widths(W))), np.int32)
        f = self._cut_chunk_group(packed)
        f.table[:] = self.num_pages
        if f.ring is not None:
            f.ring[:] = ring_table(np.full(group, self.num_slots, np.int32),
                                   self._ring_pages, self._n_table_entries)
        f.meta_i[:, 0] = self.num_slots
        if self._slot_state:
            f.meta_i[:, 6] = self.num_slots
        f.meta_f[:, 1] = 1.0
        return packed, f

    def _chunk_group_paged_impl(self, params, packed, cache):
        """One chunk program for a GROUP of chunk trains, pages-direct
        (ISSUE 15 tentpole): each row is one train's next ``<=W``-token
        chunk, scattered straight through its own page-table row
        (``table`` [g, NP] — CoW-borrowed head pages sit below the
        row's ``start`` and are never written; the unallocated tail is
        sentinel-steered and drops, like the spec verify scatter), with
        the staircase read bounded by the row's own start. The cache
        argument is DONATED across chunks — XLA updates the pool in
        place, no row cache, no commit copy.

        The group's per-dispatch state arrives as ONE upload, ``packed``
        int32 [g, cols], cut here by static offsets
        (:meth:`_cut_chunk_group`; the float32 fields travel as their
        bits): a transfer is a host call on the first token's path
        whatever its size, and under several engines' threads a hand-back
        of the interpreter lock.

        First-token fusion: ``_sample_tokens`` runs in-program on every
        row's take-row logits, so a FINAL chunk's admission ends at a
        ``[g]`` ids fetch — never a logits round-trip. Final rows also
        scatter their verified prompt length into ``cache.lengths``;
        non-final rows are steered to the sentinel slot (``mode="drop"``
        voids both)."""
        f = self._cut_chunk_group(packed)
        slots, starts, take_idx, topk, seeds, new_len = (
            f.meta_i[:, j] for j in range(6))
        temps, topp = f.meta_f[:, 0], f.meta_f[:, 1]
        params = self._mp(params)
        rings = {}
        if f.ring is not None:
            # state by layer kind: the rows' slots' ring tables
            rings["ring_tables"] = f.ring
        if self._slot_state:
            # conv layers: the rows' slots, whose states the program zeroes
            # (a row at its prompt's start) or carries on, on the device
            rings["state_slots"] = f.meta_i[:, 6]
        # An expert model's routing counters ride the ids fetch: [g + 4].
        taken, pools, *moe = self.model.prefill_chunk_paged(
            params, f.tokens, f.mask, cache, f.table, starts, take_idx,
            **self._moe_kw, **rings,
        )
        # (the pools come back under the cache's own table: the rows'
        # tables were the layers' to write through, not the result's)
        cache = pools.replace(
            lengths=cache.lengths.at[slots].set(new_len, mode="drop"))
        first = self._sample_tokens(
            taken, temps, topk, seeds, jnp.zeros_like(slots), f.bias_ids,
            f.bias_vals, topp,
        )
        if moe:
            first = jnp.concatenate([first, moe[0]])
        return first, cache

    def _decode_impl(self, params, cache, step_state, horizon: int,
                     samp_f, samp_i, bias_ids, bias_vals, counts, carried):
        """``horizon`` chained decode steps in one program (one host sync).

        The per-DISPATCH state arrives as ONE packed [4, B] int32 upload —
        rows = pending tokens / active mask / next sample index / "use the
        carry" — instead of separate transfers. ``carried`` [B] is the
        previous scan's last tokens, still on the device: a row whose
        fourth entry is set reads its pending token there, so the host can
        dispatch this scan BEFORE it has fetched that one (all zero: the
        uploaded tokens, the order in which every scan is fetched first).
        The scan's own last tokens come back beside ``packed`` as the next
        scan's ``carried``. Per-slot sampling state arrives
        packed by dtype — ``samp_f`` [4, B] stacks
        temperature/top_p/presence/frequency, ``samp_i`` [2, B] stacks
        top_k/seeds — so a sampling-state refresh costs two transfers
        instead of eight (each transfer is a host call on the step's
        critical path, whatever its size).

        Rows already at capacity produce garbage logits (decode_step masks
        their scatter); fold the in-bounds check into the mask so their
        "sampled" token is never surfaced, and return the per-substep
        effective masks so the host knows which slots actually advanced.

        Everything the host needs comes back PACKED in one int32 array
        [2h+1, B] (h token rows, h advanced rows, 1 lengths row) so the
        device→host boundary is crossed once per dispatch, not three times.
        An expert model adds four rows, each one routing counter of the
        whole scan broadcast over B (``Turn``'s ``moe_*``): [2h+5, B].
        """
        tokens = jnp.where(step_state[3].astype(bool), carried,
                           step_state[0])[:, None]
        active = step_state[1].astype(bool)
        tok_idx0 = step_state[2]
        # Mask sampling state to the ACTIVE rows in-program: freed slots
        # keep stale device values (completions no longer re-upload), and
        # a stale temperature>0 would otherwise hold _sample_tokens'
        # runtime all-greedy lax.cond on the expensive branch for a whole
        # traffic lull's worth of greedy-only dispatches.
        temps = jnp.where(active, samp_f[0], 0.0)
        topp, pres, freq = samp_f[1], samp_f[2], samp_f[3]
        topk, seeds = samp_i[0], samp_i[1]
        rows = jnp.arange(tokens.shape[0])

        def substep(carry, j):
            cache, tokens, counts = carry
            # The pool rounds capacity up to whole pages; the engine's
            # max_len stays the generation bound, so a slot blocks (and
            # capacity-finishes) at max_len whatever the page size.
            advanced = jnp.logical_and(active, cache.lengths < self.max_len)
            # Dequantize INSIDE the scan body: hoisted outside, the bf16
            # tree becomes a loop-invariant XLA materializes once and
            # re-streams every substep — the exact bandwidth the int8
            # residency is supposed to save. In-body, the compiler may
            # fuse each convert+scale into its consuming matmul.
            logits, cache, *moe = self.model.decode_step_paged(
                self._mp(params), tokens, cache, advanced, **self._moe_kw
            )
            # Repetition control: subtract presence (any prior emission)
            # and frequency (per emission) penalties over the slot's
            # generated-token counts. All-zero penalties make this an
            # exact no-op on the hot path.
            logits = logits.astype(jnp.float32) - (
                pres[:, None] * (counts > 0)
                + freq[:, None] * counts.astype(jnp.float32)
            )
            nxt = self._sample_tokens(logits, temps, topk, seeds,
                                      tok_idx0 + j, bias_ids, bias_vals,
                                      topp)
            nxt = jnp.where(advanced, nxt, tokens[:, 0])
            counts = counts.at[rows, nxt].add(advanced.astype(jnp.int32))
            return (cache, nxt[:, None], counts), (nxt, advanced, *moe)

        (cache, _, counts), (toks, adv, *moe) = jax.lax.scan(
            substep, (cache, tokens, counts),
            jnp.arange(horizon, dtype=jnp.int32),
        )
        packed = [toks, adv.astype(jnp.int32), cache.lengths[None, :]]
        if moe:
            packed.append(jnp.broadcast_to(
                merge_routing_counters(moe[0])[:, None],
                (4, tokens.shape[0])))
        last = toks[-1]
        if self._carry_sharding is not None:
            last = jax.lax.with_sharding_constraint(
                last, self._carry_sharding)
        return (jnp.concatenate(packed, axis=0), cache,
                self._pin_counts(counts), last)

    def _spec_impl(self, params, cache, dcache, step_state,
                   bias_ids, bias_vals):
        """One speculative round for the whole batch, greedy-exact.
        ``step_state`` [2, B] int32 packs pending tokens + active mask
        into the round's single per-dispatch upload.

        Draft scans ``k+1`` single-token steps (proposing d_1..d_k and
        keeping its own cache complete through d_k), the target scores the
        [t0, d_1..d_k] window in one ``verify_step`` forward, and each row
        accepts its longest matching draft prefix plus the target's own
        next token — between 1 and k+1 tokens per round, never diverging
        from what plain greedy decode would emit.

        Returns ``(packed [k+3, B] int32, cache, dcache)``: k+1 output-token
        rows, an n_out row, and a post-round lengths row — one host fetch.
        """
        params = self._mp(params)
        tokens = step_state[0][:, None]
        active = step_state[1].astype(bool)
        k = self.spec_tokens
        B = tokens.shape[0]
        S = self.max_len  # shared-cache capacity

        def dstep(carry, _):
            dc, tok = carry
            logits, dc = self.draft_model.decode_step(
                self.draft_params, tok, dc, active
            )
            nxt = jnp.argmax(
                logits.astype(jnp.float32), axis=-1
            ).astype(jnp.int32)
            nxt = jnp.where(active, nxt, tok[:, 0])
            return (dc, nxt[:, None]), nxt

        dlen0 = dcache.lengths
        (dcache, _), drafts = jax.lax.scan(
            dstep, (dcache, tokens), None, length=k + 1
        )  # drafts [k+1, B]; the final proposal is drafted only to keep
        # the draft cache complete — it is never verified.
        d = drafts[:k].T  # [B, k]
        window = jnp.concatenate([tokens, d], axis=1)  # [B, k+1]
        # Verify through the page-table scatter + the staircase paged
        # read (scratch pages pre-arranged host-side by
        # _reserve_spec_scratch).
        logits, cache = self.model.verify_step_paged(
            params, window, cache, active)
        logits = logits.astype(jnp.float32)
        # Same per-request bias as the plain path (ONE rule — _apply_bias —
        # broadcast over the window) so biased greedy stays
        # speculative-exact.
        dense_bias = self._apply_bias(
            jnp.zeros((B, logits.shape[-1]), jnp.float32),
            bias_ids, bias_vals,
        )
        logits = logits + dense_bias[:, None, :]
        greedy = jnp.argmax(
            logits, axis=-1
        ).astype(jnp.int32)  # [B, k+1]; greedy[:, j] follows window[:, j]
        match = (d == greedy[:, :k]).astype(jnp.int32)
        m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # accepted drafts
        n_out = m + 1
        # Capacity clamp: only tokens whose k/v actually landed may count.
        remaining = jnp.maximum(S - cache.lengths, 0)
        n_out = jnp.where(active, jnp.minimum(n_out, remaining), 0)
        j_idx = jnp.arange(k + 1)[None, :]
        gm = jnp.take_along_axis(greedy, m[:, None], axis=1)  # [B, 1]
        d_pad = jnp.concatenate(
            [d, jnp.zeros((B, 1), jnp.int32)], axis=1
        )
        out = jnp.where(j_idx < m[:, None], d_pad, gm)  # [B, k+1]
        adv = n_out.astype(jnp.int32)
        cache = cache.replace(lengths=cache.lengths + adv)
        # Draft cache tracked the SAME sequence: roll its lengths back to
        # the verified prefix (its k/v for t0..d_k stay resident; garbage
        # past the new length is overwritten before it is ever attended).
        dcache = dcache.replace(lengths=dlen0 + adv)
        packed = jnp.concatenate(
            [out.T, n_out[None, :], cache.lengths[None, :]], axis=0
        )
        return packed, cache, dcache

    def _draft_catchup_impl(self, dparams, dcache, window, active, counts):
        """Write the draft k/v for tokens the TARGET just decoded plainly
        (window [B, h] at each row's own draft length) and advance draft
        lengths by the per-row advanced count — the draft stays in lockstep
        with the sequence without influencing it."""
        _, dcache = self.draft_model.verify_step(
            dparams, window, dcache, active
        )
        return dcache.replace(
            lengths=dcache.lengths + jnp.where(active, counts, 0)
        )

    def _admit_group_sizes(self) -> List[int]:
        """Compiled chunk group widths: powers of two up to
        ``max_admissions_per_step``, plus the cap itself when it isn't
        one. The cap is a GROUP-WIDTH clamp: ``_pump_prefill`` batches up
        to that many same-width single-chunk trains per dispatch (its
        PACING is the token budget, not this count). Every group width
        the engine can dispatch must round up to a width warmup
        compiled, or a burst pays a 20-40s XLA compile mid-serving —
        the warmup-coverage contract (``ops/jit_model.py``)."""
        sizes, s = [], 1
        while s <= self.max_admissions_per_step:
            sizes.append(s)
            s *= 2
        if sizes[-1] != self.max_admissions_per_step:
            sizes.append(self.max_admissions_per_step)
        return sizes

    def warmup(self) -> None:
        """Compile every hot-path program before serving: the chunk
        program over every (bucket x group) shape, the decode horizons
        {1, ttft, decode} and the spec/draft programs when a draft rides
        along.

        Contract-bearing (ISSUE 20): the whole run is bracketed by the
        compile ledger's warmup phase — ``end_warmup`` arms the
        steady-state mark, after which ANY compile is a recorded
        violation — and the ledger's warmup counts are cross-checked
        against ``ops/jit_model.required_for``: a registered program
        this engine needs that warmup did not compile raises HERE, at
        startup, instead of stalling a request 20-40s mid-serving."""
        ledger = get_ledger()
        before = ledger.counts(phase=PHASE_WARMUP)
        with _tracer().startup("rdb.startup.warmup",
                               replica=self._phase_tag) as span, \
                ledger.warming(), self._device_ctx():
            self._warmup_impl()
            span.attributes["programs"] = sum(
                1 for sp in _tracer().startup_spans()
                if sp.parent_id == span.span_id)
        after = ledger.counts(phase=PHASE_WARMUP)
        if after == before:
            # Zero new compiles: every program was already cached (this
            # engine was warmed before) — nothing to cross-check.
            return
        required = jit_model.required_for(self.draft_model is not None)
        gaps = [
            p.name for p in required
            if after.get(p.name, 0) <= before.get(p.name, 0)
        ]
        if gaps:
            raise RuntimeError(
                f"warmup coverage gap: registered hot-path program(s) "
                f"{gaps} compiled nothing during warmup — the warmup "
                "routine and ops/jit_model.required_for disagree; fix "
                "whichever is wrong before this engine serves"
            )

    @contextlib.contextmanager
    def _warming(self, program: str, key: str) -> Iterator[None]:
        """One warmed program's start-up span: its arguments, its first
        call and the wait for its result. The compile ledger charges the
        span with what traced, lowered and compiled (or was read from the
        cache) under it; ``run_ms`` is the rest of it: the executable's
        load, the uploads and the first execution."""
        with _tracer().startup(STARTUP_PROGRAM, replica=self._phase_tag,
                               program=program, key=key) as span:
            try:
                yield
            finally:
                span.end_ms = time.monotonic() * 1000.0
                a = span.attributes
                a["run_ms"] = span.duration_ms() - sum(
                    a.get(k, 0.0) for k in _STARTUP_COMPILE_MS)

    def _warmup_impl(self) -> None:
        # The pages-direct chunk program at every (bucket, group) shape
        # the pump can produce, plus the (1, C_max) long-train shape
        # (covered by group size 1 at the largest bucket). The packer's
        # empty group, the one a served group fills (so the warmed shapes
        # ARE the served ones): every page write drops, the lengths
        # scatter steers to the sentinel slot — the full program compiles
        # without touching a real page.
        for b in self.prompt_buckets:
            for g in self._admit_group_sizes():
                with self._warming("chunk_prefill", f"b={b},g={g}"):
                    first, self._cache = self._chunk_paged_fn(
                        self.params,
                        jnp.asarray(self._new_chunk_group(g, b)[0]),
                        self._cache,
                    )
                    first.block_until_ready()
        self._warmup_decode()

    def _warmup_decode(self) -> None:
        B = self.num_slots
        warm_samp_f = jnp.stack([
            jnp.zeros((B,), jnp.float32),
            jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.float32),
            jnp.zeros((B,), jnp.float32),
        ])
        warm_samp_i = jnp.zeros((2, B), jnp.int32)
        horizons = sorted({1, self.ttft_horizon, self.decode_horizon})
        for h in horizons:
            with self._warming("decode_step", f"h={h}"):
                (packed, self._cache, self._counts,
                 self._carry) = self._decode_fn(
                    self.params,
                    self._cache,
                    jnp.zeros((4, B), dtype=jnp.int32),
                    h,
                    warm_samp_f,
                    warm_samp_i,
                    jnp.zeros((B, self.max_bias_entries), jnp.int32),
                    jnp.zeros((B, self.max_bias_entries), jnp.float32),
                    self._counts,
                    self._carry,
                )
                packed.block_until_ready()
        if self._dcache is not None:
            with self._warming("spec_verify", f"k={self.spec_tokens}"):
                packed, self._cache, self._dcache = self._spec_fn(
                    self.params,
                    self._cache,
                    self._dcache,
                    jnp.zeros((2, B), dtype=jnp.int32),
                    jnp.zeros((B, self.max_bias_entries), jnp.int32),
                    jnp.zeros((B, self.max_bias_entries), jnp.float32),
                )
                packed.block_until_ready()
            # The catch-up runs after every PLAIN step of a spec engine —
            # one window shape per horizon; compile them now, not at the
            # first sampled request mid-serving.
            for h in horizons:
                with self._warming("draft_catchup", f"h={h}"):
                    self._dcache = self._draft_catchup_fn(
                        self.draft_params,
                        self._dcache,
                        jnp.zeros((B, h), dtype=jnp.int32),
                        jnp.zeros((B,), dtype=bool),
                        jnp.zeros((B,), dtype=jnp.int32),
                    )
                    self._dcache.lengths.block_until_ready()
            self._dcache = self._dcache.replace(
                lengths=self._put(np.zeros((self.num_slots,), np.int32))
            )
        self._counts = self._zero_counts_fn(
            self._counts, jnp.int32(0), jnp.int32(0)
        )
        # Reset state dirtied by warmup runs.
        self._cache = self._cache.replace(
            lengths=self._put(np.zeros((self.num_slots,), np.int32))
        )
        logger.info(
            "%s: warmed %d chunk programs + decode horizons {1, %d, %d}",
            self.model.name,
            len(self.prompt_buckets) * len(self._admit_group_sizes()),
            self.ttft_horizon, self.decode_horizon,
        )
        for line in self._expert_paths():
            logger.info("%s: experts: %s", self.model.name, line)
        for line in self._decode_paths():
            logger.info("%s: paged decode: %s", self.model.name, line)

    # --- admission ---------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [
            i for i, s in enumerate(self._slots)
            if s.free and i not in self._train_slots
        ]

    def _prep_prompt(self, req: Request) -> Tuple[np.ndarray, int, Dict]:
        """Validate one request BEFORE it costs a dispatch; returns
        (prompt ids, bucket, opts) where opts carries max_new / temperature
        / top_k / seed — or raises. Every way a payload can be malformed
        must surface here: past this point the request is committed to a
        slot and only engine errors can reject it."""
        try:
            prompt = np.asarray(
                req.payload["tokens"] if isinstance(req.payload, dict)
                else req.payload,
                dtype=np.int32,
            ).reshape(-1)
        except (TypeError, ValueError, KeyError) as e:
            raise BadRequest(f"{req.request_id}: malformed tokens: {e}")
        if prompt.size == 0:
            raise BadRequest(f"{req.request_id}: empty prompt")
        bucket = bucket_up(int(prompt.size), self.prompt_buckets)
        if bucket is None:
            # Longer than every bucket: a multi-chunk train (bucket
            # sentinel -1) as long as the cache can hold the prompt plus at
            # least one generated token.
            if prompt.size >= self.max_len:
                raise BadRequest(
                    f"{req.request_id}: prompt length {prompt.size} "
                    f"exceeds KV capacity {self.max_len}"
                )
            bucket = -1
        opts = {
            "_cache_len": int(prompt.size),  # post-commit cache lengths
            "max_new": self.default_max_new_tokens,
            "temperature": 0.0,   # greedy unless asked
            "top_k": 0,
            # Default seed derives from the request id via a STABLE hash
            # (crc32; Python's hash() is salted per process), so a
            # re-submitted request resamples the same way on any replica.
            "seed": zlib.crc32(req.request_id.encode()) & 0x7FFFFFFF,
            "stop": (),           # extra per-request stop token ids
            "session_id": None,   # multi-turn KV continuation key
            "logit_bias": {},     # token id -> additive logit bias
            "presence_penalty": 0.0,   # subtract once per distinct token
            "frequency_penalty": 0.0,  # subtract per emission
            "top_p": 1.0,              # nucleus sampling (1.0 = off)
        }
        if isinstance(req.payload, dict):
            p = req.payload
            try:
                # Coercion failures on client-supplied fields are the
                # CLIENT's fault (TypeError folds in: int(None) etc.) —
                # they must classify as BadRequest, not server errors.
                opts["max_new"] = int(
                    p.get("max_new_tokens", opts["max_new"])
                )
                opts["temperature"] = float(p.get("temperature", 0.0))
                opts["top_k"] = int(p.get("top_k", 0))
                opts["top_p"] = float(p.get("top_p", 1.0))
                opts["presence_penalty"] = float(
                    p.get("presence_penalty", 0.0)
                )
                opts["frequency_penalty"] = float(
                    p.get("frequency_penalty", 0.0)
                )
                if not (math.isfinite(opts["presence_penalty"])
                        and math.isfinite(opts["frequency_penalty"])):
                    # json.loads accepts Infinity/NaN; inf * 0 = NaN would
                    # silently poison the row's logits.
                    raise BadRequest(
                        f"{req.request_id}: penalties must be finite"
                    )
                if ((opts["presence_penalty"] or opts["frequency_penalty"])
                        and self._counts.shape[1] <= 1):
                    raise BadRequest(
                        f"{req.request_id}: penalties unsupported — model "
                        "exposes no vocab_size for token counting"
                    )
                if "seed" in p:
                    opts["seed"] = int(p["seed"]) & 0x7FFFFFFF
                opts["stop"] = frozenset(
                    int(t) for t in p.get("stop_token_ids", ())
                )
                if p.get("session_id") is not None:
                    opts["session_id"] = str(p["session_id"])
                    opts["_prompt_tokens"] = prompt
                bias = {
                    int(t): float(v)
                    for t, v in dict(p.get("logit_bias", {})).items()
                }
                for t in p.get("banned_tokens", ()):
                    bias[int(t)] = -1e9  # a ban = very negative bias
            except (TypeError, ValueError) as e:
                raise BadRequest(
                    f"{req.request_id}: malformed field: {e}"
                )
            if len(bias) > self.max_bias_entries:
                raise BadRequest(
                    f"{req.request_id}: {len(bias)} logit-bias entries "
                    f"exceed the limit of {self.max_bias_entries}"
                )
            V = getattr(self.model.cfg, "vocab_size", None)
            if V is not None and any(not 0 <= t < V for t in bias):
                raise BadRequest(
                    f"{req.request_id}: logit-bias token id out of vocab"
                )
            opts["logit_bias"] = bias
            if not 0.0 <= opts["top_p"] <= 1.0:
                raise BadRequest(
                    f"{req.request_id}: top_p must be in [0, 1]"
                )
            if opts["top_p"] == 0.0:
                # OpenAI's wire shape allows 0 (near-deterministic): the
                # smallest non-empty nucleus is the argmax alone.
                opts["top_p"] = 1e-9
            if opts["temperature"] < 0.0:
                raise BadRequest(
                    f"{req.request_id}: temperature must be >= 0"
                )
        return prompt, bucket, opts

    def _bias_arrays(self, opts: Dict):
        """opts -> fixed-width (ids [K], vals [K]) padded with no-op
        (0, 0.0) entries."""
        K = self.max_bias_entries
        ids = np.zeros((K,), dtype=np.int32)
        vals = np.zeros((K,), dtype=np.float32)
        for j, (t, v) in enumerate(opts.get("logit_bias", {}).items()):
            ids[j] = t
            vals[j] = v
        return ids, vals

    def _admit(self) -> int:
        """Fill free slots from the queue (continuous batching join):
        every dequeued request becomes a chunk TRAIN holding a slot. The
        prefill work is paced by ``prefill_token_budget`` in
        ``_pump_prefill``, so admission itself takes every free slot."""
        with self._phase("rdb.engine.admit") as ph:
            free = self._free_slots()
            admitted = self._admit_into(free) if free else 0
            ph.set_metadata(admitted=admitted, queue_len=len(self.queue))
            return admitted

    def _admit_into(self, free: List[int]) -> int:
        batch = self.queue.get_batch(len(free), discard_stale=True)
        # Mid-admission visibility: these requests are in NEITHER the
        # queue nor a slot until their prefill registers (seconds for a
        # cold/large program) — drain/idle checks that only look at
        # queue depth + active slots would see "idle" in that window and
        # a shutdown would abort a request that was seconds from its
        # first token (observed: the colocation demo deterministically
        # dropped its final tail request this way).
        self._admitting = len(batch)
        # The batch itself stays reachable while mid-admission: a chip
        # quarantine must be able to reject these futures — they are in
        # neither the queue nor a slot, and a wedged prefill dispatch
        # would otherwise strand them forever.
        self._admitting_batch = batch
        try:
            return self._admit_chunked(batch, free)
        finally:
            self._admitting = 0
            self._admitting_batch = []

    # --- token-budget chunked admission (ISSUE 15 tentpole) ----------------
    def _admit_chunked(self, batch: List[Request],
                       free: List[int]) -> int:
        """Universal chunked admission: every dequeued request becomes a
        :class:`_ChunkTrain` holding a slot; NO prefill dispatches here —
        the token-budget scheduler (:meth:`_pump_prefill`) advances
        trains between decode turns, so dequeue latency is microseconds
        and the stall bound is owned by one place."""
        t_dequeue = now_ms()
        started = 0
        for req in batch:
            req.admit_ms = t_dequeue
            try:
                prompt, bucket, opts = self._prep_prompt(req)
            except Exception as e:  # noqa: BLE001 — bad prompt must not kill loop
                req.reject(e)
                continue
            slot_idx = free[started]  # len(batch) <= len(free) by dequeue
            try:
                self._start_train(req, prompt, bucket, opts, slot_idx)
            except Exception as e:  # noqa: BLE001 — no-dangle rule
                logger.exception(
                    "%s: train admission failed", self.model.name
                )
                self._release_pages(opts)
                req.reject(e)
                continue
            started += 1
        return started

    def _start_train(self, req: Request, prompt: np.ndarray, bucket: int,
                     opts: Dict, slot_idx: int) -> None:
        """Create the chunk train for one admission: resolve session
        reuse (CoW page borrows with the base floored to a page
        boundary — the partial boundary page belongs to its owner and
        its positions are in the prompt, so the train recomputes them
        into its own pages) and park the train for the budget pump.
        Fresh bucketed prompts keep their bucket as the chunk width so
        same-bucket trains group into one program; long prompts and
        seeded continuations chunk at the largest bucket."""
        C_max = self.prompt_buckets[-1]
        total = int(prompt.size)
        base = 0
        W = bucket if bucket > 0 else C_max
        hit = None
        if self.paged_sessions is not None and opts["session_id"]:
            hit = self.paged_sessions.lookup(opts["session_id"], prompt)
            if hit is None:
                opts["_session_miss"] = True
        opts.setdefault("_pages", [])
        opts["_shared_pages"] = 0
        if hit is not None:
            shared_ids, stored_len = hit
            n_share = stored_len // self.page_size
            # Counted at REGISTRATION (_register, via _session_hit): a
            # starvation-valve requeue re-admits and re-looks-up —
            # counting here would double-count.
            opts["_session_hit"] = True
            if n_share > 0:
                head = list(shared_ids[:n_share])
                self._allocator.incref(head)
                opts["_pages"] = head
                opts["_shared_pages"] = n_share
                base = n_share * self.page_size
                self._page_journal.record(
                    "cow_copy", n_share,
                    self._allocator.allocated_pages, source="session",
                )
            W = C_max
        # NOTE: prefix-cache lookup is deferred to the train's FIRST
        # chunk dispatch (_maybe_borrow_prefix): earlier admissions of
        # the same dequeue have published their pages by then, and two
        # identical queued prompts must keep sharing.
        self._trains.append(_ChunkTrain(
            req=req, prompt=prompt, opts=opts, slot_idx=slot_idx, C=W,
            pos=base, base=base, total=total, started_ms=now_ms(),
        ))
        self._train_slots.add(slot_idx)

    def _pump_prefill(self, budget: Optional[int] = None,
                      behind_turn: bool = False) -> int:
        """Spend at most ``prefill_token_budget`` tokens (or ``budget``,
        what is left of it) advancing pending chunk trains — the
        engine-owned interleave. FCFS
        head-first (oldest train's TTFT first); same-width trains batch
        into ONE chunk program per dispatch. Page-starved trains park for the
        round (counted) instead of evicting live streams; a round where
        NOTHING could progress while no stream is active triggers the
        starvation valve (requeue the newest train) so parked trains
        can never deadlock the pool among themselves. Returns the
        prefill tokens spent (0: nothing was dispatched).

        ``behind_turn``: a scan is issued and not fetched (``_iterate``).
        Groups are then dispatched and NOT completed (``_issued_groups``),
        up to the first that ends a prompt; pages come from the free list
        alone, and a train that would need a cache pin shed or a spilled
        prefix read back parks, uncounted, for the pump after the harvest.
        Any other pump completes what is in flight first."""
        if not behind_turn:
            self._drain_issued()     # it completes its groups; may reclaim
        if not self._trains:
            return 0
        with self._phase("rdb.engine.prefill",
                         trains=len(self._trains)) as ph:
            tokens = self._spend_prefill_budget(
                self.prefill_token_budget if budget is None else budget,
                behind_turn)
            ph.set_metadata(tokens=tokens)
            return tokens

    def _spend_prefill_budget(self, budget: int, behind_turn: bool) -> int:
        """One round of :meth:`_pump_prefill`; returns the tokens spent."""
        model_tag = {"model": self.model.name}
        offered = budget
        parked: set = set()
        dispatched_any = False
        while budget > 0:
            # A train at its prompt's end waits for its group's completion.
            head = next(
                (t for t in self._trains
                 if id(t) not in parked and t.pos < t.total), None
            )
            if head is None or head.C > budget:
                break
            members = [head]
            # Group SINGLE-chunk trains only: a multi-chunk train
            # dispatches solo so it can complete (and publish its
            # prefix pages) before an identical queued prompt's
            # first chunk looks the prefix up — batching two copies
            # of the same long prompt would compute both.
            if head.total - head.base <= head.C:
                cap = min(self.max_admissions_per_step,
                          max(1, budget // head.C))
                for t in self._trains:
                    if len(members) >= cap:
                        break
                    if (t is head or id(t) in parked
                            or t.C != head.C or t.pos >= t.total
                            or t.total - t.base > t.C):
                        continue
                    members.append(t)
            ready = []
            for t in members:
                if (self._maybe_borrow_prefix(t, spilled=not behind_turn)
                        and self._grant_train_pages(
                            t, reclaim=not behind_turn)):
                    ready.append(t)
                else:
                    parked.add(id(t))
                    if not behind_turn:
                        PREFILL_STARVED.inc(tags=model_tag)
            if not ready:
                continue
            try:
                issued = self._issue_chunk_group(ready)
                if behind_turn:
                    self._issued_groups.append(issued)
                else:
                    self._complete_chunk_group(issued)
            except Exception as e:  # noqa: BLE001 — no-dangle rule
                logger.exception(
                    "%s: chunk dispatch failed", self.model.name
                )
                for t in ready:
                    self._drop_train(t, e)
                continue
            budget -= head.C * len(ready)
            dispatched_any = True
            if self.interleave_hook is not None:
                # Colocation fairness: co-tenant engines get their scans
                # between chunk dispatches.
                self.interleave_hook()
            if behind_turn and issued.finals:
                # Its prefix pages publish at its completion, after the
                # scan's harvest: the rest of the budget waits for that.
                break
        if (parked and not dispatched_any
                and not self._active_mask.any()):
            self._relieve_train_starvation()
        PREFILL_PENDING.set(float(len(self._trains)), tags=model_tag)
        return offered - budget

    def _drain_prefill(self) -> None:
        """Pump pending chunk trains to completion (tests and manual
        drivers that dequeued via ``_admit`` and want the admission
        fully registered; the serving loop never calls this — it pumps
        one budget per turn). Decode turns run ONLY when trains are
        parked behind pages that active streams hold — EOS is then the
        only thing that can free them."""
        while self._trains:
            before = (sum(t.pos for t in self._trains), len(self._trains))
            self._pump_prefill()
            after = (sum(t.pos for t in self._trains), len(self._trains))
            if after != before:
                continue
            if self._active_mask.any():
                # Starved behind live streams: advance them one turn so
                # finishes can free pages (a spin here would never end —
                # nothing else releases what the actives hold).
                self._step(horizon=1)
                continue
            # No progress and nothing decoding: trains are parked on
            # pages only EOS could free — a driver bug, not a wait.
            raise TimeoutError(
                f"{self.model.name}: chunk trains cannot progress "
                "(page-starved with no active streams)"
            )

    def _maybe_borrow_prefix(self, train: _ChunkTrain,
                             spilled: bool = True) -> bool:
        """Longest-shared-page-prefix CoW borrow, resolved at the
        train's FIRST chunk dispatch (not at dequeue): earlier trains
        from the same burst publish their pages at completion, and an
        identical queued prompt must share them — a dequeue-time lookup
        would always miss. Borrowed pages
        become the train's head; ``pos``/``base`` jump past the shared
        positions. False, with nothing resolved, where the lookup missed
        in HBM and the spill tier may not be read now (``spilled`` False:
        a scan is in flight, and a reload writes the pool)."""
        if (self.paged_prefix is None
                or train.pos != train.base or train.pos != 0
                or train.opts.get("_shared_pages", 0)
                or train.opts.get("_prefix_done")
                or train.total <= self.page_size):
            return True
        phit = self.paged_prefix.lookup(train.prompt)
        if (phit is None and not spilled and self.host_spill is not None
                and len(self.host_spill)):
            return False
        train.opts["_prefix_done"] = True
        if phit is None and self.host_spill is not None:
            phit = self._reload_spilled_prefix(train.prompt)
        if phit is None:
            PREFIX_MISSES.inc(tags={"model": self.model.name,
                                    "granularity": "page"})
            return True
        shared_ids, shared_len = phit
        head = list(shared_ids)
        self._allocator.incref(head)
        train.opts["_pages"] = head + train.opts["_pages"]
        train.opts["_shared_pages"] = len(head)
        train.pos = train.base = shared_len
        self._page_journal.record(
            "cow_copy", len(head), self._allocator.allocated_pages,
            source="prefix",
        )
        PREFIX_HITS.inc(tags={"model": self.model.name,
                              "granularity": "page"})
        return True

    def _grant_train_pages(self, train: _ChunkTrain,
                           reclaim: bool = True) -> bool:
        """Per-chunk page grant: extend the train's page run to cover
        the NEXT chunk's real positions (final chunks also cover the
        first generated token — or the first spec verify window on spec
        engines, the shared ``spec_scratch_pages`` rule). Cache pins
        shed first (not with ``reclaim`` False: behind a scan in flight
        the free list alone is taken; a spill reads the pool); a
        still-starved train parks (False) — live streams
        are never evicted to feed an admission."""
        take = min(train.C, train.total - train.pos)
        final = train.pos + take >= train.total
        if final:
            if self._dcache is not None:
                need = spec_scratch_pages(
                    train.total, self.spec_tokens + 1, self.page_size,
                    self._paged_capacity,
                )
            else:
                need = pages_for(
                    min(train.total + 1, self._paged_capacity),
                    self.page_size,
                )
        else:
            need = pages_for(train.pos + take, self.page_size)
        delta = need - len(train.opts["_pages"])
        if delta <= 0:
            return True
        while reclaim and not self._allocator.can_alloc(delta):
            if not self._reclaim_cache_pins():
                break
        if not self._allocator.can_alloc(delta):
            return False
        train.opts["_pages"].extend(self._allocator.alloc(delta))
        return True

    def _issue_chunk_group(self, trains: List[_ChunkTrain]) -> _IssuedGroup:
        """ONE pages-direct chunk program for up to a compiled group of
        same-width trains: chunk k/v scatter through per-row page-table
        rows, first token sampled in-program for final rows. Pad rows
        duplicate row 0 (identical data to identical pages — idempotent,
        the group-admission convention). The group's state is filled
        into ONE host buffer (:meth:`_new_chunk_group`, the packer the
        warm-up shares) and reaches the program as ONE upload. Prepared
        and dispatched, nothing fetched: the trains stand at their next
        position, and a train at its prompt's end stays in ``_trains``
        until :meth:`_complete_chunk_group`."""
        W = trains[0].C
        n = len(trains)
        active = int(self._active_mask.sum())
        pending = len(self._trains)
        with self._phase("rdb.engine.prefill.prepare"):
            group = next(s for s in self._admit_group_sizes() if s >= n)
            packed, f = self._new_chunk_group(group, W)
            finals: List[Tuple[int, _ChunkTrain]] = []
            for i, t in enumerate(trains):
                piece = t.prompt[t.pos : t.pos + W]
                take = int(piece.size)
                final = t.pos + take >= t.total
                f.tokens[i, :take] = piece
                f.mask[i, :take] = 1
                f.table[i] = table_array(
                    t.opts["_pages"], self._n_table_entries, self.num_pages
                )
                # Non-final rows steer the lengths scatter to the
                # sentinel slot: only the FINAL chunk publishes the
                # verified length.
                f.meta_i[i, :6] = (
                    t.slot_idx if final else self.num_slots, t.pos,
                    take - 1, t.opts["top_k"], t.opts["seed"], t.total)
                if self._slot_state:
                    f.meta_i[i, 6] = t.slot_idx
                f.meta_f[i] = (t.opts["temperature"],
                               t.opts.get("top_p", 1.0))
                f.bias_ids[i], f.bias_vals[i] = self._bias_arrays(t.opts)
                if final:
                    finals.append((i, t))
            if self._ring_pages:
                f.ring[:n] = ring_table(
                    np.asarray([t.slot_idx for t in trains], np.int32),
                    self._ring_pages, self._n_table_entries)
            # A filler row repeats row 0's writes; its mask stays 0 (the
            # program reads the mask only to count an expert model's REAL
            # routed tokens).
            packed[n:] = packed[0]
            f.mask[n:] = 0
        # conv layers: rows that begin their prompt (the program zeroes
        # their slots' states) and rows begun from a carried state
        state_turns, state_attrs = (0, 0), {}
        if self._slot_state:
            carries = sum(1 for t in trains if t.pos > 0)
            state_turns = (n - carries, carries)
            state_attrs = dict(zip(("state_resets", "state_carries"),
                                   state_turns))
        seq, behind = self._note_issue()
        t_dispatch = now_ms()
        with self._phase("rdb.engine.prefill.dispatch", seq=seq,
                         **state_attrs):
            first, self._cache = self._chunk_paged_fn(
                self.params, jnp.asarray(packed), self._cache)
        t_issued = now_ms()
        for t in trains:
            t.pos = min(t.pos + W, t.total)
        PREFILL_CHUNKS.inc(n, tags={"model": self.model.name})
        return _IssuedGroup(first, seq, trains, finals, group, t_dispatch,
                            t_issued, behind, active, pending, *state_turns)

    def _complete_chunk_group(self, issued: _IssuedGroup) -> None:
        """What a dispatched group leaves to do: where rows ended their
        prompt, fetch their first tokens, retire the trains, publish their
        prefix pages and register the slots; then the ring's record."""
        t_fetched, fetch = 0.0, (0.0, False)
        moe = (0, 0, 0, 0)
        if issued.finals:
            ready = issued.first.is_ready()
            with self._phase("rdb.engine.prefill.fetch", seq=issued.seq,
                             ready=int(ready)):
                fetch = (now_ms(), ready)
                first_host = np.asarray(issued.first)  # rdb-lint: disable=host-sync-in-hot-path (THE one fetch per chunk dispatch: the fused first-token ids — TTFT ends here, never at a logits round-trip)
            t_fetched = now_ms()
            self._note_fetched(issued.seq)
            if self._moe_kw:
                moe = first_host[issued.group:]
        with self._phase("rdb.engine.prefill.finish"):
            for i, t in issued.finals:
                self._retire_train(t)
                if self.paged_prefix is not None:
                    # Publish BEFORE registration: a stop-on-first-token
                    # finish frees the slot's pages, and the insert must
                    # pin them first.
                    self.paged_prefix.insert(t.prompt, t.opts["_pages"])
                if self._dcache is not None:
                    # The draft has no pages-direct path (its cache is a
                    # slab): replay the whole prompt through the draft's
                    # chunk program so speculation starts synced.
                    self._draft_long_fill(
                        t.prompt, t.slot_idx, self.prompt_buckets[-1]
                    )
                self._register(t.slot_idx, t.req, int(first_host[i]),
                               t.opts, t_fetched)
        self._log_dispatch("chunk", issued.t_dispatch, issued.t_issued,
                           t_fetched, 0,
                           issued.trains[0].C * len(issued.trains),
                           issued.active, issued.pending, moe,
                           queued_behind=issued.queued_behind,
                           state_turns=(issued.state_resets,
                                        issued.state_carries),
                           fetch=fetch, seq=issued.seq)

    def _retire_train(self, train: _ChunkTrain) -> None:
        if train in self._trains:
            self._trains.remove(train)
        self._train_slots.discard(train.slot_idx)

    def _drop_train(self, train: _ChunkTrain, exc: Exception) -> None:
        """A failed train must never dangle: release its pages (borrowed
        head decrefs its borrow) and reject the caller."""
        self._retire_train(train)
        self._release_pages(train.opts)
        train.req.reject(exc)

    def _relieve_train_starvation(self) -> None:
        """Deadlock valve for per-chunk grants: with no active streams
        there is no EOS to free pages, so an all-parked train set would
        wait forever on pages the OTHER parked trains hold. Requeue the
        NEWEST train (least sunk prefill cost — the slot-starvation
        requeue's twin), releasing its grant back to the pool. A LONE
        starved train should be impossible (the pool must back one
        slot's worth by construction, and with no actives + drained
        cache pins nothing else holds pages) — but if it ever happens,
        requeue it too: back in the queue, deadline-based staleness
        eventually rejects it, instead of the loop spinning on an
        unservable train forever.
        Prefer a train that has not dispatched yet (pos == base): zero
        sunk prefill cost AND no metrics to double-count."""
        if not self._trains:
            return
        train = next(
            (t for t in reversed(self._trains) if t.pos == t.base),
            self._trains[-1],
        )
        self._retire_train(train)
        self._release_pages(train.opts)
        if not self.queue.add_request(train.req, reject_on_full=False,
                                      requeue=True):
            self.queue.count_external_drop(
                train.req, reason="requeue_refused"
            )
            train.req.reject(RequestDropped(
                f"{train.req.request_id}: queue refused requeue during "
                "page-starved chunked admission"
            ))

    # --- paged admission bookkeeping ---------------------------------------
    def _read_pages(self, page_ids: List[int]) -> Dict[str, np.ndarray]:
        """Gather the listed pages' contents to host (spill, the fabric's
        parcels: every host-side read of the pool comes through here). The
        pages are pinned (prefix-cache refs) and never rewritten after
        publication (CoW invariant), so this read races nothing; a scan
        issued ahead is completed first (its callers have, by then)."""
        self._drain_issued()
        return self._cache.read_pages(
            np.asarray(page_ids, np.int32), self.model.cfg)

    def _write_pages(self, page_ids: List[int],
                     payload: Dict[str, np.ndarray]) -> None:
        """Scatter spilled contents into freshly allocated pages
        (reload). Functional update — the pool array has one logical
        writer (this engine thread), like the page-table upload."""
        self._drain_issued()
        with self._device_ctx():
            self._cache = self._cache.write_pages(
                jnp.asarray(np.asarray(page_ids, np.int32)), payload,
                self.model.cfg)

    def _reload_spilled_prefix(
        self, prompt: np.ndarray
    ) -> Optional[Tuple[List[int], int]]:
        """Probe the host-RAM spill tier for the longest spilled
        page-prefix of ``prompt``; on a hit the pages come back into
        fresh HBM, get republished in the prefix cache, and the caller
        proceeds exactly as on an HBM hit. Returns the (page_ids,
        shared_len) borrow or None (absent, or no free pages for the
        reload — recompute then, never deepen the pressure)."""
        max_n = (int(prompt.size) - 1) // self.page_size
        if max_n <= 0 or len(self.host_spill) == 0:
            return None
        keys = digest_chain(prompt, self.page_size, max_n)
        for n in range(max_n, 0, -1):
            if keys[n - 1] not in self.host_spill:
                continue
            pids = self.host_spill.reload(keys[n - 1], self._allocator)
            if pids is None:
                return None
            # Republish (the cache pins them), then drop the reload's
            # own hold — pin symmetry identical to a slot publishing.
            self.paged_prefix.insert(prompt[: n * self.page_size], pids)
            self._allocator.decref(pids)
            return self.paged_prefix.lookup(prompt)
        return None

    def prefix_digests(self, limit: int = 128) -> Optional[Dict[str, Any]]:
        """Bounded digest publication for cluster-wide prefix routing:
        HBM prefix-cache entries plus spilled entries (both servable
        here — one reload vs a full recompute elsewhere), as
        ``{"page_size": ..., "digests": {hex: chain_len}}``."""
        if self.paged_prefix is None:
            return None
        digests = self.paged_prefix.digests(limit)
        if self.host_spill is not None and len(digests) < limit:
            for key, n in self.host_spill.digests(
                limit - len(digests)
            ).items():
                digests.setdefault(key, n)
        out: Dict[str, Any] = {
            "page_size": self.page_size, "digests": digests,
        }
        if self.host_spill is not None:
            # Spill round-trip convergence fix: a reload moves an entry
            # between tiers without changing this engine's advertised
            # union, so replacement-expiry upstream sees "unchanged" and
            # never notifies out-of-process routers. Surface the reloaded
            # keys so the controller forces a push (key present only when
            # non-empty — steady-state publications stay byte-identical).
            reloaded = self.host_spill.drain_republish()
            if reloaded:
                out["reloaded"] = reloaded
        return out

    def _reclaim_cache_pins(self) -> bool:
        """Shed one LRU cache pin under pool pressure — prefix entries
        first (pure recompute cost), then session turns (a re-prefill
        next turn). Cache pins are optimizations; live streams are not:
        this runs before any capacity-finish eviction. With a spill tier
        the shed prefix entry's page CONTENTS move to host RAM first, so
        the 'recompute cost' becomes 'one reload'. Returns True if an
        entry was dropped (its pages free unless a borrower still holds
        them — callers loop)."""
        if self.paged_prefix is not None and self.host_spill is not None:
            lru = self.paged_prefix.peek_lru()
            if lru is not None:
                key, pages = lru
                self.host_spill.spill(
                    key, list(pages), self._allocator.allocated_pages
                )
        for which, cache in (("prefix", self.paged_prefix),
                             ("session", self.paged_sessions)):
            if cache is not None and cache.evict_lru():
                self._page_journal.record(
                    "cache_reclaim", 0, self._allocator.allocated_pages,
                    cache=which,
                )
                return True
        return False

    def _release_pages(self, opts: Dict) -> None:
        """Undo an admission's page reservation (failed/requeued before
        a slot took ownership). Decrefs the whole list — borrowed CoW
        pages release their borrow, private pages free."""
        pages = opts.pop("_pages", None)
        opts.pop("_shared_pages", None)
        if pages:
            self._allocator.decref(pages)

    # --- the draft model's prompt replay (speculative engines) -------------
    def _interleave_step(self) -> None:
        """One plain decode step for the active batch between the chunk
        dispatches of a draft replay — the bound that keeps a long fill
        from stalling in-flight requests for more than one chunk. When a
        colocation executor hosts this engine it installs
        ``interleave_hook``, so CO-TENANT engines get scans between chunks
        too — otherwise one tenant's long-prompt admission would
        monopolize the shared chip for the whole fill
        (engine/colocate.py)."""
        if self._active_mask.any():
            self._step(horizon=1)
        if self.interleave_hook is not None:
            self.interleave_hook()

    def _draft_long_fill(self, prompt: np.ndarray, slot_idx: int,
                         C: int) -> None:
        """Chunk the long prompt through the DRAFT model into its cache
        row, interleaving decode steps between chunks like the target fill
        — the chunked-prefill latency bound (one chunk's stall, not the
        whole prompt) must hold for the draft pass too."""
        fns = self._draft_fill_fns.get(C)
        if fns is None:
            def chunk_impl(dparams, tokens, attn_mask, row, start, take):
                return self.draft_model.prefill_chunk(
                    dparams, tokens, attn_mask, row, start, take
                )

            fns = (
                instrument("draft_long_chunk",
                           jax.jit(chunk_impl, donate_argnums=(3,))),
                instrument("draft_long_commit",
                           jax.jit(commit_row, donate_argnums=(0,))),
            )
            self._draft_fill_fns[C] = fns
        chunk_fn, commit_fn = fns
        # Whole chunks covering the draft cache: the replay starts at
        # position 0, so every chunk is aligned.
        dcap = self._dcache.capacity
        row = self.draft_model.make_cache(1, ((dcap + C - 1) // C) * C)
        _, row = run_chunked(
            chunk_fn, self.draft_params, prompt, C, row,
            between=self._interleave_step,
        )
        self._dcache = commit_fn(self._dcache, row, jnp.int32(slot_idx))

    def _register(
        self, slot_idx: int, req: Request, first_tok: int, opts: Dict,
        t: float,
    ) -> None:
        max_new = opts["max_new"]
        slot = self._slots[slot_idx]
        slot.request = req
        slot.generated = [first_tok]
        slot.max_new_tokens = max_new
        slot.prefill_done_ms = t
        slot.last_token = first_tok
        slot.stop = opts["stop"]
        slot.session_id = opts.get("session_id")
        slot.prompt_tokens = opts.get("_prompt_tokens")
        self._len_host[slot_idx] = int(opts.get("_cache_len", 0))
        # Ownership handoff: the slot now holds the admission's page
        # reservation; the host table mirror maps it for the next
        # dispatch's refresh.
        slot.pages = list(opts.get("_pages", ()))
        slot.shared_pages = int(opts.get("_shared_pages", 0))
        self._table_host[slot_idx] = table_array(
            slot.pages, self._n_table_entries, self.num_pages
        )
        self._table_dirty = True
        self._tokens[slot_idx, 0] = first_tok
        self._active_mask[slot_idx] = True
        self._temps[slot_idx] = opts["temperature"]
        self._topk[slot_idx] = opts["top_k"]
        self._topp[slot_idx] = opts.get("top_p", 1.0)
        self._seeds[slot_idx] = opts["seed"]
        self._bias_ids[slot_idx], self._bias_vals[slot_idx] = \
            self._bias_arrays(opts)
        self._pres[slot_idx] = opts.get("presence_penalty", 0.0)
        self._freq[slot_idx] = opts.get("frequency_penalty", 0.0)
        self._sampling_dev = None  # host arrays changed
        if self._pres[slot_idx] or self._freq[slot_idx]:
            # Stale counts only matter to rows that USE them: zero the
            # reused slot's row on demand (penalty-free admissions — the
            # common case — skip the dispatch; their penalties multiply
            # the stale counts by zero).
            self._counts = self._zero_counts_fn(
                self._counts, jnp.int32(slot_idx), jnp.int32(first_tok)
            )

        PREFILLS_TOTAL.inc(tags={"model": self.model.name})
        if opts.get("_session_hit"):
            # Counted here, past every requeue window.
            SESSION_HITS.inc(tags={"model": self.model.name})
        if opts.get("_session_miss"):
            SESSION_MISSES.inc(tags={"model": self.model.name})
        TTFT_MS.observe(
            t - req.arrival_ms, tags={"model": self.model.name},
            trace_id=(req.trace_ctx or {}).get("trace_id"),
        )
        admit_ms = getattr(req, "admit_ms", None) or t
        queue_wait = max(0.0, admit_ms - req.arrival_ms)
        # The share of queue_wait spent inside the decode scan that was in
        # flight when the request arrived: overlap of [arrival, dequeue]
        # with the most recently completed scan window (the turn ring's).
        scan = self._last_scan
        scan_wait = 0.0 if scan is None else max(
            0.0, min(admit_ms, scan.t_fetched)
            - max(req.arrival_ms, scan.t_dispatch))
        prefill_ms = max(0.0, t - admit_ms)
        self._ttft_parts.append(
            (queue_wait, min(scan_wait, queue_wait), prefill_ms)
        )
        TTFT_QUEUE_MS.observe(queue_wait, tags={"model": self.model.name})
        TTFT_PREFILL_MS.observe(prefill_ms, tags={"model": self.model.name})
        if _tracer().enabled:
            # Retroactive prefill span (admit -> first token) in the
            # request's trace: with the queue.wait span the pop emitted,
            # the flight record now shows the full TTFT decomposition.
            _tracer().record_span(
                "decode.prefill",
                ctx=req.trace_ctx,
                start_ms=admit_ms,
                end_ms=t,
                model=self.model.name,
                lane=self.model.name,
                queue_wait_ms=round(queue_wait, 2),
                scan_wait_ms=round(min(scan_wait, queue_wait), 2),
            )
        req.stream_put(first_tok)
        # First token may already satisfy the stop conditions.
        if self._is_stop(slot, first_tok) or max_new <= 1:
            reason = "eos" if self._is_stop(slot, first_tok) else "length"
            self._finish(slot_idx, reason)

    def _is_stop(self, slot: _Slot, tok: int) -> bool:
        return (
            (self.eos_token_id is not None and tok == self.eos_token_id)
            or tok in slot.stop
        )

    # --- step + eviction ---------------------------------------------------
    def _finish(self, slot_idx: int, reason: str) -> None:
        slot = self._slots[slot_idx]
        req = slot.request
        t = now_ms()
        if slot.pages:
            if (self.paged_sessions is not None and slot.session_id
                    and slot.prompt_tokens is not None):
                # O(1) session store: pin the pages covering the turn's
                # history (prompt + generated[:-1]: the final token is
                # still pending, never fed) instead of copying the row
                # out. Cached positions past the history (spec rounds
                # advance the cache through tokens the host truncated at
                # a stop) sit beyond the stored length and are
                # overwritten by the next turn's tail prefill before
                # they can be attended. Incref (store) strictly before
                # the slot's decref below, so the pages never transit
                # the free list.
                history = np.concatenate([
                    np.asarray(slot.prompt_tokens, np.int32),
                    np.asarray(slot.generated[:-1], np.int32),
                ])
                self.paged_sessions.store(
                    slot.session_id, slot.pages, history
                )
            self._free_slot_pages(slot_idx)
        result = DecodeResult(
            tokens=list(slot.generated),
            finish_reason=reason,
            ttft_ms=slot.prefill_done_ms - req.arrival_ms,
            total_ms=t - req.arrival_ms,
        )
        req.fulfill(result)
        if _tracer().enabled:
            # Completion event joined to the caller's trace: carries the
            # numbers an operator actually debugs with.
            with _tracer().attach_context(req.trace_ctx, "decode.sequence") as sp:
                if sp is not None:
                    sp.attributes.update(
                        tokens=len(slot.generated),
                        finish_reason=reason,
                        ttft_ms=round(result.ttft_ms, 1),
                        total_ms=round(result.total_ms, 1),
                    )
        self.queue.record_batch_completion([req], completed_at_ms=t)
        TOKENS_TOTAL.inc(len(slot.generated), tags={"model": self.model.name})
        self._slots[slot_idx] = _Slot()
        self._active_mask[slot_idx] = False
        self._len_host[slot_idx] = 0
        self._temps[slot_idx] = 0.0
        self._topk[slot_idx] = 0
        self._topp[slot_idx] = 1.0
        self._seeds[slot_idx] = 0
        self._bias_ids[slot_idx] = 0
        self._bias_vals[slot_idx] = 0.0
        self._pres[slot_idx] = 0.0
        self._freq[slot_idx] = 0.0
        # NO device-array invalidation here: the freed slot's stale device
        # values are masked (inactive rows' samples are discarded and add
        # zero to counts), and _register refreshes the row before any
        # reuse — invalidating on every completion forced a full re-upload
        # of all eight sampling arrays per finished sequence, pure host
        # overhead at high completion churn.
        self.completed += 1

    # --- page-pool management ----------------------------------------------
    def _free_slot_pages(self, slot_idx: int) -> None:
        """Return a finished slot's page references to the pool — EOS
        frees pages immediately mid-cycle: this runs inside ``_harvest``,
        before the next admission check, so a burst waiting on pages can
        admit the moment a stream ends.
        The device table row goes to sentinel at the next refresh, which
        happens before any dispatch could write through it."""
        slot = self._slots[slot_idx]
        if slot.pages:
            self._allocator.decref(slot.pages)
            slot.pages = []
            slot.shared_pages = 0
        self._table_host[slot_idx] = self.num_pages
        self._len_host[slot_idx] = 0
        self._table_dirty = True

    def _refresh_table(self) -> None:
        """Upload the host page-table mirror when it changed (admission,
        finish, growth). The device table has exactly ONE writer — this
        upload; compiled programs treat it as read-only — so the mirror
        can never drift from what the kernel gathers through."""
        if self._table_dirty:
            self._cache = self._cache.replace(
                page_table=self._put(self._table_host)
            )
            self._table_dirty = False

    def _ensure_page_headroom(self, horizon: int) -> None:
        """Grow active slots' page runs to cover the next ``horizon``
        substeps before the scan dispatches (a scan cannot allocate
        mid-flight — shapes are static and the allocator is host state).

        Over-subscribed pools can run dry here; the documented policy is
        to CAPACITY-FINISH the most recently admitted other slot (its
        caller gets a complete-but-truncated result, the same contract
        as cache exhaustion) and reuse its pages — newest-first eviction
        keeps long-running streams, which have the most sunk cost,
        alive. Full-backing pools (the default) never enter the eviction
        branch."""
        for i in np.flatnonzero(self._active_mask):
            slot = self._slots[i]
            if slot.free:
                continue
            delta = self._pages_short(i, horizon)
            if delta <= 0:
                continue
            while not self._allocator.can_alloc(delta):
                # Shed cache pins first: a pool pinned by prefix/session
                # entries must never truncate a live stream to grow
                # another (the entries are pure optimizations and, being
                # the only non-slot owners, are what makes slot eviction
                # reclaim nothing).
                if self._reclaim_cache_pins():
                    continue
                victim = self._eviction_victim(exclude=int(i))
                if victim is None:
                    break
                PAGE_EVICTIONS.inc(tags={"model": self.model.name})
                self._page_journal.record(
                    "eviction", len(self._slots[victim].pages),
                    self._allocator.allocated_pages, slot=int(victim),
                )
                self._finish(victim, "capacity")
            if not self._allocator.can_alloc(delta):
                # Not even eviction could cover this slot: truncate IT.
                PAGE_EVICTIONS.inc(tags={"model": self.model.name})
                self._page_journal.record(
                    "eviction", len(slot.pages),
                    self._allocator.allocated_pages, slot=int(i),
                )
                self._finish(int(i), "capacity")
                continue
            slot.pages.extend(self._allocator.alloc(delta))
            self._table_host[i] = table_array(
                slot.pages, self._n_table_entries, self.num_pages
            )
            self._table_dirty = True

    def _pages_short(self, i: int, positions: int) -> int:
        """Pages slot ``i`` lacks to hold ``positions`` more than the host's
        mirror of its length says it holds (0 or less: none); the engine's
        ``max_len`` bounds what a slot can ever hold."""
        return pages_for(
            min(int(self._len_host[i]) + positions, self.max_len),
            self.page_size) - len(self._slots[i].pages)

    def _eviction_victim(self, exclude: int) -> Optional[int]:
        """Most recently admitted active slot other than ``exclude``
        (newest-first eviction), or None."""
        best, best_t = None, -1.0
        for j, s in enumerate(self._slots):
            if j == exclude or s.free or not self._active_mask[j]:
                continue
            if s.prefill_done_ms > best_t:
                best, best_t = j, s.prefill_done_ms
        return best

    def _pick_horizon(self) -> int:
        """Three-tier horizon: full scan only when the batch is full (no
        admission possible — throughput-bound), single steps while requests
        wait for a free slot (admit ASAP), and the short ``ttft_horizon``
        when slots are free but nothing is queued — so an arrival during the
        scan waits at most ttft_horizon substeps, not decode_horizon."""
        if self.decode_horizon <= 1:
            return 1
        if self._trains:
            # Chunk trains pending: single-step turns keep the
            # chunk/turn interleave cadence tight — a long scan between
            # chunks would stretch every pending train's TTFT by the
            # whole scan.
            return 1
        if not self._free_slots():
            return self.decode_horizon
        if len(self.queue) == 0:
            return self.ttft_horizon
        return 1

    def ttft_breakdown(self) -> Dict[str, float]:
        """p50/p95 of the TTFT components over the rolling window:
        ``queue_wait`` (arrival -> dequeue — slot starvation plus waiting
        out in-flight scans), ``scan_wait`` (the in-flight-scan share of
        queue_wait; bounded by ttft_horizon substeps while slots are free),
        and ``prefill`` (dequeue -> first token). Published in the bench
        LLM row so an on-chip run shows where the TTFT milliseconds live."""
        parts = list(self._ttft_parts)
        if not parts:
            return {"n": 0}
        out: Dict[str, float] = {"n": len(parts)}
        for name, vals in zip(
            ("queue_wait_ms", "scan_wait_ms", "prefill_ms"),
            zip(*parts),
        ):
            s = sorted(vals)
            out[f"{name}_p50"] = round(s[len(s) // 2], 2)
            out[f"{name}_p95"] = round(s[min(len(s) - 1,
                                             int(len(s) * 0.95))], 2)
        return out

    def reset_ttft_window(self) -> None:
        """Drop the rolling TTFT window and the turn ring (benchmark phase
        boundaries)."""
        self._ttft_parts.clear()
        self.turns.clear()
        self.turns_dropped = 0

    def _sampling_arrays(self):
        """Device copies of the per-slot sampling state, PACKED by dtype:
        (samp_f [4,B] = temps/topp/pres/freq, samp_i [2,B] = topk/seeds,
        bias_ids [B,K], bias_vals [B,K]) — four transfers per refresh
        instead of eight."""
        if self._sampling_dev is None:
            self._sampling_dev = (
                jnp.asarray(np.stack(
                    [self._temps, self._topp, self._pres, self._freq]
                )),
                jnp.asarray(np.stack([self._topk, self._seeds])),
                jnp.asarray(self._bias_ids),
                jnp.asarray(self._bias_vals),
            )
        return self._sampling_dev

    def _turn_links(self, active_mask) -> Optional[List]:
        """Trace contexts of the sequences active in the scan just
        fetched, for its ``decode.turn`` span; taken BEFORE the harvest
        can finish any of them. None with the flight recorder off."""
        if not _tracer().enabled or not active_mask.any():
            return None
        return [
            _link_to(slot.request.trace_ctx)
            for i, slot in enumerate(self._slots)
            if active_mask[i] and slot.request is not None
        ]

    def _record_turn_span(self, rec: Turn, links: List, horizon: int,
                          spec: bool = False) -> None:
        """One retroactive span per decode scan (dispatch -> host fetch,
        the turn ring's stamps), linked to every sequence that was active
        in it: continuous batching's fan-in, the decode analogue of the
        batch-execution span. Bounded by num_slots links per turn."""
        _tracer().record_span(
            "decode.turn",
            start_ms=rec.t_dispatch,
            end_ms=rec.t_fetched,
            links=links,
            model=self.model.name,
            lane=self.model.name,
            horizon=int(horizon),
            active=rec.active,
            spec=spec,
        )

    def _use_spec(self) -> bool:
        """Speculative rounds serve all-greedy batches only: sampled rows
        need rejection sampling for exactness, so any temperature>0 row
        drops the whole batch back to plain decode."""
        active = self._active_mask
        return (
            self._dcache is not None
            and self._sample_custom is None
            and bool(active.any())
            and float(self._temps[active].max(initial=0.0)) == 0.0
            # Penalties need the per-step count updates of the plain path
            # — NEGATIVE penalties (valid per the API) count too, so test
            # magnitude, not the signed max.
            and float(np.abs(self._pres[active]).max(initial=0.0)) == 0.0
            and float(np.abs(self._freq[active]).max(initial=0.0)) == 0.0
        )

    # --- paged spec-round page bookkeeping (ISSUE 13 tentpole) -----------
    def _reserve_spec_scratch(self) -> bool:
        """Extend each active slot's device table to cover this round's
        verify window ``[len, len + k + 1)`` with SCRATCH pages drawn
        from the shared pool (``tile_math.spec_scratch_pages`` — the
        admission headroom rule re-applied per round). Scratch pages are
        named by the table (the verify scatter writes through them) but
        are NOT yet owned by the slot: the round's outcome splices the
        accepted prefix's pages into ``slot.pages`` and frees the
        rejected tail (:meth:`_splice_spec_pages`).

        Under pool pressure, cache pins shed first (same ladder as
        :meth:`_ensure_page_headroom`); if the pool still cannot host a
        window, every page taken for THIS round is returned and the
        caller degrades to a plain step — speculation is an
        optimization, and the degradation is bounded (plain decode),
        never a truncated live stream."""
        win = self.spec_tokens + 1
        for i in np.flatnonzero(self._active_mask):
            slot = self._slots[i]
            if slot.free:
                continue
            need = spec_scratch_pages(
                int(self._len_host[i]), win, self.page_size,
                self._paged_capacity,
            )
            delta = need - len(slot.pages)
            if delta <= 0:
                continue  # partial-page headroom covers the window
            while not self._allocator.can_alloc(delta):
                if not self._reclaim_cache_pins():
                    break
            if not self._allocator.can_alloc(delta):
                self._rollback_spec_scratch()
                return False
            pids = self._allocator.alloc(delta)
            n0 = len(slot.pages)
            self._spec_scratch[int(i)] = (n0, pids)
            self._table_host[i, n0:n0 + delta] = pids
            self._table_dirty = True
        return True

    def _rollback_spec_scratch(self) -> None:
        """Give back every scratch page of an unresolved round (aborted
        reserve, or a round that died between reserve and splice). The
        table row is REBUILT from the slot's owned pages rather than
        sentinel-stamping the recorded span: between a crashed round and
        this rollback the row may have been rewritten by a plain step's
        headroom growth or a finish + fresh admission at the same index,
        and blind sentinels over that span would silently void a live
        occupant's KV writes (mode=\"drop\") — the corruption class the
        regression test pins. The scratch pages themselves are still
        exclusively round-held (refcount 1, never in ``slot.pages``), so
        the decref is unconditionally correct."""
        for i, (_n0, pids) in self._spec_scratch.items():
            self._table_host[i] = table_array(
                self._slots[i].pages, self._n_table_entries, self.num_pages
            )
            self._table_dirty = True
            self._allocator.decref(pids)
        self._spec_scratch.clear()

    def _splice_spec_pages(self, lengths_host: np.ndarray) -> None:
        """Resolve the round's scratch pages from the verified lengths:
        scratch pages whose table span is covered by the ACCEPTED length
        commit by page-table splice — re-pointed into ``slot.pages``
        with zero KV bytes copied (the entries already name them; the
        accepted tokens' k/v landed there during verify) — and the
        rejected tail frees back to the pool, its table entries reset to
        the sentinel. Each movement is an allocator-journal event
        (``spec_commit``/``spec_reject``), the acceptance signal the
        Perfetto export renders next to ``decode.turn`` spans. Runs
        BEFORE the harvest so a finishing slot frees exactly the pages
        it owns. The dict is drained up front: were an entry to survive
        its own resolution, a later rollback would decref the same
        pages twice."""
        items = list(self._spec_scratch.items())
        self._spec_scratch.clear()
        for i, (n0, pids) in items:
            slot = self._slots[i]
            covered = pages_for(int(lengths_host[i]), self.page_size)
            commit_n = max(0, min(covered - n0, len(pids)))
            committed, rejected = pids[:commit_n], pids[commit_n:]
            if committed:
                slot.pages.extend(committed)
                self._page_journal.record(
                    "spec_commit", len(committed),
                    self._allocator.allocated_pages, slot=int(i),
                )
            if rejected:
                self._table_host[i, n0 + commit_n:n0 + len(pids)] = \
                    self.num_pages
                self._table_dirty = True
                self._allocator.decref(rejected)
                self._page_journal.record(
                    "spec_reject", len(rejected),
                    self._allocator.allocated_pages, slot=int(i),
                )

    def spec_acceptance(self) -> Optional[float]:
        """Rolling draft-token acceptance rate (accepted/drafted over
        the bounded round window), or None before the first round —
        stamped into bench rows and the profiled-acceptance input the
        sim's spec pricing consumes."""
        if not self._spec_acc_window:
            return None
        acc = sum(a for a, _ in self._spec_acc_window)
        drafted = sum(d for _, d in self._spec_acc_window)
        return acc / drafted if drafted else None

    def _spec_step(self) -> None:
        k = self.spec_tokens
        with self._phase("rdb.engine.turn") as ph:
            with self._phase("rdb.engine.turn.prepare"):
                if self._spec_scratch:
                    # A previous round died between reserve and splice
                    # (a device error the loop swallowed): its scratch
                    # would otherwise leak refcounts forever and
                    # shadow-occupy the pool. Roll it back before
                    # arranging a fresh window.
                    self._rollback_spec_scratch()
                reserved = self._reserve_spec_scratch()
            if not reserved:
                # Pool too tight for a verify window this round: one
                # plain step instead (its own headroom ladder may
                # capacity-evict, but the spec path never does) — under
                # sustained pressure throughput degrades to plain decode,
                # not off a cliff.
                return self._plain_turn(ph, 1)
            active = int(self._active_mask.sum())
            kv_pages_live = self._kv_pages_live(k + 1)
            kv_rows = self._kv_rows(k + 1)
            ph.set_metadata(horizon=k, active=active, spec=1)
            try:
                # From here to the packed fetch, scratch is armed but
                # unresolved: ANY failure — table upload, sampling-state
                # upload, the dispatch itself — must roll it back NOW,
                # not at the next spec round (there may never be one: a
                # sampled row can pin _use_spec() False for the engine's
                # remaining lifetime, shadow-occupying the pool), then
                # let the loop's error handling see the error.
                with self._phase("rdb.engine.turn.prepare"):
                    self._refresh_table()
                    (_samp_f, _samp_i, bias_ids_d, bias_vals_d) = \
                        self._sampling_arrays()
                    state = np.stack([
                        self._tokens[:, 0],
                        self._active_mask.astype(np.int32),
                    ])
                seq, behind = self._note_issue()
                t_dispatch = now_ms()
                with self._phase("rdb.engine.turn.dispatch", seq=seq):
                    packed, self._cache, self._dcache = self._spec_fn(
                        self.params,
                        self._cache,
                        self._dcache,
                        jnp.asarray(state),
                        bias_ids_d,
                        bias_vals_d,
                    )
                t_issued = now_ms()
                ready = packed.is_ready()
                with self._phase("rdb.engine.turn.fetch", seq=seq,
                                 ready=int(ready)):
                    fetch = (now_ms(), ready)
                    ph_host = np.asarray(packed)  # ONE fetch per round  # rdb-lint: disable=host-sync-in-hot-path (THE one fetch per spec round: ph_host carries tokens+counts+lengths packed)
            except BaseException:
                self._rollback_spec_scratch()
                raise
            t_fetched = now_ms()
            self._note_fetched(seq)
            with self._phase("rdb.engine.turn.harvest"):
                links = self._turn_links(self._active_mask)
                out = ph_host[: k + 1]        # [k+1, B]
                n_out = ph_host[k + 1]        # [B]
                lengths = ph_host[k + 2]      # [B]
                # Accepted prefixes commit by page-table splice, rejected
                # tails free — resolved from the post-round lengths BEFORE
                # the harvest can finish (and free) any slot.
                self._splice_spec_pages(lengths)
                self.steps += 1
                DECODE_STEPS.inc(tags={"model": self.model.name})
                tags = {"model": self.model.name, "paged": "true"}
                SPEC_ROUNDS.inc(tags=tags)
                live = np.asarray([
                    not slot.free and self._active_mask[i] and n_out[i] > 0
                    for i, slot in enumerate(self._slots)
                ])
                drafted = k * active
                accepted = int((n_out[live] - 1).sum()) if live.any() else 0
                # Conservation by construction, pinned in tier-1:
                # accepted + rejected == drafted, per round.
                if drafted:
                    SPEC_DRAFTED.inc(drafted, tags=tags)
                    SPEC_REJECTED.inc(drafted - accepted, tags=tags)
                    self._spec_acc_window.append((accepted, drafted))
                    rate = self.spec_acceptance()
                    if rate is not None:
                        SPEC_ACCEPTANCE.set(rate, tags=tags)
                if accepted:  # one summed increment, not one .inc() per slot
                    SPEC_ACCEPTED.inc(accepted, tags=tags)
                # Same harvest as the plain scan, with advanced =
                # (j < n_out): a short row is draft rejection, not cache
                # capacity.
                self._harvest(
                    out,
                    np.arange(k + 1)[:, None] < n_out[None, :],
                    lengths,
                    k + 1,
                    blocked_finishes_capacity=False,
                )
            rec = self._log_dispatch("turn", t_dispatch, t_issued, t_fetched,
                                     1, 0, active, len(self._trains),
                                     kv_pages_live=kv_pages_live,
                                     kv_rows=kv_rows, queued_behind=behind,
                                     fetch=fetch, seq=seq)
            if links is not None:
                self._record_turn_span(rec, links, k, spec=True)

    def _step(self, horizon: Optional[int] = None) -> None:
        if horizon is None and self._use_spec():
            return self._spec_step()
        with self._phase("rdb.engine.turn") as ph:
            self._plain_turn(ph, horizon)

    def _plain_turn(self, ph: Any, horizon: Optional[int]) -> None:
        """One plain decode scan inside the open ``rdb.engine.turn`` phase
        ``ph``, fetched at once: prepare, dispatch, fetch, harvest, and the
        ring's record."""
        self._complete_turn(ph, self._issue_turn(ph, horizon))

    def _issue_turn(self, ph: Any, horizon: Optional[int],
                    ahead_of: Optional[_IssuedTurn] = None) -> _IssuedTurn:
        """Prepare and dispatch one plain decode scan inside the open
        ``rdb.engine.turn`` phase ``ph``; nothing is fetched. Until
        :meth:`_complete_turn` the slots' host state (mask, tokens, lengths,
        tables, sampling arrays) must stand as dispatched: admission and a
        chunk group's DISPATCH touch none of it, a group's completion
        (``_register``) does.

        ``ahead_of``: the scan in flight, dispatched and NOT fetched, where
        :meth:`_horizon_ahead` allowed this one (``horizon``) to go out
        behind it. Every active row then reads its pending token from that
        scan's last substep on the device (``_decode_impl``'s ``carried``),
        and what the host would have learned from the fetch goes forward by
        arithmetic: the sample index and the mirror of the lengths by that
        scan's substeps (all of which advance, or this was not allowed; its
        harvest writes the same lengths). Any other scan is dispatched with
        nothing in flight."""
        if ahead_of is None:
            self._drain_issued()
        late = 0 if ahead_of is None else ahead_of.h
        with self._phase("rdb.engine.turn.prepare"):
            h = horizon if horizon is not None else self._pick_horizon()
            if late:
                self._len_host[self._active_mask] += late
            # Pages for every position this scan can write, allocated
            # host-side before the dispatch (static shapes can't grow
            # mid-scan), then one tiny [B, NP] table upload when dirty.
            self._ensure_page_headroom(h)
            self._refresh_table()
            # Per-slot index of the NEXT token to sample (prefill was
            # index 0).
            tok_idx = np.asarray(
                [len(s.generated) + late if not s.free else 0
                 for s in self._slots],
                dtype=np.int32,
            )
            prev_tokens = self._tokens.copy()  # draft catch-up window head
            active_at_dispatch = self._active_mask.copy()
            active = int(active_at_dispatch.sum())
            kv_pages_live = self._kv_pages_live()
            kv_rows = self._kv_rows()
            kv_full = self._kv_full_pages_live()
            # a latent model's layers all walk the whole prefix: the
            # pool rows its scans read are the live pages' (0: k/v pairs)
            kv_latent = (int(kv_pages_live) * self.page_size
                         * self._latent_layers)
            samp_f, samp_i, bias_ids_d, bias_vals_d = self._sampling_arrays()
            # ONE per-dispatch upload: tokens / active / sample index /
            # which rows read the carry (all the active ones, or none).
            carry = active_at_dispatch if late else np.zeros_like(
                active_at_dispatch)
            state = np.stack([
                self._tokens[:, 0],
                active_at_dispatch.astype(np.int32),
                tok_idx,
                carry.astype(np.int32),
            ])
        ph.set_metadata(horizon=h, active=active, spec=0, ahead=int(late > 0))
        seq, behind = self._note_issue()
        t_dispatch = now_ms()
        with self._phase("rdb.engine.turn.dispatch", seq=seq):
            (packed, self._cache, self._counts,
             self._carry) = self._decode_fn(
                self.params,
                self._cache,
                jnp.asarray(state),
                h,
                samp_f,
                samp_i,
                bias_ids_d,
                bias_vals_d,
                self._counts,
                self._carry,
            )
        return _IssuedTurn(packed, seq, h, t_dispatch, now_ms(), behind,
                           active_at_dispatch, prev_tokens,
                           len(self._trains), kv_pages_live, kv_rows,
                           kv_full, kv_latent,
                           tuple(s.request for s in self._slots), late > 0)

    def _complete_turn(self, ph: Any, issued: _IssuedTurn) -> None:
        """Fetch and harvest an issued scan inside the open
        ``rdb.engine.turn`` phase ``ph``, and write the ring's record. The
        harvest goes by the IDENTITY the scan was dispatched with, the mask
        and each slot's tenant then: a slot registered since took no part in
        it, and where the scan was issued ahead (``issued.ahead``) a tenant
        that ended in the scan in front of it (an EOS nobody knew of) had
        its rows decoded for nothing, past its length in pages that were
        its own: discarded and counted (``Turn.wasted_substeps``), whether
        the slot is free, held by a train or another request's by now."""
        h, active_at_dispatch = issued.h, issued.active_at_dispatch
        active = int(active_at_dispatch.sum())
        ph.set_metadata(horizon=h, active=active, spec=0)
        ready = issued.packed.is_ready()
        self._note_ready(ready)
        with self._phase("rdb.engine.turn.fetch", seq=issued.seq,
                         ready=int(ready)):
            fetch = (now_ms(), ready)
            packed_host = np.asarray(issued.packed)   # ONE fetch per dispatch  # rdb-lint: disable=host-sync-in-hot-path (THE one fetch per dispatch: packed carries tokens+advanced+lengths)
        t_fetched = now_ms()
        self._note_fetched(issued.seq)
        with self._phase("rdb.engine.turn.harvest"):
            if issued.requests:
                active_at_dispatch = active_at_dispatch & np.fromiter(
                    (s.request is r
                     for s, r in zip(self._slots, issued.requests)),
                    bool, len(self._slots))
            wasted = (active - int(active_at_dispatch.sum())) * h
            links = self._turn_links(active_at_dispatch)
            toks_host = packed_host[:h]               # [h, B]
            advanced_host = packed_host[h : 2 * h].astype(bool)   # [h, B]
            lengths_host = packed_host[2 * h]         # [B] (post-horizon)
            self.steps += h
            DECODE_STEPS.inc(h, tags={"model": self.model.name})
            if self._dcache is not None:
                # Keep the DRAFT cache tracking the sequence through plain
                # decode intervals (sampled-row fallback, inter-chunk
                # steps): without this, speculation resumes from a stale
                # draft context and acceptance collapses. The tokens whose
                # k/v landed at positions [len, len+h) are
                # [pending, emitted[:-1]].
                window = np.concatenate(
                    [issued.prev_tokens, toks_host[: h - 1].T], axis=1
                )  # [B, h]
                counts = advanced_host.sum(axis=0).astype(np.int32)
                self._dcache = self._draft_catchup_fn(
                    self.draft_params,
                    self._dcache,
                    jnp.asarray(window),
                    jnp.asarray(active_at_dispatch),
                    jnp.asarray(counts),
                )
            self._harvest(toks_host, advanced_host, lengths_host, h,
                          active_mask=active_at_dispatch)
        rec = self._log_dispatch(
            "turn", issued.t_dispatch, issued.t_issued, t_fetched, h, 0,
            active, issued.trains,
            packed_host[2 * h + 1:, 0] if self._moe_kw else (0, 0, 0, 0),
            kv_pages_live=issued.kv_pages_live, kv_rows=issued.kv_rows,
            queued_behind=issued.queued_behind,
            kv_full_pages_live=issued.kv_full_pages_live,
            kv_latent_rows=issued.kv_latent_rows,
            fetch=fetch, seq=issued.seq, ahead=(issued.ahead, wasted))
        if links is not None:
            self._record_turn_span(rec, links, h)

    def _harvest(self, toks_host, advanced_host, lengths_host, h: int,
                 blocked_finishes_capacity: bool = True,
                 active_mask: Optional[np.ndarray] = None) -> None:
        """Distribute a scan's [h, B] outputs to their slots: those of
        ``active_mask``, the mask the scan was dispatched with (default:
        the live one — nothing registered since the dispatch).

        Vectorized: at 64 slots x a 32-substep horizon the former
        per-token Python loop executed ~2k interpreter iterations per
        dispatch — pure host overhead on a chip whose dispatch cadence is
        a few ms. Here numpy computes, per slot, how many tokens to
        accept and which finish fires, with the SAME semantics as the
        scalar loop it replaced: a non-advanced substep finishes
        "capacity" (cache was full at entry, no token), a stop token is
        accepted then finishes "eos", the max_new bound accepts its last
        token then finishes "length" — and at equal accepted counts the
        scalar loop's check order makes eos beat length beat capacity.
        Tokens append in bulk; only requests that actually stream pay a
        per-token push. Substeps after EOS/stop decoded garbage into the
        slot's cache tail; prefill overwrites the row on reuse.

        ``blocked_finishes_capacity``: in a plain scan a non-advanced
        substep means the cache was full — finish "capacity". The
        speculative path reuses this harvest with advanced = (j < n_out),
        where a short row means DRAFT REJECTION, not capacity: there only
        n_out == 0 (no room for even the target's own token) finishes,
        plus the shared trailing max_len check.
        """
        if active_mask is None:
            active_mask = self._active_mask
        active_idx = [
            i for i, slot in enumerate(self._slots)
            if not slot.free and active_mask[i]
        ]
        if not active_idx:
            return
        cols = np.asarray(active_idx, dtype=np.int64)  # rdb-lint: disable=host-sync-in-hot-path (host-built python index list, no device value)
        # Host mirror of cache lengths (page-headroom math + the
        # kv_occupancy metric); finished slots re-zero in _finish.
        self._len_host[cols] = lengths_host[cols]
        toks = toks_host[:, cols]          # [h, n]
        adv = advanced_host[:, cols]       # [h, n]
        # First non-advanced substep (h if every substep advanced).
        blocked = ~adv
        cap_at = np.where(
            blocked.any(axis=0), blocked.argmax(axis=0), h
        )
        # First stop token: the shared EOS id vectorized; per-request
        # extra stop ids (rare) OR-ed in per column.
        if self.eos_token_id is not None:
            stop_mask = toks == self.eos_token_id
        else:
            stop_mask = np.zeros_like(adv)
        for c, i in enumerate(active_idx):
            extra = self._slots[i].stop
            if extra:
                stop_mask[:, c] |= np.isin(
                    toks[:, c], np.fromiter(extra, dtype=np.int64)
                )
        stop_take = np.where(
            stop_mask.any(axis=0), stop_mask.argmax(axis=0) + 1, h + 1
        )
        len_take = np.asarray([
            max(0, self._slots[i].max_new_tokens
                - len(self._slots[i].generated))
            for i in active_idx
        ])
        accepted = np.minimum.reduce([
            cap_at, stop_take, len_take, np.full_like(cap_at, h),
        ])
        for c, i in enumerate(active_idx):
            slot = self._slots[i]
            acc = int(accepted[c])
            if acc > 0:
                new_toks = toks[:acc, c].tolist()
                slot.generated.extend(new_toks)
                slot.last_token = new_toks[-1]
                self._tokens[i, 0] = new_toks[-1]
                if slot.request.stream is not None:
                    for tok in new_toks:
                        slot.request.stream_put(tok)
            if stop_take[c] == accepted[c]:
                self._finish(i, "eos")
            elif len_take[c] == accepted[c]:
                self._finish(i, "length")
            elif cap_at[c] == accepted[c] and (
                cap_at[c] < h if blocked_finishes_capacity
                else cap_at[c] == 0
            ):
                # A genuinely blocked substep (cache full at entry) —
                # cap_at == h just means every substep advanced.
                self._finish(i, "capacity")
            elif lengths_host[i] >= self.max_len:
                self._finish(i, "capacity")

    # --- page fabric (live stream migration + prefix push) -----------------
    def live_stream_ids(self) -> List[str]:
        """Request ids of migration-eligible streams: slotted, past
        their first token, not mid-chunked-prefill (trains hold no
        emitted tokens yet, so they are requeue-safe under the
        at-most-once-after-first-token pin and drain the old way).
        Benign-racy read for planners; eligibility is re-checked on the
        engine thread at service time."""
        out: List[str] = []
        for i, s in enumerate(self._slots):
            if s.free or s.request is None or i in self._train_slots:
                continue
            if s.generated:
                out.append(s.request.request_id)
        return out

    def request_migration(
        self, request_id: str,
        deliver: Callable[[PageParcel], bool],
    ) -> bool:
        """Thread-safe: ask this engine to migrate ``request_id`` out
        through ``deliver`` at its next between-turns service point.
        ``deliver`` is invoked ON the engine thread with the frozen
        parcel and must return True only once the destination accepted
        it; the slot is committed (freed without fulfil) on True and
        left decoding untouched on False/raise. Returns False if the
        stream is not live here (advisory — a stream that finishes
        before service is simply skipped, duplicates are harmless)."""
        refuse_unsupported(self.model.cfg, self.model.name, parcel=True)
        live = any(
            (not s.free) and s.request is not None
            and s.request.request_id == request_id
            for s in self._slots
        )
        if not live:
            return False
        with self._fabric_lock:
            self._migrate_out_q.append((request_id, deliver))
        return True

    def request_prefix_push(
        self, key: bytes, deliver: Callable[[PageParcel], bool],
    ) -> bool:
        """Thread-safe: export prefix-cache entry ``key`` as a push
        parcel through ``deliver`` at the next service point (skipped
        if evicted by then)."""
        if self.paged_prefix is None:
            return False
        with self._fabric_lock:
            self._push_out_q.append((key, deliver))
        return True

    def accept_parcel(self, parcel: PageParcel) -> bool:
        """Thread-safe destination half of the courier edge: admission-
        check ``parcel`` and enqueue it for import on the engine thread.
        The checks are ADVISORY (the free-pages read races the engine
        thread benignly); the import path keeps its own OOM fallback
        chain (reclaim cache pins -> capacity-truncate), so a stale
        accept is honest, never corrupting. A False return leaves the
        source slot untouched — it simply resumes decoding."""
        refuse_unsupported(self.model.cfg, self.model.name, parcel=True)
        if parcel.page_size != self.page_size:
            return False
        if parcel.kind == STREAM:
            if parcel.resume_len > self.max_len:
                return False
            s = parcel.sampling
            if (float(s.get("temperature", 0.0)) > 0.0
                    and int(s.get("base_seed", -1)) != self.base_seed):
                # Sampled rows only resume byte-identically under the
                # same engine-level PRNG base key; greedy rows never
                # consult it.
                return False
            with self._fabric_lock:
                pending = [p for p in self._parcel_in_q
                           if p.kind == STREAM]
                free_slots = sum(
                    1 for i, sl in enumerate(self._slots)
                    if sl.free and i not in self._train_slots
                )
                if len(pending) + 1 > free_slots:
                    return False
                pend_pages = sum(p.n_pages for p in pending)
                if not self._allocator.can_alloc(
                        pend_pages + parcel.n_pages):
                    return False
                self._parcel_in_q.append(parcel)
            return True
        # Prefix pushes are speculative: admission only rejects the
        # impossible (bigger than the pool); a tight pool skips the
        # install at import time rather than deepening pressure.
        if parcel.n_pages > self.num_pages:
            return False
        with self._fabric_lock:
            self._parcel_in_q.append(parcel)
        return True

    def _fabric_pending(self) -> bool:
        with self._fabric_lock:
            return bool(self._parcel_in_q or self._migrate_out_q
                        or self._push_out_q)

    def _service_fabric(self) -> None:
        """Engine thread, between decode turns: drain the parcel
        mailboxes and process them. Pops under the rank-100 fabric lock
        into locals FIRST, then processes unlocked — the handlers call
        into queue accounting (rank 80) and request futures (rank 90),
        which must never nest under rank 100."""
        with self._phase("rdb.engine.fabric"):
            with self._fabric_lock:
                if not (self._parcel_in_q or self._migrate_out_q
                        or self._push_out_q):
                    return
                inbound, self._parcel_in_q = self._parcel_in_q, []
                moves, self._migrate_out_q = self._migrate_out_q, []
                pushes, self._push_out_q = self._push_out_q, []
            # a parcel reads or writes pages: nothing may be in flight
            self._drain_issued()
            for parcel in inbound:
                self._import_parcel(parcel)
            for rid, deliver in moves:
                self._migrate_stream_out(rid, deliver)
            for key, deliver in pushes:
                self._push_prefix_out(key, deliver)

    def _migrate_stream_out(
        self, request_id: str,
        deliver: Callable[[PageParcel], bool],
    ) -> None:
        """Freeze -> deliver -> commit. The export is read-only and the
        slot is torn down only AFTER the courier acknowledged delivery,
        so every failure mode (courier death, partition mid-parcel,
        destination refusal) leaves the stream decoding here as if the
        directive never arrived."""
        idx = None
        for i, s in enumerate(self._slots):
            if (not s.free and s.request is not None
                    and s.request.request_id == request_id
                    and i not in self._train_slots and s.generated):
                idx = i
                break
        if idx is None:
            return  # finished/moved since requested — nothing to do
        slot = self._slots[idx]
        req = slot.request
        parcel = export_stream_parcel(self, idx)
        ok = False
        try:
            ok = bool(deliver(parcel))
        except Exception:  # noqa: BLE001 — courier faults must not kill the stream
            logger.exception(
                "%s: migrate_out delivery failed for %s",
                self.model.name, request_id,
            )
        if not ok:
            return
        # Commit: the destination owns the stream now. Mirror _finish's
        # slot/sampling reset WITHOUT fulfil or completion accounting —
        # the same TokenStream keeps flowing from the new engine, and
        # note_migrated_out closes this queue's books instead.
        self._page_journal.record(
            "migrate_out", parcel.n_pages,
            self._allocator.allocated_pages,
            slot=int(idx), bytes=parcel.nbytes, request=request_id,
        )
        self.queue.note_migrated_out(req)
        self._free_slot_pages(idx)
        self._slots[idx] = _Slot()
        self._active_mask[idx] = False
        self._temps[idx] = 0.0
        self._topk[idx] = 0
        self._topp[idx] = 1.0
        self._seeds[idx] = 0
        self._bias_ids[idx] = 0
        self._bias_vals[idx] = 0.0
        self._pres[idx] = 0.0
        self._freq[idx] = 0.0
        self.migrated_out += 1

    def _import_parcel(self, parcel: PageParcel) -> None:
        if parcel.kind == PREFIX:
            self._install_prefix(parcel)
            return
        idx = None
        for i, s in enumerate(self._slots):
            if s.free and i not in self._train_slots:
                idx = i
                break
        need = parcel.n_pages
        if idx is not None:
            while not self._allocator.can_alloc(need):
                # Accepted capacity evaporated (admissions raced the
                # courier): cache pins are optimizations, inbound live
                # streams are not.
                if not self._reclaim_cache_pins():
                    break
        if idx is None or not self._allocator.can_alloc(need):
            # OOM-after-accept last resort: a complete-but-truncated
            # result — the same honest contract as cache exhaustion.
            self._fulfill_truncated(parcel)
            return
        pages = self._allocator.alloc(need) if need else []
        if parcel.payload:
            self._write_pages(pages, parcel.payload)
        self._register_migrated(idx, parcel, pages)
        self._page_journal.record(
            "migrate_in", need, self._allocator.allocated_pages,
            slot=int(idx), bytes=parcel.nbytes,
            request=parcel.request.request_id,
        )
        self.queue.note_migrated_in(parcel.request)
        self.migrated_in += 1

    def _register_migrated(
        self, slot_idx: int, parcel: PageParcel, pages: List[int],
    ) -> None:
        """Splice an imported stream into ``slot_idx`` and resume it.
        The _register variant for a stream that already emitted tokens:
        no stream_put / TTFT / prefill accounting (all happened at the
        source), device ``lengths`` set explicitly (normally the
        prefill program's job), penalty counts reconstructed from the
        generated list (the counts row of a live slot equals
        ``bincount(generated)`` — the scan counts only tokens it
        sampled plus the register-counted first token, and a live slot
        kept every one of them)."""
        slot = self._slots[slot_idx]
        slot.request = parcel.request
        slot.generated = list(parcel.generated)
        slot.max_new_tokens = parcel.max_new_tokens
        slot.prefill_done_ms = parcel.prefill_done_ms
        slot.last_token = int(parcel.generated[-1])
        slot.stop = parcel.stop
        slot.session_id = parcel.session_id
        slot.prompt_tokens = parcel.prompt_tokens
        slot.pages = list(pages)
        slot.shared_pages = 0
        self._len_host[slot_idx] = int(parcel.cache_len)
        self._table_host[slot_idx] = table_array(
            slot.pages, self._n_table_entries, self.num_pages
        )
        self._table_dirty = True
        self._tokens[slot_idx, 0] = slot.last_token
        self._active_mask[slot_idx] = True
        s = parcel.sampling
        self._temps[slot_idx] = float(s.get("temperature", 0.0))
        self._topk[slot_idx] = int(s.get("top_k", 0))
        self._topp[slot_idx] = float(s.get("top_p", 1.0))
        self._seeds[slot_idx] = int(s.get("seed", 0))
        self._bias_ids[slot_idx] = np.asarray(s["bias_ids"]) \
            if "bias_ids" in s else 0
        self._bias_vals[slot_idx] = np.asarray(s["bias_vals"]) \
            if "bias_vals" in s else 0.0
        self._pres[slot_idx] = float(s.get("presence_penalty", 0.0))
        self._freq[slot_idx] = float(s.get("frequency_penalty", 0.0))
        self._sampling_dev = None  # host arrays changed
        with self._device_ctx():
            self._cache = self._cache.replace(
                lengths=self._cache.lengths.at[slot_idx].set(
                    int(parcel.cache_len)
                )
            )
            if self._pres[slot_idx] or self._freq[slot_idx]:
                vocab = int(self._counts.shape[1])
                row = np.bincount(
                    np.asarray(parcel.generated, np.int64) % vocab,
                    minlength=vocab,
                )[:vocab].astype(np.int32)
                self._counts = self._counts.at[slot_idx].set(
                    jnp.asarray(row)
                )

    def _fulfill_truncated(self, parcel: PageParcel) -> None:
        """Destination-OOM fallback after accept: resolve the stream as
        complete-but-truncated instead of stranding it (the source
        already committed the hand-off and cannot take it back)."""
        req = parcel.request
        t = now_ms()
        self.queue.note_migrated_in(req)
        req.fulfill(DecodeResult(
            tokens=list(parcel.generated),
            finish_reason="capacity",
            ttft_ms=parcel.prefill_done_ms - req.arrival_ms,
            total_ms=t - req.arrival_ms,
        ))
        self.queue.record_batch_completion([req], completed_at_ms=t)
        self.completed += 1
        logger.warning(
            "%s: migrated-in stream %s capacity-truncated "
            "(destination OOM after accept)",
            self.model.name, req.request_id,
        )

    def _push_prefix_out(
        self, key: bytes, deliver: Callable[[PageParcel], bool],
    ) -> None:
        parcel = export_prefix_parcel(self, key)
        if parcel is None:
            return  # evicted between planning and export
        ok = False
        try:
            ok = bool(deliver(parcel))
        except Exception:  # noqa: BLE001 — a failed push costs nothing
            logger.exception(
                "%s: prefix push delivery failed", self.model.name
            )
        if not ok:
            return
        self._page_journal.record(
            "push_out", parcel.n_pages,
            self._allocator.allocated_pages, bytes=parcel.nbytes,
        )
        self.pushes_out += 1

    def _install_prefix(self, parcel: PageParcel) -> None:
        """Install a pushed prefix parcel digest-direct: alloc, write,
        publish under the parcel's chain address. Skips duplicates and
        tight pools (a speculative warm must never evict local state to
        make room for itself)."""
        cache = self.paged_prefix
        if cache is None or not parcel.digest:
            return
        if parcel.digest in cache._entries:
            return
        need = parcel.n_pages
        if not self._allocator.can_alloc(need):
            return
        pages = self._allocator.alloc(need)
        self._write_pages(pages, parcel.payload)
        if cache.install(parcel.digest, pages):
            self._page_journal.record(
                "push_in", need, self._allocator.allocated_pages,
                bytes=parcel.nbytes,
            )
            self.pushes_in += 1
        # Pin symmetry: install increfs for the cache; drop the alloc's
        # own hold (pages free immediately on the losing race branch).
        self._allocator.decref(pages)

    # --- loop --------------------------------------------------------------
    def run_until_idle(self, timeout_s: float = 60.0) -> None:
        """Drive admissions + steps until queue and slots are empty (tests,
        offline batch generation): the serving loop's own iteration."""
        deadline = time.monotonic() + timeout_s
        with self._device_ctx():
            while time.monotonic() < deadline:
                admitted, turned = self._iterate()
                if (not turned and not admitted and not self._trains
                        and len(self.queue) == 0
                        and not self._fabric_pending()):
                    return
            self._drain_issued()
        raise TimeoutError(f"{self.model.name}: decode did not drain")

    def _iterate(self) -> Tuple[int, bool]:
        """ONE iteration of the engine (``_loop`` and ``run_until_idle``
        both run it): fabric, admission, the prefill budget, then a decode
        scan that is FETCHED LAST. Between the scan's dispatch and its fetch
        run what does not need its result — admission, and the dispatch of
        the next chunk group — so the device holds the next program while
        the host harvests this one, and the host prepares behind a running
        program. A turn has ONE prefill budget, spent at the earliest point
        a train is pending: what the section behind the scan spent, the pump
        before the next scan does not spend again (never two budgets between
        two scans).

        Where nothing waits to join the batch (:meth:`_horizon_ahead`) the
        NEXT scan is what runs behind this one: scan N+1 is dispatched
        before N is fetched, its pending tokens read from N's last substep
        on the device; N is fetched and harvested while N+1 runs, and N+1
        STAYS IN FLIGHT when the iteration ends: the next iteration starts
        from it (admission and the prefill pump behind it, N+2 issued or
        not, N+1 fetched). So the device goes from scan to
        scan without waiting for the host's harvest, preparation and launch.
        At most ONE scan is ever dispatched ahead of an unfetched one, and a
        chunk group never stands behind two. A request that arrives while a
        scan is ahead has its chunk behind that scan (up to ``ttft_horizon``
        substeps later than on an empty device) and registers before the
        next scan is issued.

        At most one scan is in flight between iterations, and nothing else:
        whatever reads or frees the pool from the host, or ends the loop,
        completes it first (:meth:`_drain_issued`: the fabric, a dispatch
        that may reclaim or evict, ``_pump_prefill`` by hand,
        ``_drain_prefill``, ``run_until_idle``'s timeout, the loop's end,
        ``stop``, ``abort_active``, ``release_buffers``).
        Returns (requests admitted before the scan, whether a scan ran or
        is in flight)."""
        admitted = 0
        if self._issued_turn is None:
            self._service_fabric()
            admitted = self._admit()
            left = self.prefill_token_budget - self._prefill_spent
            self._prefill_spent = 0
            if left > 0:
                self._pump_prefill(left)
            if not self._active_mask.any():
                return admitted, False
            if self._dcache is not None:
                # A draft model's rounds and catch-up keep their order: each
                # scan fetched at once.
                self._step()
                self._publish_gauges()
                return admitted, True
            with self._phase("rdb.engine.turn") as ph:
                self._issued_turn = self._issue_turn(ph, None)
        # Behind the scan in flight (this iteration's, or the one the last
        # iteration issued ahead): whoever arrived since joins HERE.
        ahead = None
        try:
            self._admit()
            left = self.prefill_token_budget - self._prefill_spent
            if left > 0:
                self._prefill_spent += self._pump_prefill(
                    left, behind_turn=True)
            h = self._horizon_ahead()
            if h:
                with self._phase("rdb.engine.turn") as ph:
                    ahead = self._issue_turn(
                        ph, h, ahead_of=self._issued_turn)
        finally:
            self._complete_issued(ahead)
        self._publish_gauges()
        return admitted, True

    def _horizon_ahead(self) -> int:
        """The horizon of a scan that may be dispatched now, AHEAD of the
        unfetched scan in flight, or 0: today's order. From what the engine
        can observe at this moment, all of:

        - nothing waits to join the batch — no chunk train, no queued
          request, nothing in the fabric's mailboxes — so no request's first
          token stands behind a scan that need not have been committed;
        - no draft model (its rounds and catch-up keep their order);
        - no active slot is CERTAIN to end inside the scan in flight, by its
          length bound or the cache's end, both known before the fetch: its
          slot should be refilled at once, and its rows would be wasted
          (an EOS or a stop id cannot be known: ``Turn.wasted_substeps``);
        - the pages for both scans' positions (the host's lengths are a scan
          late: the upper bound) are on the free list: nothing is reclaimed
          or evicted with a scan in flight;
        - the DEVICE is the pace (:meth:`_note_ready`): under
          ``AHEAD_READY_MAX`` of the recent scan fetches found their result
          ready. Above it the host comes late to its fetches: there is no
          wait for a committed scan to hide, and it costs a newcomer its
          place."""
        cur = self._issued_turn
        if (cur is None or self._ready_share > AHEAD_READY_MAX
                or self._dcache is not None or self._trains
                or self._issued_groups or len(self.queue)
                or self._fabric_pending()):
            return 0
        idx = np.flatnonzero(self._active_mask)
        if idx.size == 0:
            return 0
        h = self._pick_horizon()
        grow = 0
        for i in idx:
            slot = self._slots[i]
            if (slot.max_new_tokens - len(slot.generated) <= cur.h
                    or int(self._len_host[i]) + cur.h >= self.max_len):
                return 0
            grow += max(0, self._pages_short(i, cur.h + h))
        if grow and not self._allocator.can_alloc(grow):
            return 0
        return h

    def _note_ready(self, ready: bool) -> None:
        """A decode scan is about to be fetched: ``ready``, its result had
        reached the host's side before the host came for it, so the device
        had finished and was waiting. Kept as a moving average over about
        ``_READY_TURNS`` fetches (``_ready_share``), in either order of the
        loop. Where it stands above ``AHEAD_READY_MAX`` the HOST is the pace
        (several engine threads under one interpreter lock): a scan issued
        ahead hides no wait, since there is none, and it costs a newcomer
        its place (its chunk stands behind a committed scan, and the loop
        admits once a tile where today's order admits twice), so
        :meth:`_horizon_ahead` keeps today's order until the share falls
        again. A fetch that follows its scan's dispatch at once (today's
        order) finds the result ready only where the host took longer than
        the scan over the launch and what it dispatched behind it; a fetch
        a scan late finds it ready unless the device is the pace."""
        self._ready_share += (float(ready) - self._ready_share) / _READY_TURNS

    def _drain_issued(self) -> None:
        """Complete what is in flight (the scan issued ahead, the groups
        behind it), where the next step reads or frees the pool from the
        host or must find every token harvested."""
        if self._issued_turn is not None or self._issued_groups:
            self._complete_issued()

    def _complete_issued(self, ahead: Optional[_IssuedTurn] = None) -> None:
        """Fetch and harvest the issued scan, then complete the chunk
        groups dispatched behind it, in their order (first tokens,
        ``_register``, the ring's records after the scan's). ``ahead``: the
        scan dispatched behind it (:meth:`_horizon_ahead`; there are no
        groups then), which is what is in flight from here on."""
        issued, self._issued_turn = self._issued_turn, ahead
        groups, self._issued_groups = self._issued_groups, []
        try:
            if issued is not None:
                with self._phase("rdb.engine.turn") as ph:
                    self._complete_turn(ph, issued)
        finally:
            if groups:
                with self._phase("rdb.engine.prefill",
                                 trains=len(self._trains), tokens=0):
                    for group in groups:
                        try:
                            self._complete_chunk_group(group)
                        except Exception as e:  # noqa: BLE001 — no-dangle rule
                            logger.exception(
                                "%s: chunk completion failed",
                                self.model.name)
                            for t in group.trains:
                                self._drop_train(t, e)

    def _publish_gauges(self) -> None:
        """The gauge writes after a turn."""
        with self._phase("rdb.engine.publish"):
            tags = {"model": self.model.name}
            ACTIVE_SLOTS.set(float(self._active_mask.sum()), tags=tags)
            KV_PAGES_FREE.set(float(self._allocator.free_pages), tags=tags)
            KV_PAGE_OCCUPANCY.set(
                self._allocator.allocated_pages / self.num_pages,
                tags=tags,
            )

    def _loop(self) -> None:
        with self._device_ctx():
            while self._run.is_set():
                try:
                    _admitted, turned = self._iterate()
                    if not turned and not self._trains:
                        t_idle = now_ms()
                        with self._phase("rdb.engine.idle_wait"):
                            self.queue.wait_for_requests(self.idle_wait_s)
                        self._idle_ms += now_ms() - t_idle
                        self._idled = True
                    self.last_heartbeat = time.monotonic()
                except Exception:  # noqa: BLE001 — engine must not die silently
                    logger.exception(
                        "%s: decode loop iteration failed", self.model.name
                    )
                    time.sleep(0.05)  # rdb-lint: disable=event-loop-blocking (decode-loop error backoff on the engine's own thread)
            try:
                self._drain_issued()     # the scan issued ahead: its tokens
            except Exception:  # noqa: BLE001 — the thread ends either way
                logger.exception(
                    "%s: the scan in flight at the loop's end was lost",
                    self.model.name)

    def release_buffers(self) -> None:
        """Drop the engine's HBM footprint (cache + params + compiled fns)
        so a replacement replica can reuse the chip. Call only after the
        loop has stopped; the engine is unusable afterwards."""
        self._drain_stopped()
        self._cache = None
        self.params = None
        self._draft_fill_fns.clear()
        self._decode_fn = None
        self._chunk_paged_fn = None
        self._counts = None
        self._zero_counts_fn = None
        self._sampling_dev = None
        self._dcache = None
        if self.draft_model is not None:
            self.draft_params = None
            self._spec_fn = None
            self._draft_catchup_fn = None
        # Drop cache pins first (clean decrefs), then the pool state.
        if self.paged_prefix is not None:
            self.paged_prefix.clear()
        if self.paged_sessions is not None:
            self.paged_sessions.clear()
        if self.host_spill is not None:
            self.host_spill.clear()  # host copies die with the pool
        self._allocator = None
        self._table_host = None

    def abort_active(self, exc: Exception) -> None:
        """Reject every request still occupying a slot (replica shutdown:
        in-flight sequences must not leave futures/streams hanging). Call
        only after the loop has stopped."""
        self._drain_stopped()
        for i, slot in enumerate(self._slots):
            if not slot.free and slot.request is not None:
                slot.request.reject(exc)
                if self._allocator is not None:
                    self._free_slot_pages(i)
                self._slots[i] = _Slot()
                self._active_mask[i] = False
        # Chunk trains are in-flight requests too (slot held, pages
        # granted, no tokens yet): reject + release, never strand.
        for train in list(self._trains):
            train.req.reject(exc)
            if self._allocator is not None:
                self._release_pages(train.opts)
        self._trains.clear()
        self._train_slots.clear()
        # Accepted-but-unimported inbound parcels hold live streams the
        # SOURCE already released (note_migrated_out closed its books);
        # reject them too — they entered no books here, so conservation
        # holds on both sides.
        with self._fabric_lock:
            inbound, self._parcel_in_q = self._parcel_in_q, []
            self._migrate_out_q.clear()
            self._push_out_q.clear()
        for parcel in inbound:
            if parcel.kind == STREAM and parcel.request is not None:
                parcel.request.reject(exc)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._run.set()
        # The stall clock starts when the loop does, not at construction:
        # a full-size warmup outlasts the health check's stall timeout
        # (200 s of compiles for gpt2_medium on a v5e vs 60 s), and a
        # heartbeat stamped in __init__ made the controller replace
        # every such replica the moment it started serving.
        self.last_heartbeat = time.monotonic()
        self._thread = threading.Thread(
            target=self._loop, name=f"decode-{self.model.name}", daemon=True
        )
        self._thread.start()

    def stop(self, timeout_s: float = 5.0) -> None:
        self._run.clear()
        if self._thread is not None:
            self._thread.join(timeout_s)
            if self._thread.is_alive():
                # Wedged in a device call: leave the handle so callers can
                # see the thread still lives (buffer release must not happen
                # under it).
                logger.warning(
                    "%s: loop thread did not exit within %.1fs",
                    self.model.name, timeout_s,
                )
            else:
                self._thread = None
        self._drain_stopped()

    def _drain_stopped(self) -> None:
        """:meth:`_drain_issued` for the entry points that follow the loop's
        end (which drains itself; an engine driven by hand has no loop).
        Not under a loop thread that never came back: a wedged device would
        take this thread too."""
        if self._thread is None and self._cache is not None:
            with self._device_ctx():
                self._drain_issued()

    def kv_occupancy(self) -> float:
        """Useful fraction of RESERVED KV positions — the decode
        slot-occupancy metric the paged pool exists to raise: only
        allocated pages are reserved, so the value follows cached tokens
        and not ``num_slots * max_len``. 1.0 when nothing is reserved."""
        used = float(self._len_host.sum())
        reserved = float(
            self._allocator.allocated_pages * self.page_size
        ) if self._allocator is not None else 0.0
        return used / reserved if reserved > 0 else 1.0

    def _expert_paths(self) -> List[str]:
        """Which path each program's expert layers took (``ops/moe.py``'s
        trace-time record of the process: engines of one process trace
        the same programs by the same rule); empty for a dense model."""
        if not self._moe_kw:
            return []
        from ray_dynamic_batching_tpu.ops.moe import moe_paths

        return sorted({f"{p.program}: {p.rows} rows -> {p.describe()}"
                       for p in moe_paths() if p.program})

    def _decode_paths(self) -> List[str]:
        """Which body each program's paged-kernel calls took
        (``ops/decode_attention.py::decode_paths``: flat heads or per
        head, and why; the process's trace-time record, as above)."""
        from ray_dynamic_batching_tpu.ops.decode_attention import (
            decode_paths,
        )

        return sorted({f"{p.program}: {p.describe()}"
                       for p in decode_paths() if p.program})

    def turn_summary(self, records: Optional[Sequence[Turn]] = None,
                     span_ms: Optional[float] = None,
                     longest: int = 8) -> Dict[str, Any]:
        """:func:`summarize_turns` of the ring, or of ``records`` (a slice
        of it) over ``span_ms``: what ``snapshot()["turns"]`` shows an
        operator and what the benchmark's engine metrics are read from."""
        # deque.copy() is one call under the GIL: the engine thread may
        # append while an operator's thread reads.
        return summarize_turns(
            list(self.turns.copy()) if records is None else records,
            self.num_slots, self.turns_dropped, span_ms, longest,
            self._table_walked,
            self._n_table_entries if self._ring_pages else 0,
        )

    def startup_summary(self) -> Dict[str, Any]:
        """This engine's start, from the process's start-up log
        (``Tracer.startup_spans``): its own spans (``replica`` is its
        phase tag), the replica's and the deploy's above them and the
        deploy's register as ``rows`` (:func:`startup_rows`), and
        :func:`startup_sums` of them. Under a deploy of several replicas
        the others' time is in this engine's ``unaccounted_s``. Empty of
        rows once the bounded log has turned over."""
        log = {sp.span_id: sp for sp in _tracer().startup_spans()}
        mine = {i: sp for i, sp in log.items()
                if sp.attributes.get("replica") == self._phase_tag}
        for sp in list(mine.values()):
            while sp.parent_id in log and sp.parent_id not in mine:
                sp = log[sp.parent_id]
                mine[sp.span_id] = sp
        # ... and what stands beside them for all replicas (the register).
        mine.update((i, sp) for i, sp in log.items()
                    if sp.parent_id in mine
                    and "replica" not in sp.attributes)
        rows = startup_rows(list(mine.values()))
        return dict(startup_sums(rows), rows=rows)

    def snapshot(self) -> Dict[str, Any]:
        """Operator-facing state dump (the engine analogue of
        ``LiveScheduler.snapshot()``): slot/KV occupancy plus the
        allocator event journal (bounded ring; ``events``
        carries the retained tail, ``journal_total``/``journal_rotated``
        say how much history the ring has seen/shed, so a consumer can
        tell a quiet pool from a ring that wrapped). The journal feeds
        ``utils/trace_export.to_chrome_trace(spans, journal=...)`` for a
        Perfetto lane time-aligned with decode-turn spans. ``paged`` and
        ``prefill.mode`` have one value each: kept for the dashboards
        that read them."""
        turns = self.turn_summary()
        select = {}
        if self._index_topk:
            # a selecting model: what the indexer keeps, and how each
            # program's selecting layers were read
            from ray_dynamic_batching_tpu.ops.sparse_attention import (
                sparse_forms,
            )

            select = {
                "index_topk": self._index_topk,
                "select_layers": self._select_layers,
                "selected_row_share": turns.get("kv_selected_row_share"),
                "sparse_forms": sparse_forms()}
            if self._latent_layers:
                # a selection over a LATENT pool, a decode substep (means
                # over the ring's scans): the rows a query could attend,
                # those its indexer keeps, the pool rows the mask form
                # walks (every live page's), and the index keys scored
                # (every column of every slot's table: the view is
                # gathered whole)
                substeps = max(1, sum(
                    t.substeps for t in self.turns.copy() if t.kind == "turn"))
                select["rows_a_substep"] = {
                    "live": turns.get("kv_rows_live", 0) / substeps,
                    "selected": turns.get("kv_rows_selected", 0) / substeps,
                    "walked": turns.get("kv_latent_rows", 0) / substeps,
                    "index_scored": (self.num_slots * self._paged_capacity
                                     * self._select_layers)}
        out: Dict[str, Any] = {
            "model": self.model.name,
            "paged": True,
            "num_slots": self.num_slots,
            "active_slots": self.active_slots,
            "kv_occupancy": self.kv_occupancy(),
            "ttft": self.ttft_breakdown(),
            "turns": turns,
            "prefill": {
                "mode": "chunked",
                "token_budget": self.prefill_token_budget,
                "pending_trains": len(self._trains),
            },
            "page_size": self.page_size,
            "num_pages": self.num_pages,
            "free_pages": self._allocator.free_pages,
            "allocated_pages": self._allocator.allocated_pages,
            "kv_pool": dict(
                self._pool_stats,
                pages_live=turns.get("kv_pages_live", 0),
                pages_scanned=turns.get("kv_pages_scanned", 0),
                decode_paths=self._decode_paths(),
                # each layer's window (0: it attends its whole prefix) and
                # the table columns its decode scan walks a slot
                layer_windows=list(self._layer_windows),
                layer_table_widths=list(self._layer_table_widths),
                **({"full_pages_live": turns.get("kv_full_pages_live", 0)}
                   if self._ring_pages else {}),
                **({"latent_rows_read": turns.get("kv_latent_rows", 0)}
                   if self._latent_layers else {}),
                # conv layers: chunks that zeroed a slot's state and chunks
                # begun from a carried one (the state's shape and bytes are
                # ``conv_state`` / ``bytes_by_kind``, from ``describe``)
                **({"state_resets": turns.get("state_resets", 0),
                    "state_carries": turns.get("state_carries", 0)}
                   if self._slot_state else {}),
                # a hybrid model: the state-space state its scans moved
                **({"ssm_state_bytes_moved": turns.get("ssm_state_bytes", 0)}
                   if self._ssm_step_bytes else {}),
                **select,
            ),
            "page_journal": {
                "events": self._page_journal.snapshot(),
                "journal_total": self._page_journal.total,
                "journal_rotated": self._page_journal.rotated_out,
            },
            "fabric": {
                "migrated_out": self.migrated_out,
                "migrated_in": self.migrated_in,
                "pushes_out": self.pushes_out,
                "pushes_in": self.pushes_in,
            },
            "startup": self.startup_summary(),
        }
        if self._moe_kw:
            from ray_dynamic_batching_tpu.models.moe import routing_rule

            cfg = self.model.cfg
            first = cfg.moe_first_expert
            out["moe"] = {
                "rows_per_expert": turns.get("moe_rows_per_expert"),
                "imbalance": turns.get("moe_imbalance"),
                "paths": self._expert_paths(),
                # the experts held here, of the router's, and how it routes
                "held_experts": [first, first + cfg.held_experts],
                "num_experts": cfg.num_experts,
                "routing": routing_rule(cfg).describe(),
                "held_rows_share": turns.get("moe_held_rows_share"),
            }
        if self.draft_model is not None:
            out["spec"] = {
                "spec_tokens": self.spec_tokens,
                "acceptance": self.spec_acceptance(),
                "rounds_windowed": len(self._spec_acc_window),
            }
        return out

    @property
    def active_slots(self) -> int:
        return int(self._active_mask.sum())

    @property
    def busy(self) -> bool:
        """Work in flight: active slots OR requests mid-admission
        (dequeued but not yet slotted — invisible to both queue depth
        and ``active_slots``; drain logic that ignores this window
        aborts requests seconds from their first token). Accepted-but-
        unimported inbound parcels count too: the source already
        committed the hand-off."""
        return (self._admitting > 0 or bool(self._trains)
                or bool(self._active_mask.any())
                or self._fabric_pending())


# --- the engine thread's time, tiled from the ring ---------------------------
# Below the engine and its programs on purpose: an edit here moves no line
# of a jitted function (the chip's compile cache keys on them).
class ThreadParts(NamedTuple):
    """Where the engine thread's time went over one tile (ms, wall clock):
    ``blocked``, ``idle`` and ``host`` sum to ``wall`` where nothing was
    ``clipped``; ``cpu`` is what the thread's CPU clock charged the tile
    (``Turn.cpu_ms``: a reading beside the three, part of none)."""

    wall: float
    blocked: float
    idle: float
    host: float
    cpu: float
    clipped: float


def thread_parts(prev: Turn, rec: Turn) -> ThreadParts:
    """The tile that ends at ``rec``: ``wall`` from the previous record's
    ``t_done`` to its own; ``blocked`` in its fetch, where the result was
    not ready when the host came for it (a fetch that found it ready is a
    copy, and counts as the host's); ``idle`` inside idle waits; and
    ``host``, the rest: the thread had work on the host's side (prepare,
    the jitted call with its uploads, harvest, admission, the record's own
    writing), whether it ran or wanted to run and did not (the interpreter
    lock, the scheduler, the runtime's threads inside the call). A rest
    below 0 (hand-built records; never one thread's own) is ``clipped`` to
    0 and kept."""
    wall = rec.t_done - prev.t_done
    blocked = (rec.t_fetched - rec.t_fetch
               if rec.t_fetch and not rec.ready_at_fetch else 0.0)
    rest = wall - blocked - rec.idle_ms
    return ThreadParts(wall, blocked, rec.idle_ms, max(rest, 0.0),
                       rec.cpu_ms, max(-rest, 0.0))


def thread_tiling(turns: Sequence[Turn], longest: int = 8) -> Dict[str, Any]:
    """:func:`summarize_turns`' keys for the engine thread's time, over
    consecutive records (the first has no predecessor and ends no tile; a
    list with a record left out of its middle gives that record's time to
    the next one's ``host``): ``thread_ms``, the tiles' parts summed; the
    three ``thread_{blocked,idle,host}_share``, each part over the three's
    sum (``wall`` + ``clipped``: they sum to 1);
    ``fetch_found_ready_share`` of the fetched records; and
    ``longest_records``, the ``longest`` tiles of largest ``wall`` with
    their own parts and what the record was."""
    tiles = [(thread_parts(a, b), b) for a, b in zip(turns, turns[1:])]
    if not tiles:
        return {}
    sums = ThreadParts(*(sum(col) for col in zip(*(p for p, _ in tiles))))
    out: Dict[str, Any] = {"thread_ms": sums._asdict()}
    whole = sums.wall + sums.clipped
    if whole > 0:
        out.update((f"thread_{name}_share", getattr(sums, name) / whole)
                   for name in ("blocked", "idle", "host"))
    fetched = [t for t in turns if t.t_fetched]
    if fetched:
        out["fetch_found_ready_share"] = sum(
            1 for t in fetched if t.ready_at_fetch) / len(fetched)
    out["longest_records"] = [
        dict({k: round(v, 3) for k, v in p._asdict().items()},
             kind=t.kind, substeps=t.substeps,
             queued_behind=t.queued_behind,
             t_dispatch=round(t.t_dispatch, 3))
        for p, t in heapq.nlargest(longest, tiles, key=lambda x: x[0].wall)]
    return out

