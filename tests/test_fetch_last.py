"""The engine fetches a scan LAST (ISSUE 37; tier-1, CPU, a tiny model:
counts, order and tokens only — never a time).

One iteration of the loop (``DecodeEngine._iterate``) dispatches its decode
scan, then runs what does not need the scan's result — admission, and the
DISPATCH of the next chunk group — and only then fetches and harvests the
scan and completes the group. The device sees ``turn_i, chunk_{i+1},
turn_{i+1}``. Pinned here:

- served tokens and finish reasons equal the model-level reference's
  (``tests/decode_reference.py``) and those of the same engine driven in the
  old order (every dispatch fetched at once), greedy and seeded sampling,
  with stop ids, ``max_new_tokens`` 1 and a capacity finish;
- the ring shows the overlap (a chunk with ``queued_behind`` above 0
  dispatched before the scan ahead of it was fetched) and the cadence (one
  prefill budget a turn; an arrival after a harvest has its first chunk in
  front of the next scan);
- a slot registered between a scan's dispatch and its harvest takes nothing
  of that scan;
- a train parked on pages behind a scan is granted after the harvest that
  freed some, and counted starved once;
- ``stop``, the fabric, ``abort_active`` and ``release_buffers`` find nothing
  in flight.

Where nothing waits to join the batch, scan N+1 is dispatched BEFORE scan N is
fetched, its pending tokens read on the device from N's last substep (ISSUE
56; ``DecodeEngine._horizon_ahead``). Pinned in section (vi) on:

- the tokens, finish reasons and order of today's order (forced by a hook on
  the condition, never an option) and the reference's, greedy and seeded
  sampling, horizons 1, 2 and 8, with an EOS in the middle of scan N and on
  its last substep (the scan ahead ran ``h`` substeps for nothing: counted,
  discarded), penalties and per-request stop ids, an expert model with conv
  state and a state-space model (a garbage scan moves nothing into the
  slot's next tenant);
- a length finish or the cache's end due inside N: NOT issued ahead;
- an arrival while a scan is ahead: its chunk stands behind ONE unfetched
  scan and it registers before the next is issued;
- every host-side reader or freer of the pool, and every end of the loop,
  completes the scan in flight first and loses no token;
- where the host comes LATE to its fetches (over ``AHEAD_READY_MAX`` of the
  recent ones found their result ready: the host is the pace, a committed
  scan hides nothing), today's order runs until the share falls again. What
  a fetch found is a TIME, so every test here TELLS the engine (``_HOSTS``:
  a host that always waits, is always late, alternates, or is late at first
  and then waits, so that the order changes in the middle of the streams),
  and the program's own rule runs on it: tokens, EOS waste, an arrival and
  the drains are pinned under each.
"""

import itertools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_dynamic_batching_tpu.engine import decode as decode_mod
from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
from ray_dynamic_batching_tpu.engine.queue import RequestQueue
from ray_dynamic_batching_tpu.engine.request import Request
from ray_dynamic_batching_tpu.models import registry  # noqa: F401
from ray_dynamic_batching_tpu.models.base import get_model

from tests.decode_reference import teacher_forced

MAX_LEN = 96


@pytest.fixture(scope="module")
def lm():
    model = get_model("llama_tiny", dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


# What each scan's fetch is told it found (``DecodeEngine._note_ready``'s
# ``ready``), by the host's pace. Whether a result was there before the host
# came is a TIME, and this CPU's is no chip's: the tests give the observation
# and the program's own rule runs on it. ``late_then_waits`` begins just above
# the limit: four fetches that waited bring today's order to its end.
_HOSTS = {
    "waits": lambda: itertools.repeat(False),       # the device is the pace
    "always_late": lambda: itertools.repeat(True),
    "alternating": lambda: itertools.cycle([True, False]),
    "late_then_waits": lambda: itertools.repeat(False),
}
HOSTS = sorted(_HOSTS)


def _engine(lm, host="waits", **kw):
    """``host``: a key of ``_HOSTS``, or None: the fetch's own observation."""
    model, params = lm
    queue = RequestQueue(model.name, max_len=256)
    opts = dict(num_slots=4, max_len=MAX_LEN, prompt_buckets=[8, 16],
                eos_token_id=None, default_max_new_tokens=8,
                decode_horizon=4, page_size=128)
    opts.update(kw)
    engine = DecodeEngine(model, params, queue, **opts)
    if host is not None:
        told, note = _HOSTS[host](), engine._note_ready
        engine._note_ready = lambda ready: note(next(told))
        if host == "late_then_waits":
            engine._ready_share = decode_mod.AHEAD_READY_MAX * 1.03
    return engine, queue


def _request(queue, model_name, tokens, max_new, **payload):
    """A request, queued (``queue`` None: its payload only)."""
    req = Request(model=model_name, payload=dict(
        tokens=list(tokens), max_new_tokens=max_new, **payload),
        slo_ms=60_000.0)
    if queue is not None:
        queue.add_request(req)
    return req


def _reference(lm, req, n=None):
    model, params = lm
    p = req.payload
    return teacher_forced(
        model, params, p["tokens"], n or int(p["max_new_tokens"]),
        temperature=float(p.get("temperature", 0.0)),
        top_k=int(p.get("top_k", 0)), seed=int(p.get("seed", 0)))


def _run_in_the_old_order(engine, timeout_iters=10_000):
    """The parent's iteration: every dispatch fetched at once."""
    for _ in range(timeout_iters):
        engine._service_fabric()
        admitted = engine._admit()
        engine._pump_prefill()
        if engine._active_mask.any():
            engine._step()
        elif not admitted and not engine._trains and len(engine.queue) == 0:
            return
    raise AssertionError("did not drain")


# --- (i) tokens and finish reasons -------------------------------------------
def _mixed(lm, queue, model_name, sampled):
    """A long-lived stream, then multi-chunk prompts (40, 30, 35 tokens at a
    16-token chunk) beside it: a stop id, ``max_new_tokens`` 1, and a prompt
    that runs into the cache's end."""
    rng = np.random.default_rng(7)

    def toks(n):
        return rng.integers(1, 500, n).tolist()

    sampling = (lambda seed: dict(temperature=0.7, top_k=12, seed=seed)) \
        if sampled else (lambda seed: {})
    plain = _request(None, model_name, toks(20), 8, **sampling(5))
    # a stop id the reference's third token names
    stop_at = _reference(lm, plain)[2]
    # queued in this order: the live stream is decoding when the rest arrive
    return {
        "live": _request(queue, model_name, toks(4), 40, **sampling(1)),
        "long": _request(queue, model_name, toks(40), 6, **sampling(2)),
        "one": _request(queue, model_name, toks(35), 1, **sampling(3)),
        "mid": _request(queue, model_name, toks(30), 6, **sampling(4)),
        "stop": _request(queue, model_name, plain.payload["tokens"], 8,
                         stop_token_ids=[stop_at], **sampling(5)),
        "capacity": _request(queue, model_name, toks(90), 20, **sampling(6)),
    }


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "seeded_sampling"])
def test_tokens_and_finish_reasons_are_the_references_and_the_old_orders(
        lm, sampled):
    served = {}
    for order_name in ("fetch_last", "old"):
        engine, queue = _engine(lm)
        reqs = _mixed(lm, queue, engine.model.name, sampled)
        if order_name == "fetch_last":
            engine.run_until_idle(timeout_s=300)
        else:
            _run_in_the_old_order(engine)
        served[order_name] = {
            k: r.future.result(timeout=5) for k, r in reqs.items()}
        engine._allocator.check()
        assert engine._allocator.free_pages == engine.num_pages
        assert engine._issued_turn is None and not engine._issued_groups
        if order_name == "fetch_last":
            ring, payloads = list(engine.turns), reqs
    new, old = served["fetch_last"], served["old"]
    for k in new:
        assert (new[k].tokens, new[k].finish_reason) == (
            old[k].tokens, old[k].finish_reason), k
    # ... and the model-level reference's
    for k in ("live", "long", "mid", "one"):
        assert new[k].tokens == _reference(lm, payloads[k]), k
        assert new[k].finish_reason == "length"
    ref = _reference(lm, payloads["stop"])
    cut = ref.index(payloads["stop"].payload["stop_token_ids"][0]) + 1
    assert new["stop"].tokens == ref[:cut] and cut <= 3
    assert new["stop"].finish_reason == "eos"
    cap = new["capacity"]
    assert cap.finish_reason == "capacity"
    assert 1 <= len(cap.tokens) <= MAX_LEN - 90 + 1 < 20
    assert cap.tokens == _reference(lm, payloads["capacity"],
                                    n=len(cap.tokens))
    # the run did overlap: chunks were dispatched behind unfetched scans
    assert any(t.kind == "chunk" and t.queued_behind for t in ring)


# --- (ii) the ring: overlap engaged, cadence kept ---------------------------------
def _live_stream(engine, queue, new=48, prompt=4, seed=2):
    """One registered, decoding stream (admitted and drained by hand)."""
    rng = np.random.default_rng(seed)
    live = _request(queue, engine.model.name,
                    rng.integers(1, 500, prompt).tolist(), new)
    engine._admit()
    engine._drain_prefill()
    assert engine.active_slots == 1
    engine.reset_ttft_window()
    return live


def test_the_ring_shows_a_chunk_dispatched_behind_an_unfetched_scan(lm):
    engine, queue = _engine(lm, num_slots=6)
    live = _live_stream(engine, queue)
    rng = np.random.default_rng(3)
    burst = [_request(queue, engine.model.name,
                      rng.integers(1, 500, 80).tolist(), 4)   # 5 chunks
             for _ in range(3)]
    engine.run_until_idle(timeout_s=300)
    for r in burst + [live]:
        r.future.result(timeout=5)
    ring = list(engine.turns)
    # the ring stays in dispatch order
    assert [t.t_dispatch for t in ring] == sorted(t.t_dispatch for t in ring)
    overlapped = [(a, b) for a, b in zip(ring, ring[1:])
                  if a.kind == "turn" and b.kind == "chunk"
                  and b.queued_behind > 0]
    assert overlapped
    for scan, chunk in overlapped:
        # dispatched behind the scan, before the scan was fetched
        assert scan.t_dispatch <= chunk.t_dispatch < scan.t_fetched
        assert chunk.t_done >= scan.t_done        # its record comes after
    # a scan behind a chunk that ended no prompt is queued behind it
    assert any(a.kind == "chunk" and not a.t_fetched and b.kind == "turn"
               and b.queued_behind > 0 for a, b in zip(ring, ring[1:]))
    # a dispatch that found nothing queued was made after everything before
    # it was done on the host too
    for a, b in zip(ring, ring[1:]):
        if not b.queued_behind:
            assert a.t_done <= b.t_dispatch
    # the cadence: never more than one budget of chunk tokens between two
    # scans, and while trains were pending one chunk behind EVERY scan
    budget = engine.prefill_token_budget
    since = 0
    for t in ring:
        if t.kind == "turn":
            since = 0
        else:
            since += t.tokens
            assert since <= budget, ring
    assert all(t.substeps == 1 for t in ring
               if t.kind == "turn" and t.trains)
    # every chunk but the burst's first went out behind a scan
    chunks = [t for t in ring if t.kind == "chunk"]
    assert len(chunks) == 15
    assert [t.queued_behind > 0 for t in chunks] == [False] + [True] * 14
    s = engine.turn_summary()
    assert s["overlapped_dispatch_share"] == pytest.approx(
        sum(1 for t in ring if t.queued_behind) / len(ring))
    assert engine.snapshot()["turns"]["overlapped_dispatch_share"] == (
        s["overlapped_dispatch_share"])


def test_an_arrival_after_a_harvest_has_its_first_chunk_before_the_next_scan(
        lm, monkeypatch):
    engine, queue = _engine(lm)
    monkeypatch.setattr(engine, "_horizon_ahead", lambda: 0)   # today's order
    _live_stream(engine, queue)
    engine._iterate()                       # a scan, nothing behind it
    late = _request(queue, engine.model.name, list(range(1, 13)), 4)
    engine._iterate()
    kinds = [(t.kind, t.queued_behind) for t in engine.turns]
    # the arrival's chunk found the device empty and went in FRONT of the
    # second scan; a one-chunk prompt: registered before that scan
    assert kinds == [("turn", 0), ("chunk", 0), ("turn", 0)]
    assert engine.active_slots == 2 and late.future.done() is False
    assert engine.turns[2].active == 2


@pytest.mark.parametrize("host", ["waits", "late_then_waits"])
def test_an_arrival_while_a_scan_is_ahead_stands_behind_that_one_scan(
        lm, host):
    """(d) A request that arrives while scan N+1 is committed ahead: its
    chunk group is dispatched with ONE decode scan unfetched before it (N is
    fetched by then), it registers before the next scan is issued, and the
    next scan is not issued ahead while it pends."""
    engine, queue = _engine(lm, host=host)
    live = _live_stream(engine, queue, new=80)
    while engine._issued_turn is None:      # a host late at first: 4 scans
        engine._iterate()             # N; N+1 issued ahead; N harvested
    ahead = engine._issued_turn
    assert ahead is not None and ahead.ahead and not engine._issued_groups
    at = len(engine.turns) - 1
    assert at == (0 if host == "waits" else 4)
    assert [(t.kind, t.queued_behind, t.ahead) for t in engine.turns] == [
        ("turn", 0, False)] * (at + 1)
    late = _request(queue, engine.model.name, list(range(1, 13)), 4)
    engine._iterate()
    n, n1, chunk = list(engine.turns)[at:]
    assert [(t.kind, t.queued_behind, t.ahead) for t in (n, n1, chunk)] == [
        ("turn", 0, False), ("turn", 1, True), ("chunk", 1, False)]
    # behind N+1 alone: N had been fetched when the chunk was dispatched
    assert n.t_fetched <= chunk.t_dispatch
    assert n1.t_dispatch <= chunk.t_dispatch <= n1.t_fetch
    assert n1.t_fetched <= chunk.t_fetched
    # it pended: nothing was issued ahead of N+1, and nothing is in flight
    assert engine._issued_turn is None and not engine._issued_groups
    assert engine.active_slots == 2 and n1.active == 1
    (new,) = [i for i, s in enumerate(engine._slots) if s.request is late]
    assert len(engine._slots[new].generated) == 1      # its first token only
    engine._iterate()
    both = engine.turns[at + 3]
    assert both.kind == "turn" and both.active == 2
    assert not both.ahead and both.queued_behind == 0
    engine.run_until_idle(timeout_s=300)
    for r in (live, late):
        assert r.future.result(timeout=5).tokens == _reference(lm, r)
    assert engine.turn_summary()["ahead_wasted_substeps"] == 0


def test_an_arrival_during_a_scan_is_issued_behind_it_and_registered_after(
        lm, monkeypatch):
    engine, queue = _engine(lm)
    live = _live_stream(engine, queue)
    issue = engine._issue_turn
    arrivals = []

    def issue_then_arrive(ph, horizon, ahead_of=None):
        out = issue(ph, horizon, ahead_of=ahead_of)
        if not arrivals:    # arrives while the scan runs
            arrivals.append(_request(queue, engine.model.name,
                                     list(range(1, 13)), 4))
        return out

    monkeypatch.setattr(engine, "_issue_turn", issue_then_arrive)
    before = len(engine._slots[0].generated)
    engine._iterate()
    scan, chunk = list(engine.turns)
    assert (scan.kind, chunk.kind) == ("turn", "chunk")
    assert chunk.queued_behind == 1 and scan.t_dispatch <= chunk.t_dispatch
    # fetched after the scan, registered after its harvest: the scan ran
    # with one slot, the new slot holds its prompt and its first token only
    assert scan.t_fetched <= chunk.t_fetched and scan.active == 1
    (new,) = [i for i, s in enumerate(engine._slots)
              if s.request is arrivals[0]]
    assert len(engine._slots[new].generated) == 1
    assert engine._len_host[new] == 12
    assert len(engine._slots[0].generated) == before + scan.substeps
    # the budget was spent behind the scan: not again before the next one
    assert engine._prefill_spent == 16
    engine.run_until_idle(timeout_s=300)
    for r in (live, arrivals[0]):
        assert r.future.result(timeout=5).tokens == _reference(lm, r)


# --- (iii) the hazard: a slot registered before the harvest -----------------------
def test_a_slot_registered_before_a_scans_harvest_takes_nothing_of_it(lm):
    engine, queue = _engine(lm)
    live = _live_stream(engine, queue, new=6)
    with engine._phase("rdb.engine.turn") as ph:
        issued = engine._issue_turn(ph, 2)
    # a whole admission lands between the dispatch and the harvest
    late = _request(queue, engine.model.name, list(range(3, 14)), 5)
    engine._admit()
    engine._pump_prefill()
    (new,) = [i for i, s in enumerate(engine._slots) if s.request is late]
    assert engine._active_mask[new] and not issued.active_at_dispatch[new]
    first = list(engine._slots[new].generated)
    with engine._phase("rdb.engine.turn") as ph:
        engine._complete_turn(ph, issued)
    assert engine._slots[new].generated == first and len(first) == 1
    assert engine._len_host[new] == 11         # the prompt's, not the scan's
    assert engine._tokens[new, 0] == first[0]
    assert len(engine._slots[0].generated) == 1 + 2
    engine.run_until_idle(timeout_s=300)
    for r in (live, late):
        assert r.future.result(timeout=5).tokens == _reference(lm, r)


# --- (iv) a train parked behind a scan ---------------------------------------------
def test_a_train_parked_behind_a_scan_is_granted_after_the_harvest(
        lm, monkeypatch):
    # two pages: the live stream's 130 positions hold both until it ends
    engine, queue = _engine(lm, num_slots=2, max_len=192,
                            prompt_buckets=[32], kv_pool_pages=2)
    live = _live_stream(engine, queue, new=3, prompt=130)
    assert engine._allocator.free_pages == 0
    late = _request(queue, engine.model.name, list(range(1, 21)), 3)
    grants = []
    grant = engine._grant_train_pages

    def spy(train, reclaim=True):
        ok = grant(train, reclaim=reclaim)
        grants.append((reclaim, ok))
        return ok

    monkeypatch.setattr(engine, "_grant_train_pages", spy)
    tags = {"model": engine.model.name}
    starved0 = decode_mod.PREFILL_STARVED.get(tags=tags)
    engine._iterate()      # admitted; parked before the scan and behind it
    assert grants == [(True, False), (False, False)]
    assert engine._trains and engine._trains[0].pos == 0
    assert engine._prefill_spent == 0
    # behind the scan the parking is not counted: the pump after the
    # harvest, today's, counts it
    assert decode_mod.PREFILL_STARVED.get(tags=tags) == starved0 + 1
    engine._iterate()      # the live stream's last token: its pages free
    assert live.future.done() and engine._allocator.free_pages == 2
    assert grants[-1] == (False, False) and engine._trains
    engine._iterate()      # the first pump after that harvest grants
    assert grants[-1] == (True, True) and not engine._trains
    engine.run_until_idle(timeout_s=300)
    assert late.future.result(timeout=5).tokens == _reference(lm, late)
    assert live.future.result(timeout=5).tokens == _reference(lm, live)
    engine._allocator.check()


# --- (v) nothing is in flight where it must not be ---------------------------------
def test_a_fabric_request_arriving_behind_a_scan_is_served_with_nothing_issued(
        lm, monkeypatch):
    engine, queue = _engine(lm)
    live = _live_stream(engine, queue)
    seen = []

    def deliver(parcel):
        seen.append((engine._issued_turn, list(engine._issued_groups),
                     len(parcel.request.payload["tokens"])))
        return False        # refused: the stream stays and decodes on

    pump = engine._pump_prefill

    def pump_and_ask(budget=None, behind_turn=False):
        if behind_turn and not seen:
            assert engine._issued_turn is not None
            assert engine.request_migration(live.request_id, deliver)
        return pump(budget, behind_turn)

    monkeypatch.setattr(engine, "_pump_prefill", pump_and_ask)
    serve = engine._service_fabric

    def serve_checked():
        assert engine._issued_turn is None and not engine._issued_groups
        return serve()

    monkeypatch.setattr(engine, "_service_fabric", serve_checked)
    engine._iterate()
    assert not seen and engine._fabric_pending()      # asked, not served yet
    generated = len(engine._slots[0].generated)
    engine._iterate()
    assert seen == [(None, [], 4)] and not engine._fabric_pending()
    assert len(engine._slots[0].generated) > generated
    engine.run_until_idle(timeout_s=300)
    assert live.future.result(timeout=5).tokens == _reference(lm, live)


def test_stop_waits_for_the_issued_scan_then_abort_and_release_find_nothing(
        lm, monkeypatch):
    engine, queue = _engine(lm)
    engine.warmup()
    live = _live_stream(engine, queue, new=10_000)     # never ends itself
    behind, go = threading.Event(), threading.Event()
    pump = engine._pump_prefill

    def pump_held(budget=None, behind_turn=False):
        if behind_turn and not go.is_set():
            behind.set()
            go.wait(60)
        return pump(budget, behind_turn)

    monkeypatch.setattr(engine, "_pump_prefill", pump_held)
    engine.start()
    assert behind.wait(60)
    # a scan is issued and not fetched; a request arrives behind it too
    assert engine._issued_turn is not None
    generated = len(engine._slots[0].generated)
    late = _request(queue, engine.model.name, list(range(1, 30)), 4)
    stopper = threading.Thread(target=engine.stop, kwargs={"timeout_s": 60})
    stopper.start()
    stopper.join(0.2)
    assert stopper.is_alive()        # stop waits: the iteration is not over
    go.set()
    stopper.join(60)
    assert not stopper.is_alive() and engine._thread is None
    # the iteration completed what it had issued before the loop ended
    assert engine._issued_turn is None and not engine._issued_groups
    assert len(engine._slots[0].generated) > generated
    engine.abort_active(RuntimeError("shutdown"))
    with pytest.raises(RuntimeError):
        live.future.result(timeout=5)
    assert not engine._trains and not engine._active_mask.any()
    assert engine._allocator.free_pages == engine.num_pages
    engine.release_buffers()
    assert engine._cache is None and engine._allocator is None
    assert not late.future.done()     # never left the queue: the queue's


# --- (vi) scan N+1 issued before scan N is fetched (ISSUE 56) ----------------------
def _today(engine, monkeypatch):
    """Today's order, by a hook on the condition: no scan is issued ahead."""
    monkeypatch.setattr(engine, "_horizon_ahead", lambda: 0)
    return engine


def _nothing_in_flight(engine):
    return engine._issued_turn is None and not engine._issued_groups


def _settled(engine):
    engine._allocator.check()
    assert engine._allocator.free_pages == engine.num_pages
    assert _nothing_in_flight(engine)


def _stop_index(ref, h, substep, least=None):
    """An index t of ``ref`` (a request's tokens; index 0 is the prefill's)
    that scans of ``h`` substeps emit on substep ``substep``, whose token no
    earlier index holds: a stop id that ends the request exactly there."""
    least = h + 1 if least is None else least
    for t in range(least, len(ref)):
        if (t - 1) % h == substep and ref[t] not in ref[:t]:
            return t
    raise AssertionError("the reference repeats itself: pick another seed")


def _ahead_mix(lm, queue, model_name, sampled, h):
    """Seven requests over four slots: streams that end by length, by a stop
    id in the middle of a scan and on a scan's last substep (where the scans
    stand once slots turn over is the run's own matter: section (vi)'s next
    tests place them exactly), with penalties, and refills that take the
    slots the stops freed."""
    rng = np.random.default_rng(17 + h)

    def toks(n):
        return rng.integers(1, 500, n).tolist()

    sampling = (lambda seed: dict(temperature=0.8, top_k=16, seed=seed)) \
        if sampled else (lambda seed: {})
    out = {}
    for name, (prompt, new, substep) in {
            "mid": (6, 40, 0 if h == 1 else h // 2 - 1),
            "last": (9, 40, h - 1)}.items():
        probe = _request(None, model_name, toks(prompt), new,
                         **sampling(len(out) + 1))
        ref = _reference(lm, probe)
        out[name] = _request(
            queue, model_name, probe.payload["tokens"], new,
            stop_token_ids=[ref[_stop_index(ref, h, substep)]],
            **sampling(len(out) + 1))
    out["long"] = _request(queue, model_name, toks(5), 44, **sampling(3))
    out["pen"] = _request(queue, model_name, toks(7), 21,
                          presence_penalty=0.7, frequency_penalty=0.4,
                          **sampling(4))
    out["short"] = _request(queue, model_name, toks(12), 7, **sampling(5))
    out["refill"] = _request(queue, model_name, toks(14), 19, **sampling(6))
    out["pen_stop"] = _request(queue, model_name, toks(4), 30,
                               frequency_penalty=0.9, stop_token_ids=[7, 11],
                               **sampling(7))
    return out


def _serve_the_mix(lm, sampled, h, host, today=False):
    """``_ahead_mix`` served to the end: (each request's payloads, its
    result, the ring's summary, the ring's scans)."""
    engine, queue = _engine(lm, host=host, decode_horizon=h, ttft_horizon=h)
    if today:
        engine._horizon_ahead = lambda: 0
    reqs = _ahead_mix(lm, queue, engine.model.name, sampled, h)
    engine.run_until_idle(timeout_s=300)
    served = {k: r.future.result(timeout=5) for k, r in reqs.items()}
    _settled(engine)
    return (reqs, served, engine.turn_summary(),
            [t for t in engine.turns if t.kind == "turn"])


_TODAY = {}     # (sampled, h): today's order served once for every host


@pytest.mark.parametrize("host", HOSTS)
@pytest.mark.parametrize("h", [1, 2, 8])
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "seeded_sampling"])
def test_issued_ahead_serves_todays_tokens_in_todays_order(
        lm, sampled, h, host):
    if (sampled, h) not in _TODAY:
        _TODAY[sampled, h] = _serve_the_mix(lm, sampled, h, "waits",
                                            today=True)
    _, old, today, _ = _TODAY[sampled, h]
    payloads, new, ring, scans = _serve_the_mix(lm, sampled, h, host)
    for k in new:
        assert (new[k].tokens, new[k].finish_reason) == (
            old[k].tokens, old[k].finish_reason), k
    for k in ("long", "short", "refill"):
        assert new[k].tokens == _reference(lm, payloads[k]), k
        assert new[k].finish_reason == "length"
    for k in ("mid", "last"):
        ref = _reference(lm, payloads[k])
        cut = ref.index(payloads[k].payload["stop_token_ids"][0]) + 1
        assert new[k].tokens == ref[:cut] and new[k].finish_reason == "eos"
    # the mechanism engaged, and only where asked and while the host waits
    assert today["scans_issued_ahead_share"] == 0
    assert today["ahead_wasted_substeps"] == 0
    at = [i for i, t in enumerate(scans) if t.ahead]
    if host == "waits":
        assert ring["scans_issued_ahead_share"] > 0
        assert today["overlapped_dispatch_share"] < (
            ring["overlapped_dispatch_share"])
    elif host == "late_then_waits":     # the order changed in mid-stream
        assert at and at[0] >= 5
    elif host == "always_late":         # five late fetches: today's order
        assert all(i <= 5 for i in at)
    else:                               # every other fetch late: ten
        assert all(i <= 10 for i in at)


def _pair(lm, monkeypatch, h, today=False, **kw):
    """Two streams registered together (so every scan holds both, and the
    k-th scan emits the indices 1 + (k-1)h .. kh of each), nothing queued."""
    engine, queue = _engine(lm, num_slots=2, decode_horizon=h,
                            ttft_horizon=h, **kw)
    if today:
        _today(engine, monkeypatch)
    return engine, queue


def _register_both(engine, queue, a, b):
    for r in (a, b):
        queue.add_request(r)
    engine._admit()
    engine._drain_prefill()
    assert engine.active_slots == 2
    engine.reset_ttft_window()


@pytest.mark.parametrize("host", ["waits", "late_then_waits"])
@pytest.mark.parametrize("h, substep", [(2, 0), (2, 1), (8, 3), (8, 7),
                                        (1, 0)])
def test_an_eos_inside_scan_n_costs_the_scan_ahead_its_rows_and_nothing_else(
        lm, monkeypatch, h, substep, host):
    """(a), (b): the stop id falls on substep ``substep`` of scan N (``h - 1``:
    its last). N+1 was issued ahead with the slot active: it ran ``h`` substeps
    past the stream's end into the stream's own pages, counted and discarded;
    the other stream's tokens are the reference's. Under a host that is late
    at first the stop falls after the order has changed (scan 5 is the first
    issued ahead)."""
    engine, queue = _pair(lm, monkeypatch, h, host=host)
    name = engine.model.name
    # seeded sampling: a greedy tiny model soon repeats itself
    draw = dict(temperature=0.9, top_k=40, seed=3)
    new, least = (40, None) if host == "waits" else (80, 5 * h + 1)
    probe = _request(None, name, list(range(3, 12)), new, **draw)
    ref = _reference(lm, probe)
    t = _stop_index(ref, h, substep, least)
    ends = _request(None, name, probe.payload["tokens"], new,
                    stop_token_ids=[ref[t]], **draw)
    mate = _request(None, name, list(range(20, 27)), new + 4)
    _register_both(engine, queue, ends, mate)
    engine.run_until_idle(timeout_s=300)
    got = ends.future.result(timeout=5)
    assert (got.tokens, got.finish_reason) == (ref[:t + 1], "eos")
    assert mate.future.result(timeout=5).tokens == _reference(lm, mate)
    _settled(engine)
    scans = [r for r in engine.turns if r.kind == "turn"]
    n = (t - 1) // h                 # 0-based: the scan that emitted index t
    assert scans[n].active == 2 and scans[n].wasted_substeps == 0
    # N+1: dispatched before N's fetch, with both rows; one of them wasted
    assert scans[n + 1].ahead and scans[n + 1].active == 2
    assert scans[n + 1].t_dispatch < scans[n].t_fetch
    assert scans[n + 1].wasted_substeps == h
    assert scans[n + 2].active == 1
    s = engine.turn_summary()
    assert s["ahead_wasted_substeps"] == h
    assert s["ahead_wasted_substep_share"] == pytest.approx(
        h / sum(r.active * r.substeps for r in scans))
    assert engine.snapshot()["turns"]["scans_issued_ahead_share"] == (
        s["scans_issued_ahead_share"]) > (0.5 if host == "waits" else 0.2)


@pytest.mark.parametrize("h", [2, 8])
@pytest.mark.parametrize("end", ["length", "capacity"])
def test_a_finish_due_inside_scan_n_is_not_issued_ahead(lm, monkeypatch,
                                                        h, end):
    """(c): a slot CERTAIN to end inside the scan in flight, by its length
    bound or the cache's end (both known before the fetch): the next scan
    waits for the fetch, so the slot can be refilled at once and no row is
    wasted."""
    engine, queue = _pair(lm, monkeypatch, h)
    name = engine.model.name
    if end == "length":
        ends = _request(None, name, list(range(3, 12)), 2 * h + 2)
        final = len(_reference(lm, ends))
    else:       # 9 prompt positions + the tokens fed reach MAX_LEN
        ends = _request(None, name, list(range(3, 3 + MAX_LEN - 2 * h - 2)),
                        400)
        final = None
    mate = _request(None, name, list(range(20, 27)), 6 * h)
    _register_both(engine, queue, ends, mate)
    engine.run_until_idle(timeout_s=300)
    got = ends.future.result(timeout=5)
    assert got.finish_reason == end
    if final:
        assert got.tokens == _reference(lm, ends)
    else:
        assert got.tokens == _reference(lm, ends, n=len(got.tokens))
    assert mate.future.result(timeout=5).tokens == _reference(lm, mate)
    _settled(engine)
    scans = [r for r in engine.turns if r.kind == "turn"]
    assert any(r.ahead for r in scans)            # it does engage elsewhere
    assert engine.turn_summary()["ahead_wasted_substeps"] == 0
    # the first scan with one row left: the scan in front of it held the
    # finish, so it found nothing on the device
    alone = next(i for i, r in enumerate(scans) if r.active == 1)
    assert scans[alone - 1].active == 2
    assert not scans[alone].ahead and scans[alone].queued_behind == 0
    assert scans[alone].t_dispatch >= scans[alone - 1].t_done


def test_pages_for_both_scans_come_from_the_free_list_or_todays_order_runs(
        lm, monkeypatch):
    """The pages for ``len + h_N + h_{N+1}`` (the host's mirror is a scan
    late: the upper bound) come off the free list with nothing reclaimed or
    evicted, else the turn runs in today's order; an eviction in
    ``_ensure_page_headroom`` finds nothing in flight."""
    engine, queue = _engine(lm, num_slots=2, max_len=256, page_size=128,
                            kv_pool_pages=3, prompt_buckets=[16],
                            decode_horizon=8, ttft_horizon=8)
    name = engine.model.name
    a = _request(None, name, list(range(3, 14)), 150)
    b = _request(None, name, list(range(20, 29)), 150)
    _register_both(engine, queue, a, b)
    seen = []
    finish = engine._finish

    def finish_checked(slot_idx, reason):
        seen.append((reason, _nothing_in_flight(engine)))
        return finish(slot_idx, reason)

    monkeypatch.setattr(engine, "_finish", finish_checked)
    engine.run_until_idle(timeout_s=600)
    # three pages: both grow into a second page, one is evicted for it
    reasons = sorted(r.future.result(timeout=5).finish_reason
                     for r in (a, b))
    assert reasons == ["capacity", "length"]
    assert ("capacity", True) in seen and all(ok for _, ok in seen)
    for r in (a, b):
        got = r.future.result(timeout=5).tokens
        assert got == _reference(lm, r, n=len(got))
    scans = [r for r in engine.turns if r.kind == "turn"]
    assert any(r.ahead for r in scans) and not all(
        r.ahead for r in scans[1:])
    assert engine.turn_summary()["ahead_wasted_substeps"] == 0
    _settled(engine)


# (f) an expert model with conv state a slot, and a state-space model
def _tiny(kind):
    from ray_dynamic_batching_tpu.models.causal_lm import CausalLM

    if kind == "lfm2":          # 8 layers: conv mixers, experts, attention
        from tests.test_lfm2 import TINY
    else:                       # a state-space mixer beside attention
        from tests.test_falcon_h1 import TINY
    model = CausalLM(TINY, name=f"{kind}_tiny_ahead", dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("kind", ["lfm2", "falcon_h1"])
def test_a_garbage_scan_moves_no_state_into_the_slots_next_tenant(
        kind, monkeypatch):
    """A stream ends by a stop id inside scan N; N+1, issued ahead, steps
    the freed slot's conv / state-space state ``h`` times more and routes its
    rows through the experts. The next tenant's first chunk zeroes the row
    that begins a prompt: it is served what it is served alone."""
    hybrid = _tiny(kind)
    h = 4
    kw = dict(num_slots=2, max_len=512, prompt_buckets=[32], page_size=128,
              kv_pool_pages=8, decode_horizon=h, ttft_horizon=h,
              max_admissions_per_step=2)
    rng = np.random.default_rng(5)
    prompts = {k: rng.integers(1, 500, n).tolist()
               for k, n in (("ends", 20), ("mate", 9), ("next", 27))}
    draw = dict(temperature=0.9, top_k=40)

    def engine_of(today):
        engine, queue = _engine(hybrid, **kw)
        if today:
            _today(engine, monkeypatch)
        return engine, queue, engine.model.name

    # alone, in today's order: what each is served, and where to stop
    engine, queue, name = engine_of(today=True)
    alone = {}
    for k, seed in (("ends", 1), ("next", 3)):
        r = _request(queue, name, prompts[k], 24, seed=seed, **draw)
        engine.run_until_idle(timeout_s=600)
        alone[k] = r.future.result(timeout=5).tokens
    t = _stop_index(alone["ends"], h, 1)
    served, rings = {}, {}
    for order in ("ahead", "today"):
        engine, queue, name = engine_of(today=order == "today")
        ends = _request(None, name, prompts["ends"], 24, seed=1,
                        stop_token_ids=[alone["ends"][t]], **draw)
        mate = _request(None, name, prompts["mate"], 60, seed=2, **draw)
        _register_both(engine, queue, ends, mate)
        (slot_of_ends,) = [i for i, s in enumerate(engine._slots)
                           if s.request is ends]
        for _ in range(1000):
            engine._iterate()
            if ends.future.done():
                break
        if order == "ahead":    # the garbage scan is running, or has run
            assert engine._issued_turn is not None
            assert engine._issued_turn.active_at_dispatch[slot_of_ends]
        nxt = _request(queue, name, prompts["next"], 24, seed=3, **draw)
        for _ in range(1000):
            engine._iterate()
            if any(s.request is nxt for s in engine._slots):
                break
        assert engine._slots[slot_of_ends].request is nxt
        engine.run_until_idle(timeout_s=600)
        served[order] = [r.future.result(timeout=5)
                         for r in (ends, mate, nxt)]
        rings[order] = engine.turn_summary()
        _settled(engine)
    for new, old in zip(served["ahead"], served["today"]):
        assert (new.tokens, new.finish_reason) == (
            old.tokens, old.finish_reason)
    assert served["ahead"][0].tokens == alone["ends"][:t + 1]
    assert served["ahead"][2].tokens == alone["next"]
    assert rings["ahead"]["ahead_wasted_substeps"] == h
    assert rings["today"]["ahead_wasted_substeps"] == 0
    # the chunk that began the next tenant's prompt zeroed the slot's state
    assert rings["ahead"]["state_resets"] == rings["today"]["state_resets"]
    if kind == "lfm2":          # the garbage rows were routed, and counted
        assert rings["ahead"]["moe_rows_per_expert"] > 0


# every host-side reader or freer of the pool, and every end of the loop
def _scan_ahead(engine, queue, **live):
    """A live stream with scan N harvested and N+1 in flight, issued ahead;
    returns (the request, its slot)."""
    req = _live_stream(engine, queue, **live)
    for _ in range(8):          # a host late at first: today's order first
        engine._iterate()
        if engine._issued_turn is not None:
            break
    assert engine._issued_turn is not None and engine._issued_turn.ahead
    (slot,) = [s for s in engine._slots if s.request is req]
    return req, slot


def _no_token_lost(lm, req, slot, at_least):
    """What the slot holds is the reference's prefix, the scan that was in
    flight included."""
    assert len(slot.generated) >= at_least
    assert slot.generated == _reference(lm, req)[:len(slot.generated)]


@pytest.mark.parametrize("entry", [
    "stop", "abort_active", "release_buffers", "run_until_idle_timeout",
    "_drain_prefill", "_pump_prefill", "_step", "_service_fabric"])
@pytest.mark.parametrize("host", ["waits", "late_then_waits", "alternating"])
def test_an_entry_point_entered_with_a_scan_ahead_completes_it_first(
        lm, entry, host):
    engine, queue = _engine(lm, host=host, ttft_horizon=2)
    req, slot = _scan_ahead(engine, queue, new=30)
    held = len(slot.generated)           # N harvested; N+1 (2 more) in flight
    generated = slot.generated           # the list survives the slot's reset
    late = None
    if entry == "stop":
        engine.stop()
    elif entry == "abort_active":
        engine.abort_active(RuntimeError("shutdown"))
        with pytest.raises(RuntimeError):
            req.future.result(timeout=5)
        assert engine._allocator.free_pages == engine.num_pages
    elif entry == "release_buffers":
        engine.release_buffers()
        assert engine._cache is None
    elif entry == "run_until_idle_timeout":
        with pytest.raises(TimeoutError):
            engine.run_until_idle(timeout_s=0.0)
    elif entry == "_drain_prefill":
        late = _request(queue, engine.model.name, list(range(1, 30)), 5)
        engine._admit()
        engine._drain_prefill()
        assert engine.active_slots == 2
    elif entry == "_pump_prefill":
        late = _request(queue, engine.model.name, list(range(1, 13)), 5)
        engine._admit()
        assert engine._pump_prefill() == 16 and engine.active_slots == 2
    elif entry == "_step":               # a colocation executor's quantum
        engine._step()
        held += 2
    elif entry == "_service_fabric":
        seen = []
        assert engine.request_migration(
            req.request_id,
            lambda parcel: seen.append(_nothing_in_flight(engine)))
        engine._service_fabric()      # by hand: it completes the scan first
        assert seen == [True] and not engine._fabric_pending()
    assert _nothing_in_flight(engine)
    assert len(generated) >= held + 2
    assert generated == _reference(lm, req)[:len(generated)]
    if entry in ("abort_active", "release_buffers"):
        return
    engine.run_until_idle(timeout_s=300)
    for r in filter(None, (req, late)):
        assert r.future.result(timeout=5).tokens == _reference(lm, r)
    _settled(engine)


def test_a_migration_asked_for_with_a_scan_ahead_freezes_a_harvested_stream(
        lm):
    engine, queue = _engine(lm, ttft_horizon=2)
    req, slot = _scan_ahead(engine, queue, new=30)
    held, seen = len(slot.generated), []

    def deliver(parcel):
        seen.append((_nothing_in_flight(engine), list(parcel.generated),
                     parcel.cache_len))
        return False                  # refused: the stream decodes on here

    assert engine.request_migration(req.request_id, deliver)
    engine._iterate()                 # the scan in flight is completed ...
    assert not seen and engine._issued_turn is None
    engine._iterate()                 # ... and the fabric served with none
    ((quiet, generated, cache_len),) = seen
    assert quiet and len(generated) == held + 2
    assert generated == _reference(lm, req)[:len(generated)]
    assert cache_len == 4 + len(generated) - 1
    engine.run_until_idle(timeout_s=300)
    assert req.future.result(timeout=5).tokens == _reference(lm, req)
    _settled(engine)


def test_a_parcel_or_a_spill_reads_no_page_under_a_scan_in_flight(
        lm, monkeypatch):
    engine, queue = _engine(lm, num_slots=2, max_len=384,
                            prompt_buckets=[128], prefix_cache_size=16,
                            host_spill_pages=16, ttft_horizon=2)
    name = engine.model.name
    first = _request(queue, name, list(range(1, 257)), 3)
    engine.run_until_idle(timeout_s=600)    # publishes two prefix pages
    first.future.result(timeout=5)
    key = next(iter(engine.paged_prefix._entries))
    reads = []
    read = engine._read_pages

    def read_checked(page_ids):
        out = read(page_ids)
        reads.append(_nothing_in_flight(engine))
        return out

    monkeypatch.setattr(engine, "_read_pages", read_checked)
    engine.host_spill._read = read_checked
    req, slot = _scan_ahead(engine, queue, new=30)
    parcels = []
    assert engine.request_prefix_push(key, lambda p: parcels.append(p) or True)
    engine._iterate()
    engine._iterate()
    assert reads == [True] and parcels[0].n_pages >= 1
    assert engine.pushes_out == 1
    # a reclaim under pool pressure spills the entry's pages: by hand, with
    # a scan ahead again
    while engine._issued_turn is None or not engine._issued_turn.ahead:
        engine._iterate()
    held = len(slot.generated)
    assert engine._reclaim_cache_pins()
    assert reads == [True, True] and key in engine.host_spill
    assert len(slot.generated) == held + 2     # the scan was harvested
    engine.run_until_idle(timeout_s=300)
    assert req.future.result(timeout=5).tokens == _reference(lm, req)


def test_the_loop_ends_with_nothing_in_flight_and_no_token_lost(lm):
    engine, queue = _engine(lm, ttft_horizon=2)
    engine.warmup()
    req = _live_stream(engine, queue, new=10_000)      # never ends itself
    (slot,) = [s for s in engine._slots if s.request is req]
    engine.start()
    for _ in range(2000):
        if sum(1 for t in list(engine.turns) if t.ahead) >= 5:
            break
        threading.Event().wait(0.005)
    engine.stop(timeout_s=60)
    assert engine._thread is None and _nothing_in_flight(engine)
    ring = [t for t in engine.turns if t.kind == "turn"]
    assert sum(1 for t in ring if t.ahead) >= 5
    # every scan dispatched was harvested: the slot holds them all
    n = len(slot.generated)
    assert n == 1 + sum(t.substeps for t in ring)
    assert slot.generated == teacher_forced(
        lm[0], lm[1], req.payload["tokens"], n)
    engine.abort_active(RuntimeError("shutdown"))
    assert engine._allocator.free_pages == engine.num_pages


# a late host: a committed scan hides nothing, so today's order runs
def test_the_share_of_fetches_found_ready_holds_todays_order_above_its_limit(
        lm):
    """The rule's arithmetic: a moving average over about 128 scan fetches;
    above 1/32 no scan is issued ahead. Five late fetches in a row cross it
    from nothing, 27 that waited bring it back under."""
    assert (decode_mod.AHEAD_READY_MAX, decode_mod._READY_TURNS) == (
        1 / 32, 128)
    engine, queue = _engine(lm, host=None, ttft_horizon=2)
    req = _live_stream(engine, queue, new=10_000)
    with engine._phase("rdb.engine.turn") as ph:    # a scan in flight
        engine._issued_turn = engine._issue_turn(ph, None)
    assert engine._ready_share == 0.0

    def fetches_until(allowed, ready):
        n = 0
        while (engine._horizon_ahead() == 2) != allowed:
            engine._note_ready(ready)
            n += 1
        return n

    assert fetches_until(False, ready=True) == 5
    assert engine._ready_share == pytest.approx(1 - (127 / 128) ** 5)
    assert fetches_until(True, ready=False) == 27
    assert fetches_until(False, ready=True) == 1    # it stands at the limit
    for _ in range(2000):                           # a host that stays late
        engine._note_ready(True)
    assert engine._ready_share == pytest.approx(1.0, abs=1e-6)
    assert fetches_until(True, ready=False) == 442  # 128 ln 32: ~3 s of turns
    engine._drain_issued()
    assert req.future.done() is False


def test_a_host_that_is_always_late_runs_todays_order(lm, monkeypatch):
    """The fetch's OWN observation (nothing told): the host comes to every
    fetch behind which a scan stands after its result is there (made so: the
    scan in flight is waited out before the one behind it is dispatched).
    Five such fetches and today's order runs; it is tried again only when the
    share has fallen under its limit (27 fetches that waited, or more). The
    tokens are the reference's, and a request that arrives is served."""
    engine, queue = _engine(lm, host=None, ttft_horizon=2)
    issue = engine._issue_turn

    def issue_late(ph, horizon, ahead_of=None):
        if ahead_of is not None:
            ahead_of.packed.block_until_ready()
        return issue(ph, horizon, ahead_of=ahead_of)

    monkeypatch.setattr(engine, "_issue_turn", issue_late)
    req = _live_stream(engine, queue, new=90)
    for _ in range(20):
        engine._iterate()
    late = _request(queue, engine.model.name, list(range(1, 13)), 6)
    engine.run_until_idle(timeout_s=300)
    for r in (req, late):
        assert r.future.result(timeout=5).tokens == _reference(lm, r)
    scans = [t for t in engine.turns if t.kind == "turn"]
    at = [i for i, t in enumerate(scans) if t.ahead]
    # every scan fetched with one committed behind it was found ready
    assert all(scans[i - 1].ready_at_fetch for i in at)
    # the first five at once, then at most one a 27 turns
    assert at[:5] == [1, 2, 3, 4, 5] and len(at) <= 7
    assert all(b - a >= 27 for a, b in zip(at[4:], at[5:]))
    _settled(engine)


def test_a_host_whose_fetches_wait_keeps_issuing_ahead(lm):
    engine, queue = _engine(lm, ttft_horizon=2)
    seen = []
    note = engine._note_ready
    engine._note_ready = lambda ready: (seen.append(ready), note(ready))[1]
    req = _live_stream(engine, queue, new=40)
    engine.run_until_idle(timeout_s=300)
    assert req.future.result(timeout=5).tokens == _reference(lm, req)
    scans = [t for t in engine.turns if t.kind == "turn"]
    assert len(seen) == len(scans)          # noted once a scan's fetch
    assert engine._ready_share == 0.0
    assert engine.turn_summary()["scans_issued_ahead_share"] > 0.8
