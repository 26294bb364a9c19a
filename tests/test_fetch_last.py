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
"""

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


def _engine(lm, **kw):
    model, params = lm
    queue = RequestQueue(model.name, max_len=256)
    opts = dict(num_slots=4, max_len=MAX_LEN, prompt_buckets=[8, 16],
                eos_token_id=None, default_max_new_tokens=8,
                decode_horizon=4, page_size=128)
    opts.update(kw)
    return DecodeEngine(model, params, queue, **opts), queue


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
        lm):
    engine, queue = _engine(lm)
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


def test_an_arrival_during_a_scan_is_issued_behind_it_and_registered_after(
        lm, monkeypatch):
    engine, queue = _engine(lm)
    live = _live_stream(engine, queue)
    issue = engine._issue_turn
    arrivals = []

    def issue_then_arrive(ph, horizon):
        out = issue(ph, horizon)
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
