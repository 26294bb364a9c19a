"""Traffic generation and the two load loops (the yardstick; no program import).

One general generator reads a traffic file (``traffic/<mix>.json``):

- ``loop``: ``"open"`` (arrivals on a schedule, latency from the DUE time)
  or ``"closed"`` (``clients`` callers, each sending its next request when
  the previous one completed).
- ``arrivals``: ``{"process": "poisson", "rate_rps": r}``.
- ``prompt_len`` / ``output_len``: ``{"dist": "loguniform"|"uniform"|"fixed",
  "lo": a, "hi": b}``.

Every seed offers the same work: the sizes of a window are the stated
distribution's own quantiles (one multiset for every seed). In an open loop
``--seed`` draws the arrival times over the whole window, the order of the
sizes and the token ids; in a closed loop the token ids ALONE (the order is
the traffic file's ``base_seed``'s, the same in every run). It draws the
weights too, unless the configuration file states one draw
(``weights_seed``, ``run.py::seeded_params``).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

Submit = Callable[[Dict[str, Any]], Tuple[Any, Any]]  # -> (stream, future)


def _quantile_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of the stated
    distribution: the distribution itself, with no draw's luck in it."""
    dist, lo, hi = spec["dist"], int(spec["lo"]), int(spec["hi"])
    u = (np.arange(n) + 0.5) / n
    if dist == "fixed" or lo == hi:
        return np.full(n, lo, dtype=np.int64)
    if dist == "uniform":
        x = lo + u * (hi + 1 - lo)
    elif dist == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.floor(x).astype(np.int64), lo, hi)


def build_requests(traffic: Dict[str, Any], vocab_size: int, seed: int,
                   seconds: float) -> List[Dict[str, Any]]:
    """The run's requests: ``due`` (open loop; seconds from the window's
    start), ``tokens`` and ``max_new_tokens``.

    Open loop: ``round(rate x seconds)`` arrivals, their times drawn from
    ``seed`` uniformly over the whole window and sorted: a Poisson process
    given its count. Prompt and answer lengths are the quantiles of their
    distributions, each put in an order drawn from ``seed``. So every seed
    offers the same number of requests and the same multiset of sizes (the
    seed does not change the work), while which requests arrive together,
    and when, is the seed's.

    Closed loop: ``set_size`` requests of the same quantile sizes in an
    order drawn from the traffic file's ``base_seed``, the same for every
    run, replayed round and round by the clients: the window covers a
    seed-independent stretch of one fixed set."""
    run = np.random.default_rng(int(seed))
    if traffic["loop"] == "open":
        if traffic["arrivals"]["process"] != "poisson":
            raise ValueError("only poisson arrivals are implemented")
        n = max(int(round(float(traffic["arrivals"]["rate_rps"]) * seconds)), 1)
        due = np.sort(run.uniform(0.0, seconds, size=n))
        order = run
    else:
        n = int(traffic["set_size"])
        due = np.zeros(n)
        order = np.random.default_rng(int(traffic["base_seed"]))
    p_len = order.permutation(_quantile_lengths(traffic["prompt_len"], n))
    o_len = order.permutation(_quantile_lengths(traffic["output_len"], n))
    tokens = run.integers(1, vocab_size, size=int(p_len.sum()))
    out, at = [], 0
    for i in range(n):
        L = int(p_len[i])
        out.append({
            "due": float(due[i]),
            "tokens": tokens[at:at + L].tolist(),
            "max_new_tokens": int(o_len[i]),
        })
        at += L
    return out


class _Watch:
    """Client-side stamps of one request. ``on_chunk`` runs on the engine's
    thread once per token: it stamps the clock and nothing else."""

    __slots__ = ("rec", "t0", "stamps", "done")

    def __init__(self, rec: Dict[str, Any], t0: float,
                 done: Optional[Callable[["_Watch"], None]]) -> None:
        self.rec, self.t0, self.stamps, self.done = rec, t0, [], done

    def on_chunk(self, _chunk: Any) -> None:
        self.stamps.append(time.monotonic())

    def on_close(self, err: Optional[BaseException]) -> None:
        rec, s = self.rec, self.stamps
        rec["n_out"] = len(s)
        rec["first"] = s[0] - self.t0 if s else None
        rec["last"] = s[-1] - self.t0 if s else None
        rec["stamps"] = [t - self.t0 for t in s]
        rec["error"] = repr(err) if err is not None else None
        rec["ok"] = err is None and len(s) == rec["want_out"]
        rec["closed"] = time.monotonic() - self.t0
        if self.done is not None:
            self.done(self)


def _send(submit: Submit, req: Dict[str, Any], rec: Dict[str, Any],
          t0: float, done: Optional[Callable[[_Watch], None]]) -> None:
    watch = _Watch(rec, t0, done)
    t_send = time.monotonic()
    rec["sent"] = t_send - t0
    try:
        stream, _future = submit(
            {"tokens": req["tokens"],
             "max_new_tokens": req["max_new_tokens"]})
    except Exception as e:  # noqa: BLE001 — a refusal is a failed request
        rec["submit_ms"] = (time.monotonic() - t_send) * 1000.0
        watch.on_close(e)
        return
    rec["submit_ms"] = (time.monotonic() - t_send) * 1000.0
    stream.subscribe(watch.on_chunk, watch.on_close)


def _new_record(req: Dict[str, Any], due: float) -> Dict[str, Any]:
    return {"due": due, "sent": None, "first": None, "last": None,
            "n_out": 0, "want_out": req["max_new_tokens"],
            "prompt_len": len(req["tokens"]), "ok": False, "error": None,
            "stamps": [], "submit_ms": None, "closed": None}


def _drain(records: List[Dict[str, Any]], t0: float,
           timeout_s: float) -> float:
    """Wait until every sent request has closed or ``timeout_s`` passed;
    returns the time observation ended (seconds from the window start)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(r["closed"] is not None for r in records
               if r["sent"] is not None):
            break
        time.sleep(0.02)
    return time.monotonic() - t0


def run_open_loop(submit: Submit, requests: List[Dict[str, Any]],
                  seconds: float, drain_timeout_s: float,
                  on_start: Optional[Callable[[float], None]] = None,
                  ) -> Dict[str, Any]:
    """Send each request at its due time from ONE thread (this one), never
    waiting for an answer; then wait for what is in flight. Every request
    due in the window is in the result, answered or not."""
    records = [_new_record(r, r["due"]) for r in requests]
    t0 = time.monotonic()
    if on_start is not None:
        on_start(t0)
    for req, rec in zip(requests, records):
        wait = t0 + req["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        _send(submit, req, rec, t0, None)
    left = t0 + seconds - time.monotonic()
    if left > 0:
        time.sleep(left)
    observed_until = _drain(records, t0, drain_timeout_s)
    return {"t0": t0, "window_s": seconds, "records": records,
            "observed_until_s": observed_until}


def run_closed_loop(submit: Submit, requests: List[Dict[str, Any]],
                    seconds: float, clients: int, drain_timeout_s: float,
                    on_start: Optional[Callable[[float], None]] = None,
                    ) -> Dict[str, Any]:
    """``clients`` callers replay the fixed set in order, round and round,
    each sending its next request when its last one closed; one dispatcher
    thread (this one) does all the sending. Stops sending at the window's
    end; a request's ``due`` is the time it was sent."""
    finished: "queue.Queue[_Watch]" = queue.Queue()
    records: List[Dict[str, Any]] = []
    t0 = time.monotonic()
    if on_start is not None:
        on_start(t0)
    end = t0 + seconds
    nxt = 0

    def send_next() -> None:
        nonlocal nxt
        req = requests[nxt % len(requests)]
        nxt += 1
        rec = _new_record(req, time.monotonic() - t0)
        records.append(rec)
        _send(submit, req, rec, t0, finished.put)

    for _ in range(clients):
        send_next()
    while True:
        left = end - time.monotonic()
        if left <= 0:
            break
        try:
            finished.get(timeout=left)
        except queue.Empty:
            break
        if time.monotonic() < end:
            send_next()
    observed_until = _drain(records, t0, drain_timeout_s)
    return {"t0": t0, "window_s": seconds, "records": records,
            "observed_until_s": observed_until}


def run_traffic(submit: Submit, traffic: Dict[str, Any],
                requests: List[Dict[str, Any]], seconds: float,
                on_start: Optional[Callable[[float], None]] = None,
                ) -> Dict[str, Any]:
    drain = float(traffic.get("drain_timeout_s", 30.0))
    if traffic["loop"] == "open":
        return run_open_loop(submit, requests, seconds, drain, on_start)
    if traffic["loop"] == "closed":
        return run_closed_loop(submit, requests, seconds,
                               int(traffic["clients"]), drain, on_start)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


class Heartbeat:
    """A thread that sleeps ``period_s`` over and over and records by how
    much each wake-up overshot: a host stall (a starved core, a long GIL
    hold) shows as one large overshoot with its time."""

    def __init__(self, period_s: float = 0.005) -> None:
        self.period_s = period_s
        self.overshoots: List[Tuple[float, float]] = []  # (at, overshoot_s)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="bench-heartbeat", daemon=True)

    def _run(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(self.period_s):
            now = time.monotonic()
            self.overshoots.append((now, now - last - self.period_s))
            last = now

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


class GcWatch:
    """Garbage-collector pauses, measured through ``gc.callbacks`` (the
    collector is left on: the program runs with it)."""

    def __init__(self) -> None:
        self.pauses: List[Tuple[float, float, int]] = []  # (at, s, gen)
        self._t = 0.0

    def _cb(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t = time.monotonic()
        else:
            now = time.monotonic()
            self.pauses.append((now, now - self._t, info.get("generation", -1)))

    def start(self) -> None:
        import gc

        gc.callbacks.append(self._cb)

    def stop(self) -> None:
        import gc

        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)
