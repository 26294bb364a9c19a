"""A percentile of one part of the engine's own split of the time to first
token (``DecodeEngine._ttft_parts``: one record an admission since the
window began, stamped on the engine thread). ``part`` is one of:

- ``"queue_wait"``: arrival at the engine -> taken off its queue (no free
  slot, or a scan in flight);
- ``"first_token"``: taken off the queue -> first token (prefill, and the
  turn that emits it);

in ms. Several engines: the records of all of them pooled, so a replica the
router starves or floods weighs by its admissions. The engine keeps its
newest 1,024 admissions: a replica that admits more in one window is read
over those. ``None`` where the program keeps no such record."""

from benchmark import stats

_PARTS = {"queue_wait": 0, "first_token": 2}


def read(ctx, part: str, q: float):
    if part not in _PARTS:
        raise ValueError(f"unknown part {part!r}")
    vals = [rec[_PARTS[part]] for eng in ctx["engines"]
            for rec in list(getattr(eng, "_ttft_parts", ()))]
    return stats.percentile(vals, q) if vals else None
