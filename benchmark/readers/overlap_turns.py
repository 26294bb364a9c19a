"""What the engines' turn ring says of the ORDER of their dispatches in the
window's part before the traced sub-window (``engine_turns``'s part and the
program's own ``summarize_turns``): ``metric`` is

- ``"overlapped_dispatch_pct"``: of the part's dispatches (decode scans and
  chunk groups), the share, percent, made while a program issued before was
  not yet known done (``Turn.queued_behind`` above 0: the engine fetches a
  scan LAST, after it has issued the next chunk group behind it, and a
  chunk that ends no prompt is never fetched). The host's work for such a
  dispatch hid behind a running program; for the others the device was
  known empty, and ``host_gap_share_pct`` counts the host's time before
  them.

Several engines: the mean. ``None`` where the program's summary has no such
key (the parent of the PR that brought the counter) or the ring wrapped."""

from benchmark.readers.engine_turns import _part


def read(ctx, metric: str):
    if metric != "overlapped_dispatch_pct":
        raise ValueError(f"unknown metric {metric!r}")
    engines, until_s = _part(ctx)
    if not engines or until_s <= 0:
        return None
    vals = [s.get("overlapped_dispatch_share") for s in engines]
    if any(v is None for v in vals) or any(s["dropped"] for s in engines):
        return None
    return 100.0 * sum(vals) / len(vals)
