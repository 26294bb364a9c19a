"""What the engines' turn ring says of the FULL layers' pages of a model
whose KV state is held by layer kind, in the window's part before the traced
sub-window (``kv_turns``'s part and the program's own ``summarize_turns``):
``metric`` is

- ``"kv_full_pages_live_pct"``: of the page-table entries a full layer's
  decode scan could walk (slots x entries a slot x substeps), the share,
  percent, that held a position a slot attends (``Turn.kv_full_pages_live``,
  counted by the engine from its slots' lengths as it dispatches a scan; an
  idle slot counts its first page). The window layers' ring is as many
  pages a slot whatever the traffic and is no counter. A property of the
  traffic: the kernel's time on the full layers follows it.

Several engines: the mean. ``None`` where the program's summary has no such
key (a model with one pool for every layer; the parent of the PR that
brought the counter) or the ring wrapped."""

from benchmark.readers.engine_turns import _part


def read(ctx, metric: str):
    if metric != "kv_full_pages_live_pct":
        raise ValueError(f"unknown metric {metric!r}")
    engines, until_s = _part(ctx)
    if not engines or until_s <= 0:
        return None
    vals = [s.get("kv_full_live_page_share") for s in engines]
    if any(v is None for v in vals) or any(s["dropped"] for s in engines):
        return None
    return 100.0 * sum(vals) / len(vals)
