"""What the engines' turn ring says of the paged pool's page tables in the
window's part before the traced sub-window (``engine_turns``'s part and the
program's own ``summarize_turns``): ``metric`` is

- ``"kv_live_pages_pct"``: of the page-table entries the decode scans' grid
  walks (slots x entries a slot x substeps), the share, percent, that held
  a position a slot could attend (``Turn.kv_pages_live``, counted by the
  engine from its slots' lengths as it dispatches a scan; an idle slot
  counts its first page). A property of the traffic: the paged kernel's
  time should follow it, and does not where it walks dead entries at a live
  one's cost.

Several engines: the mean. ``None`` where the program's summary has no such
key (a slab engine; the parent of the PR that brought the counter) or the
ring wrapped."""

from benchmark.readers.engine_turns import _part


def read(ctx, metric: str):
    if metric != "kv_live_pages_pct":
        raise ValueError(f"unknown metric {metric!r}")
    engines, until_s = _part(ctx)
    if not engines or until_s <= 0:
        return None
    vals = [s.get("kv_live_page_share") for s in engines]
    if any(v is None for v in vals) or any(s["dropped"] for s in engines):
        return None
    return 100.0 * sum(vals) / len(vals)
