"""What the engines' turn ring says of a selecting model's decode scans in
the window's part before the traced sub-window (``engine_turns``'s part and
the program's own ``summarize_turns``): ``metric`` is

- ``"kv_selected_rows_pct"``: of the cached rows a decode query could attend
  (slots x selecting layers x substeps x the slot's length), the share,
  percent, its indexer keeps (``Turn.kv_rows_selected`` over
  ``Turn.kv_rows_live``, counted by the engine from its slots' lengths as it
  dispatches a scan). ``100 -`` it is what a form that reads the selected
  rows only could save over one that reads every live row.

Several engines: the mean. ``None`` where the program's summary has no such
key (a model without an indexer; the parent of the PR that brought the
counter) or the ring wrapped."""

from benchmark.readers.engine_turns import _part


def read(ctx, metric: str):
    if metric != "kv_selected_rows_pct":
        raise ValueError(f"unknown metric {metric!r}")
    engines, until_s = _part(ctx)
    if not engines or until_s <= 0:
        return None
    vals = [s.get("kv_selected_row_share") for s in engines]
    if any(v is None for v in vals) or any(s["dropped"] for s in engines):
        return None
    return 100.0 * sum(vals) / len(vals)
