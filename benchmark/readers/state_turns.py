"""What the engines' turn ring says of a model's conv state a slot in the
window's part before the traced sub-window (``engine_turns``'s part and the
program's own ``summarize_turns``): ``metric`` is

- ``"conv_state_carried_chunks_pct"``: of the prompt chunks run (the rows of
  the chunk groups dispatched), the share, percent, that began from the
  state an earlier chunk of their train left in the slot
  (``Turn.state_carries``); the others began their prompt, and the program
  zeroed their slots' states (``Turn.state_resets``). A property of the
  traffic (a prompt of n chunks carries n - 1 times): the chunk program's
  hand-over runs as often as this says.

Several engines: the mean. ``None`` where the program's summary has no such
key (a model without conv layers; the parent of the PR that brought the
counter), no chunk ran in the part, or the ring wrapped."""

from benchmark.readers.engine_turns import _part


def read(ctx, metric: str):
    if metric != "conv_state_carried_chunks_pct":
        raise ValueError(f"unknown metric {metric!r}")
    engines, until_s = _part(ctx)
    if not engines or until_s <= 0:
        return None
    vals = [s.get("state_carried_chunk_share") for s in engines]
    if any(v is None for v in vals) or any(s["dropped"] for s in engines):
        return None
    for i, s in enumerate(engines):
        print(f"state: engine {i}: {s['state_resets']} chunks began a "
              f"prompt (state zeroed), {s['state_carries']} began from a "
              "carried state", flush=True)
    return 100.0 * sum(vals) / len(vals)
