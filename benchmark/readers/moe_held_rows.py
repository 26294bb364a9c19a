"""Of all the real (token, expert) pairs the router chose in the window's
part before the traced sub-window (``engine_turns``'s part and the program's
own ``summarize_turns``), the share, percent, that landed on the experts
HELD by this replica (``Turn.moe_rows`` over ``Turn.moe_pairs``): one rank's
share of an expert-parallel layer does the work of those rows only. An even
router gives a rank held / all of the experts (16 of 128: 12.5%); a seeded
router need not be even.

Several engines: the mean. ``None`` where the program's summary has no such
key (a dense model, or the parent of the PR that brought the counter) or the
ring wrapped."""

from benchmark.readers.engine_turns import _part


def read(ctx):
    engines, until_s = _part(ctx)
    if not engines or until_s <= 0:
        return None
    vals = [s.get("moe_held_rows_share") for s in engines]
    if any(v is None for v in vals) or any(s["dropped"] for s in engines):
        return None
    return 100.0 * sum(vals) / len(vals)
