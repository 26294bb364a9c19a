"""What the engines' turn ring says of expert routing in the window's part
before the traced sub-window (``engine_turns``'s part and the program's own
``summarize_turns``): ``metric`` is

- ``"moe_rows_per_expert"``: real routed rows over experts hit, summed over
  layers and substeps: how many rows share one read of an expert's weights;
- ``"moe_imbalance"``: the most rows one expert took in one layer of one
  substep, over that mean.

Several engines: the mean. ``None`` where the program's summary has no such
key (a dense model; the parent of the PR that brought the counters).
Printed once a run: the expert path each program took, as the program's
``snapshot()["moe"]`` tells it."""

from benchmark.readers.engine_turns import _part

_SAID = "_moe_paths_said"


def read(ctx, metric: str):
    if metric not in ("moe_rows_per_expert", "moe_imbalance"):
        raise ValueError(f"unknown metric {metric!r}")
    engines, until_s = _part(ctx)
    if not engines or until_s <= 0:
        return None
    if _SAID not in ctx:
        ctx[_SAID] = True
        for i, eng in enumerate(ctx["engines"]):
            for line in (eng.snapshot().get("moe") or {}).get("paths", []):
                print(f"moe: engine {i}: {line}", flush=True)
    vals = [s.get(metric) for s in engines]
    if any(v is None for v in vals) or any(s["dropped"] for s in engines):
        return None
    return sum(vals) / len(vals)
