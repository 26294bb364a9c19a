"""The median over the window's parts of a per-part percentile of a
per-request latency (``ttft_ms`` from the due time, or ``tpot_ms``): the
steadier statistic that one stall in the window cannot move
(``stats.parts_percentile``), beside the end-to-end tail over all requests,
which it can."""

from benchmark import stats


def read(ctx, field: str, q: float):
    run, traffic = ctx["run"], ctx["traffic"]
    if "parts" not in traffic or not ctx["records"]:
        return None
    return stats.parts_percentile(
        ctx["records"], field, q, run["window_s"], int(traffic["parts"]),
        run["observed_until_s"])["value"]
