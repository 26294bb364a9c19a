"""Run one benchmark cell traced on the chip and write EVERY device
operation of the traced window, by program, beside the cell's own output:
the names a per-layer metric's pattern has to take (the TPU's trace carries
no ``jax.named_scope``: an operation's event is its HLO line, reduced by
``benchmark/trace_reduce.py::stable_name``).

    chiprun -- python3 -m tools.record_cell_ops --workload xing-longdoc-batch \\
        --seed 1234567 --out chiprun_out/xing_ops.txt

The cell runs as ``python3 -m benchmark.run --trace 1`` runs it (the same
``run_cell``); the table is taken as the harness loads its trace, before
any metric reads it. Lines: ``ms count program stable_name``, largest first. A
test then holds the metric's pattern to the recorded names
(``benchmark/tests/test_kind_readers.py`` is the example)."""

from __future__ import annotations

import argparse
import bisect
import json
import sys
from pathlib import Path


def table(trace) -> list:
    """[(seconds, count, program, stable name)] of the first chip's
    operations, a program being the module whose span holds the
    operation's start ("" between programs)."""
    from benchmark import trace_reduce

    d = sorted(trace.devices)[0]
    mods = sorted(trace.in_window(trace.modules.get(d, [])),
                  key=lambda e: e.start)
    starts = [e.start for e in mods]
    acc = {}
    for ev, t in trace.op_self_times(d):
        i = bisect.bisect_right(starts, ev.start) - 1
        prog = (trace_reduce.stable_name(mods[i])
                if i >= 0 and ev.start < mods[i].end else "")
        key = (prog, trace_reduce.stable_name(ev))
        secs, n = acc.get(key, (0.0, 0))
        acc[key] = (secs + t, n + 1)
    return sorted(((s, n, p, name) for (p, name), (s, n) in acc.items()),
                  reverse=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    from benchmark import run, trace_reduce

    from_dir = trace_reduce.Trace.from_dir.__func__

    def recording(cls, trace_dir: str):
        trace = from_dir(cls, trace_dir)
        out = Path(a.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        with out.open("w") as f:
            f.write(f"# {a.workload}, traced run, seed {a.seed}: every "
                    "device operation in the trace's window\n"
                    f"# busy_s {trace.busy_s()} window_s "
                    f"{trace.window_s()}\n"
                    "# ms count program stable_name\n")
            for secs, count, prog, name in table(trace):
                f.write(f"{secs * 1000.0:.3f} {count} {prog or '-'} "
                        f"{name}\n")
        return trace

    trace_reduce.Trace.from_dir = classmethod(recording)
    run.use_checkout_cache()
    result = run.run_cell(run.Cell(a.workload), a.seed, a.seconds, True,
                          require_tpu=True)
    print(json.dumps(result))
    return 0 if result["correct"] and result["failed"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
