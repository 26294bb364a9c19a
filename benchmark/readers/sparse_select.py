"""Device time of a selecting layer's index scan and top-k (the operations
under ``jax.named_scope("sparse_index")`` and ``("sparse_select")`` in
``ops/sparse_attention.py``) as a share of the device's busy time, percent.

The TPU's trace carries no scope. Two arguments find the operations:

- ``loops``: a pattern of the WHOLE event name (on the TPU an operation's
  HLO line). The k-th largest key is found by a ``while`` of 32 counts
  whose carried tuple holds unsigned keys (``u32[..]``: nothing else in
  these programs loops over unsigned values); such a loop is taken WHOLE,
  its body's operations and its own control, whatever XLA calls the counts
  inside it.
- ``ops``: patterns of ``trace_reduce.stable_name`` for the operations
  outside those loops (the index keys' view, the scores, the order keys,
  the masks), by the position axis of a slot's table in their result's
  shape; an operation inside a loop already taken is not counted twice.

Every distinct name taken is printed with its count and time, the largest
first, so that a reader of the log sees what the share is made of. The
patterns are checked against the cell's programs as COMPILED for the chip
(``tests/test_tpu_lowering.py``; no trace of a run with the selection off
was taken): with ``index_topk`` as large as the cache no operation of the
selection is left, no loop matches and ``ops`` take a chunk's staircase
mask alone. ``None`` without a trace or where nothing matched (a model
without an indexer: none of these shapes or loops exists there)."""

import bisect
import re

from benchmark.trace_reduce import stable_name

LISTED = 12     # names printed


def read(ctx, ops, loops):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    busy = tr.busy_s()
    loop_rx, rx = re.compile(loops), [re.compile(p) for p in ops]
    taken = {}                                   # name -> [count, seconds]

    def take(name, secs):
        row = taken.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += secs

    lo, hi = tr.window
    whole = 0
    for d in tr.devices:
        spans = sorted((ev.start, ev.end) for ev in tr.in_window(tr.devices[d])
                       if loop_rx.search(ev.name))
        whole += len(spans)
        for s, e in spans:
            take("<a loop over unsigned keys, whole>",
                 max(min(e, hi) - max(s, lo), 0.0))
        starts = [s for s, _ in spans]
        for ev, t in tr.op_self_times(d):
            i = bisect.bisect_right(starts, ev.start) - 1
            if i >= 0 and ev.start < spans[i][1]:
                continue                         # inside a loop taken whole
            name = stable_name(ev)
            if any(r.search(name) for r in rx):
                take(name, t)
    chips = len(tr.devices)
    secs = sum(t for _, t in taken.values()) / chips
    if secs <= 0 or busy <= 0:
        return None
    print(f"sparse: index scan and top-k: {secs * 1000.0:.1f} ms of "
          f"{busy * 1000.0:.1f} busy on the device in the trace; the k-th "
          f"key's search as {whole / chips:.0f} loops taken whole", flush=True)
    for name, (n, t) in sorted(taken.items(), key=lambda kv: -kv[1][1])[
            :LISTED]:
        print(f"sparse:   {name}: {n / chips:.0f} operations, "
              f"{t / chips * 1000.0:.1f} ms", flush=True)
    return 100.0 * secs / busy
