"""``sparse_select``'s share (the index scores and the exact top-k, as a
share of the device's busy time, percent) for a selection over a LATENT
pool, with the decode program's REMATERIALISED views of the index keys
beside it.

A decode step gathers every slot's index keys through its table, once a
layer (``fusion_bf16_5760_128_128_`` in ``glm5-longdoc-batch``, which
``sparse_select``'s ``ops`` take by that shape). XLA's rematerialisation
pass computes three of the five layers' views a second time and renames them
``fusion.<n>.remat``, with no shape for ``trace_reduce.stable_name`` to keep.
``remat`` takes them by that name INSIDE the programs its ``module`` matches
alone: in the decode program nothing else is a rematerialised plain fusion
(``tests/test_tpu_lowering.py`` holds that on the compiled program), while a
chunk program has a dozen (norms, the experts' gather, the embedding).
``None`` where ``sparse_select`` reads nothing."""

from benchmark.readers import sparse_select


def read(ctx, ops, loops, remat):
    share = sparse_select.read(ctx, ops, loops)
    if share is None:
        return None
    tr = ctx["trace"]
    secs, count = tr.op_time(remat["op"], remat["module"])
    print(f"sparse: index keys gathered again (rematerialised, "
          f"{remat['module']}): {count:.0f} operations, "
          f"{secs * 1000.0:.1f} ms", flush=True)
    return share + 100.0 * secs / tr.busy_s()
