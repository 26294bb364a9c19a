"""Where the deploy's seconds went, from INSIDE the program: the start-up
spans every engine's ``snapshot()["startup"]`` (``startup_summary()``)
lists (``rdb.startup.*``:
deploy > replica > engine_build, warmup > warmup.program, register; on
``time.monotonic()``, the clock of the harness's ``deploy_warmup_s``) and
what the compile ledger charged each (trace, lower, backend with the cache's
read inside it, hits and misses). ``part`` is one of:

- ``"trace_lower"``: seconds of ``trace_ms + lower_ms`` over the warmed
  programs' spans of all replicas: Python, paid at every start;
- ``"backend"``: seconds of ``backend_ms`` there: a cache read when warm,
  XLA and Mosaic when cold;
- ``"first_run"``: seconds of ``run_ms`` there: the rest of each program's
  span (the executable's load, the uploads, the first execution);
- ``"engine_build"``: seconds of the ``rdb.startup.engine_build`` spans;
- ``"unaccounted"``: the ``rdb.startup.deploy`` span less those four;
- ``"cache_hit_pct"``: the persistent cache's hits over hits and misses,
  percent, over every span of the deploy: 100 on a warm start, 0 on a first.

The five in seconds sum to the deploy span: the program's own
``startup_sums`` computes them, here over the rows of all engines (each
engine lists its own spans and the replica's and deploy's above them).
Prints a ``startup:`` line a span, indented by depth, with its self time
(what no child covers), and a table by program. ``None`` where the program
has no such spans (an engine without ``startup_summary``), the deploy span is
not among them, or, for the hit share, the cache was off."""

_CACHE = "_startup_parts"
SECONDS = ("trace_lower", "backend", "first_run", "engine_build",
           "unaccounted")


def _analyse(ctx):
    if _CACHE in ctx:
        return ctx[_CACHE]
    ctx[_CACHE] = None
    rows = {}
    for engine in ctx["engines"]:
        summary = getattr(engine, "startup_summary", None)
        if summary is None:
            return None
        rows.update((r["id"], r) for r in summary()["rows"])
    from ray_dynamic_batching_tpu.engine.decode import startup_sums

    rows = sorted(rows.values(), key=lambda r: r["start_ms"])
    sums = startup_sums(rows)
    if sums["deploy_s"] is None:
        return None
    # An engine's rows know its own subtree alone: over all engines' rows
    # a shared parent's self time is what NONE of the children covers.
    covered = {}
    for r in rows:
        covered[r["parent"]] = covered.get(r["parent"], 0.0) + r["dur_ms"]
    depth = {}
    for r in rows:      # a parent starts before its children
        depth[r["id"]] = depth.get(r["parent"], -1) + 1
        self_s = (r["dur_ms"] - covered.get(r["id"], 0.0)) / 1000.0
        what = " ".join(f"{k}={r[k]}" for k in
                        ("replica", "program", "key", "chips", "cache")
                        if r.get(k) not in (None, ""))
        print(f"startup: {'  ' * depth[r['id']]}{r['name']:<28s} "
              f"+{r['start_ms'] / 1000.0:8.3f} s {r['dur_ms'] / 1000.0:8.3f} s"
              f" (self {self_s:.3f}) {what}", flush=True)
    print("startup: by program: replica program key | span_s = trace + lower"
          " + backend (of it cache_read) + run | cache saved_s", flush=True)
    for r in rows:
        if "run_ms" not in r:
            continue
        ms = {k: r.get(k, 0.0) / 1000.0 for k in
              ("dur_ms", "trace_ms", "lower_ms", "backend_ms",
               "cache_read_ms", "run_ms", "saved_ms")}
        print(f"startup: by program: {r['replica']} {r['program']} "
              f"{r['key']} | {ms['dur_ms']:.3f} = {ms['trace_ms']:.3f} + "
              f"{ms['lower_ms']:.3f} + {ms['backend_ms']:.3f} "
              f"({ms['cache_read_ms']:.3f}) + {ms['run_ms']:.3f} | "
              f"{r.get('cache', 'off')} {ms['saved_ms']:.1f}", flush=True)
    out = {p: sums[f"{p}_s"] for p in SECONDS}
    looked = sums["cache_hits"] + sums["cache_misses"]
    out["cache_hit_pct"] = (100.0 * sums["cache_hits"] / looked
                            if looked else None)
    print("startup: " + " + ".join(f"{p} {out[p]:.3f}" for p in SECONDS)
          + f" = {sum(out[p] for p in SECONDS):.3f} s; rdb.startup.deploy "
          f"{sums['deploy_s']:.3f} s; cache hits {sums['cache_hits']} misses "
          f"{sums['cache_misses']}", flush=True)
    ctx[_CACHE] = out
    return out


def read(ctx, part: str):
    out = _analyse(ctx)
    if out is None:
        return None
    if part not in out:
        raise ValueError(f"unknown part {part!r}")
    return out[part]
