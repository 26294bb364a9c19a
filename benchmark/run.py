"""Run one cell of the benchmark once.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything before the last line of standard output is for a human reader
(device stamp, set-up split, attention paths, per-part percentiles, the
stall log); the last line is the one JSON object the driver reads. The
server runs inside this process through the program's normal path
(``serve.schema.apply_config`` -> controller -> router -> ``LLMReplica`` ->
``DecodeEngine``; requests through the handle). Nothing outlives the run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
TRACE_SECONDS = 4.0
# Served tokens must lie within this logit margin of the reference's
# top-1: ref[top1] - ref[served] <= REF_TOL. The served path computes in
# bfloat16 (8 bits of mantissa) against a float32 reference; on seeded
# random weights next-token logits have a spread near 1 and the two
# disagree by a few hundredths (0.05 was measured between two bfloat16
# placements of one model, PERF.md, PR 21; the worst margin seen here at
# the published widths was 0.033, PR 23), so a greedy token can differ
# from the reference's argmax only inside such a near-tie. Wrong
# arithmetic (a dropped bias, a wrong rotary pairing, a mis-grouped head)
# moves logits by tenths to whole units and fails.
REF_TOL = 0.15


def _process_age_s() -> float:
    """Seconds since the kernel started this process (so that ``setup_s``
    counts the interpreter's start and the imports)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


_T_PROCESS = time.monotonic() - _process_age_s()


def say(msg: str) -> None:
    print(msg, flush=True)


def use_checkout_cache() -> None:
    """The program honours JAX_COMPILATION_CACHE_DIR; where nobody set it,
    the compile cache sits at one fixed path inside this checkout (a path
    that moves never hits). Call before JAX is imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


# --- the cell's files, found by name ---------------------------------------
class Cell:
    """One entry of ``workloads`` in BENCHMARK.json with every file it
    names: its configuration, its traffic mix, its metrics."""

    def __init__(self, workload: str, root: Path = ROOT) -> None:
        self.bench = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r}; have {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == self.workload["config"])
        self.config = load_json(root / cfg["file"])
        self.traffic = load_json(
            root / "benchmark" / "traffic" / f"{self.workload['traffic']}.json")
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if self._mine(m)]
        self.per_layer = [m for m in self.bench["per_layer"] if self._mine(m)]

    def _mine(self, metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def metric_spec(kind: str, name: str) -> Dict[str, Any]:
    return load_json(BENCH / kind / f"{name}.json")


# --- device ----------------------------------------------------------------
def check_device(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    peaks = load_json(BENCH / "peaks.json")
    d0 = devs[0]
    if require_tpu:
        if d0.platform != "tpu":
            raise SystemExit(
                f"no TPU: jax reports platform {d0.platform!r}; the "
                "benchmark measures on the chip only")
        if d0.device_kind not in peaks:
            raise SystemExit(
                f"device kind {d0.device_kind!r} is not in "
                "benchmark/peaks.json; add its peaks with their source")
    if len(devs) < chips:
        raise SystemExit(f"cell needs {chips} chip(s), jax reports {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips,
            "devices": devs[:chips], "peaks": peaks.get(d0.device_kind)}


def memory_peak_bytes(devices: Sequence[Any]) -> int:
    """The peak the fullest chip held, as JAX reports it: the allocator's
    peak in use PLUS the region it reserved. The TPU runtime sets the
    reserved region aside for the loaded programs' temporaries (here two to
    three further copies of the KV pool); it is not in
    ``peak_bytes_in_use`` and nobody else can have it: at 16 slots
    ``largest_free_block_bytes`` (10.90 GB) is ``bytes_limit`` (16.91 GB)
    less ``bytes_in_use`` (2.68 GB) less ``bytes_reserved`` (3.31 GB) (my
    chip run, PR 23). Both parts are printed on the ``memory:`` line."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats_line(devices: Sequence[Any]) -> str:
    stats = devices[0].memory_stats() or {}
    return "memory: " + " ".join(f"{k}={v}" for k, v in sorted(stats.items()))


# --- the system under test --------------------------------------------------
class Deployed:
    """The configuration deployed through the program's normal path."""

    def __init__(self, config: Dict[str, Any], seed: int,
                 devices: Sequence[Any], split: Dict[str, float]) -> None:
        import jax
        import jax.numpy as jnp

        from benchmark import views
        from ray_dynamic_batching_tpu.models.base import (
            ModelSLO,
            get_model,
            register_model,
        )
        from ray_dynamic_batching_tpu.parallel.placement import (
            PlacementManager,
        )
        from ray_dynamic_batching_tpu.serve.controller import ServeController
        from ray_dynamic_batching_tpu.serve.schema import (
            ServeConfigSchema,
            apply_config,
        )
        from ray_dynamic_batching_tpu.utils import compile_cache

        compile_cache.enable()
        prog = config["program"]
        self.config = config
        self.model_name = prog["register_as"]
        self.view = views.get(config.get("view", "dense"))
        dtype = jnp.dtype(prog["dtype"])
        register_model(self.model_name, slo=ModelSLO(
            latency_slo_ms=float(prog["ttft_slo_ms"])))(
                model_factory(prog, self.model_name))
        self.model = get_model(self.model_name, dtype=dtype)
        self.vocab_size = int(prog["decoder_config"]["vocab_size"])

        t = time.monotonic()
        self.params = seeded_params(config, self.model, self.view, seed, dtype)
        jax.block_until_ready(self.params)
        split["weights_s"] = time.monotonic() - t

        dep = config["deployment"]
        self.name = f"bench-{self.model_name}"
        llm = dict(dep["llm"], model=self.model_name, params=self.params,
                   dtype=dtype)
        deployment: Dict[str, Any] = {
            "name": self.name, "llm": llm,
            "num_replicas": int(dep["num_replicas"]),
            "max_ongoing_requests": int(dep["max_ongoing_requests"]),
        }
        placement = None
        if int(dep.get("chips_per_replica", 0)):
            deployment["chips_per_replica"] = int(dep["chips_per_replica"])
            placement = PlacementManager(list(devices))
        doc = {"applications": [{"name": "bench",
                                 "deployments": [deployment]}]}
        t = time.monotonic()
        self.controller = ServeController(placement=placement)
        self.controller.start()
        try:
            self.handle = apply_config(
                ServeConfigSchema.from_dict(doc), controller=self.controller
            )[self.name]
        except BaseException:
            self.controller.shutdown()
            raise
        split["deploy_warmup_s"] = time.monotonic() - t
        self.replicas = self.handle.router.replicas()
        if len(self.replicas) != deployment["num_replicas"]:
            self.close()
            raise SystemExit(
                f"{len(self.replicas)} of {deployment['num_replicas']} "
                "replicas started")

    @property
    def engines(self) -> List[Any]:
        return [rep.engine for rep in self.replicas]

    def submit(self, payload: Dict[str, Any]):
        return self.handle.remote_stream(payload, slo_ms=600_000.0)

    def completed(self) -> List[int]:
        return [int(e.completed) for e in self.engines]

    def close(self) -> None:
        try:
            self.controller.delete_deployment(self.name)
        finally:
            self.controller.shutdown()


def seeded_params(config: Dict[str, Any], model: Any, view: Any, seed: int,
                  dtype: Any) -> Any:
    """The served tree, from --seed; or, where the configuration file
    states ONE draw (``weights_seed``), from that, at every --seed. A file
    states one where the draw of the weights changes the WORK a run does (a
    seeded router behind seeded layers gives a rank's held experts anything
    from 0.7 to 1.6 of their even share of the routed rows, and the tokens
    completed follow it), and says under ``assumed`` by what rule the draw
    was chosen; --seed then draws the token ids alone, of the traffic and
    of the reference check's prompts."""
    from benchmark.weights import make_params

    return make_params(model, int(config.get("weights_seed", seed)), dtype,
                       getattr(view, "seeding", None))


def model_factory(prog: Dict[str, Any], name: str):
    """What builds the configuration's model, under ``name``, from
    ``decoder_config`` and the ``dtype`` the registry passes on:
    ``program.factory``, a ``module:callable`` of the program's package, or
    the decoder family."""
    sizes = prog["decoder_config"]
    if "factory" in prog:
        module, _, fn = prog["factory"].partition(":")
        if not module.startswith("ray_dynamic_batching_tpu."):
            raise ValueError(f"factory {prog['factory']!r} is not the "
                             "program's")
        make = getattr(importlib.import_module(module), fn)
        return lambda **kw: make(**sizes, name=name, **kw)
    from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
    from ray_dynamic_batching_tpu.models.decoder import DecoderConfig

    dcfg = DecoderConfig(**sizes)
    return lambda **kw: CausalLM(dcfg, name=name, **kw)


def attention_path_lines() -> List[str]:
    from ray_dynamic_batching_tpu.ops.attention import attention_paths

    table: Dict[tuple, int] = {}
    for r in attention_paths():
        key = (r.program or "<no program>", tuple(r.q_shape),
               tuple(r.kv_shape), str(r.kv_dtype), r.describe())
        table[key] = table.get(key, 0) + 1
    return [f"paths: {prog:14s} q{list(q)} kv{list(kv)} {dt} x{n} -> {path}"
            for (prog, q, kv, dt, path), n in sorted(table.items())]


# --- correctness against the plain reference ---------------------------------
def reference_check(dep: Deployed, seed: int) -> Dict[str, Any]:
    """Two seeded prompts served greedy through the normal path; the plain
    reference is teacher-forced on prompt + served tokens, and every served
    token must lie within REF_TOL of the reference's top-1."""
    import numpy as np

    from benchmark import reference
    from ray_dynamic_batching_tpu.utils.compile_ledger import get_ledger

    check = dep.config["reference_check"]
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    ref = reference.get(dep.config["reference"])
    weights = dep.view.view(dep.params, dep.config)  # no copies
    worst, ok, short, served_all = 0.0, True, 0, []
    # The reference's own programs compile here, after the replicas armed
    # the compile ledger's steady mark: bracket them as set-up.
    with get_ledger().warming():
        for L in check["prompt_lens"]:
            prompt = rng.integers(1, dep.vocab_size, size=int(L)).tolist()
            n_new = int(check["new_tokens"])
            _stream, fut = dep.submit(
                {"tokens": prompt, "max_new_tokens": n_new})
            served = list(fut.result(timeout=600.0).tokens)
            served_all.append(served)
            if len(served) != n_new:
                ok, short = False, short + 1
                continue
            seq = prompt + served
            logits = np.asarray(ref.logits(weights, seq[:-1], dep.config))
            for j, tok in enumerate(served):
                row = logits[len(prompt) - 1 + j]
                gap = float(row.max() - row[tok])
                worst = max(worst, gap)
                ok = ok and gap <= REF_TOL
    return {"ok": bool(ok), "worst_gap": worst, "tol": REF_TOL,
            "short_answers": short, "served": served_all}


# --- end-to-end metrics -------------------------------------------------------
def end_to_end_value(spec: Dict[str, Any], run: Dict[str, Any],
                     traffic: Dict[str, Any], setup_s: float,
                     ) -> Dict[str, Any]:
    from benchmark import stats

    kind = spec["kind"]
    recs, until = run["records"], run["observed_until_s"]
    if kind == "setup":
        return {"value": setup_s}
    if kind == "window_percentile":
        return stats.whole_window_percentile(
            recs, spec["field"], float(spec["q"]), until)
    if kind == "slo_share":
        return {"value": stats.slo_met_pct(recs, traffic["limits"], until)}
    if kind == "window_rate":
        stamps = [t for r in recs for t in r["stamps"]]
        return {"value": stats.window_rate(stamps, run["window_s"])}
    raise ValueError(f"unknown end-to-end metric kind {kind!r}")


# --- the stall log --------------------------------------------------------------
def stall_log(run: Dict[str, Any], beat: Any, gcw: Any,
              compiles: List[Dict[str, Any]], completed: List[int],
              ) -> List[str]:
    from benchmark import stats

    t0, recs = run["t0"], run["records"]
    lines = []
    names = sorted({c["fn"] for c in compiles})
    lines.append(f"stall: compiles in window: {len(compiles)} {names}")
    in_win = [(at - t0, s, g) for at, s, g in gcw.pauses if at >= t0]
    if in_win:
        at, worst, gen = max(in_win, key=lambda p: p[1])
        lines.append(
            f"stall: gc pauses in window: {len(in_win)}, total "
            f"{sum(p[1] for p in in_win) * 1000:.1f} ms, longest "
            f"{worst * 1000:.1f} ms (gen {gen}) at t={at:.2f}s")
    else:
        lines.append("stall: gc pauses in window: 0")
    beats = [(at - t0, o) for at, o in beat.overshoots if at >= t0]
    if beats:
        at, worst = max(beats, key=lambda b: b[1])
        lines.append(
            f"stall: heartbeat ({beat.period_s * 1000:.0f} ms) largest "
            f"overshoot {worst * 1000:.1f} ms at t={at:.2f}s over "
            f"{len(beats)} beats")
    firsts = sorted(r["first"] for r in recs if r["first"] is not None)
    if len(firsts) > 1:
        gap, at = max((b - a, a) for a, b in zip(firsts, firsts[1:]))
        inside = sum(1 for r in recs if at <= r["due"] < at + gap)
        lines.append(
            f"stall: largest gap between first tokens {gap * 1000:.1f} ms "
            f"from t={at:.2f}s, {inside} arrivals due inside it")
    late = [(r["sent"] - r["due"]) * 1000 for r in recs
            if r["sent"] is not None]
    if late:
        lines.append(
            f"stall: generator lateness p99 {stats.percentile(late, 99):.3f}"
            f" ms, max {max(late):.3f} ms over {len(late)} sends")
    lines.append(f"stall: completions per replica in window {completed}")
    return lines


def distribution_lines(run: Dict[str, Any], traffic: Dict[str, Any],
                       engines: Sequence[Any]) -> List[str]:
    """Per part: counts and the percentiles of time to first token; and the
    share of admissions that waited out more than one full scan."""
    from benchmark import stats

    recs, until = run["records"], run["observed_until_s"]
    n_parts = int(traffic.get("parts", 3))
    qs = (50, 75, 85, 90, 95, 99)
    lines = []
    for field in ("ttft_ms", "tpot_ms"):
        vals = stats.field_values(recs, field, until)
        for p in range(n_parts):
            mine = [v for due, v in vals
                    if stats.part_of(due, run["window_s"], n_parts) == p]
            if not mine:
                lines.append(f"parts: {field} part {p}: n=0")
                continue
            pcts = " ".join(
                f"p{q}={stats.percentile(mine, q):.1f}" for q in qs)
            lines.append(f"parts: {field} part {p}: n={len(mine)} {pcts}")
        allv = [v for _, v in vals]
        if allv:
            pcts = " ".join(
                f"p{q}={stats.percentile(allv, q):.1f}" for q in qs)
            lines.append(f"parts: {field} whole window: n={len(allv)} {pcts}")
    send = [v for _, v in stats.field_values(recs, "ttft_from_send_ms", until)]
    if send:
        lines.append(
            "parts: ttft from SEND time (not the metric) p90="
            f"{stats.percentile(send, 90):.1f}")
    parts = [p for e in engines for p in list(e._ttft_parts)]
    tpots = [v for _, v in stats.field_values(recs, "tpot_ms", until)]
    if parts and tpots:
        h = max(int(e.decode_horizon) for e in engines)
        scan_ms = h * stats.percentile(tpots, 50)
        waited = sum(1 for p in parts if p[0] > scan_ms)
        lines.append(
            f"parts: queue_wait over one full scan ({h} x tpot p50 = "
            f"{scan_ms:.0f} ms): {100.0 * waited / len(parts):.1f}% of "
            f"{len(parts)} admissions")
    return lines


def work_lines(run: Dict[str, Any], config: Dict[str, Any],
               engines: Sequence[Any]) -> List[str]:
    """For a configuration that holds a share of its experts: what the
    window's work was, beside the rate it gave. One line an engine, from
    the ring's records dispatched inside the window (always on, so an
    untraced run prints it too): the share of the routed pairs that landed
    on the experts held here, beside the even share; the requests sent and
    completed inside the window; tokens per second. A printed line, no
    metric: two runs whose rates differ can be told apart by their work."""
    from benchmark import stats

    sizes = config["program"]["decoder_config"]
    held = int(sizes.get("moe_held_experts", 0))
    if not held:
        return []
    recs, window_s = run["records"], run["window_s"]
    rate = stats.window_rate([t for r in recs for t in r["stamps"]], window_s)
    done = sum(1 for r in recs
               if r["closed"] is not None and r["closed"] <= window_s)
    lo = run["t0"] * 1000.0
    lines = []
    for i, eng in enumerate(engines):
        inside = [t for t in eng.turns.copy()   # one call: it may append
                  if lo <= t.t_dispatch < lo + window_s * 1000.0]
        summary = eng.turn_summary(records=inside, span_ms=window_s * 1000.0)
        share = summary.get("moe_held_rows_share")
        if share is None:
            continue
        lines.append(
            f"work: engine {i}: moe_held_rows_share {100.0 * share:.3f}% of "
            f"the routed pairs (even {100.0 * held / int(sizes['num_experts']):.3f}: "
            f"{held} of {sizes['num_experts']} held), "
            f"{summary.get('moe_rows_per_expert', 0.0):.3f} rows an expert "
            f"hit, turns_dropped={summary['dropped']}; requests sent "
            f"{len(recs)}, completed in the window {done}; "
            f"out_tok_per_s {rate:.3f}")
    return lines


# --- the traced sub-window ---------------------------------------------------------
class Tracer:
    """Takes a ``jax.profiler`` trace of TRACE_SECONDS (over the chips used)
    in the middle of the window, from a thread of its own."""

    def __init__(self, trace_dir: Path, window_s: float, chips: int) -> None:
        self.dir = trace_dir
        # The trace's size, and the minutes it takes to read back, grow
        # with the chips traced: four chips get a quarter of the time each
        # (a 4 s trace of four replicas took the run to 338 s of its 360).
        self.length = min(TRACE_SECONDS / chips, window_s / 4.0)
        self.offset = 0.4 * window_s
        self.host_window: Optional[tuple] = None  # monotonic (start, end)
        self.error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None

    def begin(self, t0: float) -> None:
        self._thread = threading.Thread(
            target=self._run, args=(t0,), name="bench-tracer", daemon=True)
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax.profiler as jp

        from benchmark.trace_reduce import WINDOW_ANNOTATION

        try:
            time.sleep(max(t0 + self.offset - time.monotonic(), 0.0))
            shutil.rmtree(self.dir, ignore_errors=True)
            jp.start_trace(str(self.dir))
            try:
                with jp.TraceAnnotation(WINDOW_ANNOTATION):
                    a = time.monotonic()
                    time.sleep(self.length)
                    b = time.monotonic()
            finally:
                jp.stop_trace()
            self.host_window = (a, b)
        except BaseException as e:  # noqa: BLE001 — reported by finish()
            self.error = e

    def finish(self) -> Any:
        from benchmark.trace_reduce import Trace

        if self._thread is not None:
            self._thread.join(timeout=300.0)
        if self.error is not None:
            raise RuntimeError(f"trace failed: {self.error!r}")
        return Trace.from_dir(str(self.dir))


# --- one run --------------------------------------------------------------------------
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True) -> Dict[str, Any]:
    """Deploy, check against the reference, pre-roll, measure for
    ``seconds``, reduce. Returns the last line's object."""
    import gc

    from benchmark import loadgen

    split: Dict[str, float] = {"import_s": time.monotonic() - _T_PROCESS}
    t = time.monotonic()
    device = check_device(cell.chips, require_tpu)
    split["device_init_s"] = time.monotonic() - t
    say(f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} workload={cell.name} seed={seed} "
        f"seconds={seconds} trace={int(trace)}")

    from ray_dynamic_batching_tpu.utils.compile_ledger import get_ledger

    ledger = get_ledger()
    dep = Deployed(cell.config, seed, device["devices"], split)
    try:
        t = time.monotonic()
        ref = reference_check(dep, seed)
        split["reference_check_s"] = time.monotonic() - t
        say(f"reference: ok={ref['ok']} worst margin to the reference's "
            f"top-1 {ref['worst_gap']:.4f} (tolerance {ref['tol']}); "
            f"served {ref['served']}")

        t = time.monotonic()
        requests = loadgen.build_requests(
            cell.traffic, dep.vocab_size, seed, seconds)
        # Pre-roll: the first requests of another seed, sent together and
        # cut to a few tokens each, so that every program the traffic
        # reaches (chunk trains, group widths, the full-batch horizon) has
        # run once on real pages before the window: warm-up ran them on
        # sentinels.
        n_pre = int(cell.traffic.get("preroll", 0))
        if n_pre:
            pre = loadgen.build_requests(
                cell.traffic, dep.vocab_size, seed + 1, seconds)[:n_pre]
            cut = int(cell.traffic.get("preroll_new_tokens", 16))
            futs = [dep.submit({"tokens": r["tokens"],
                                "max_new_tokens": min(r["max_new_tokens"],
                                                      cut)})[1]
                    for r in pre]
            for f in futs:
                f.result(timeout=600.0)
        split["traffic_build_preroll_s"] = time.monotonic() - t
        for line in attention_path_lines():
            say(line)

        gc.collect()
        for e in dep.engines:
            e.reset_ttft_window()
        steps0 = [int(e.steps) for e in dep.engines]
        done0 = dep.completed()
        n_viol0 = len(ledger.violations())
        beat, gcw = loadgen.Heartbeat(), loadgen.GcWatch()
        tracer = (Tracer(ROOT / ".bench_trace" / f"{cell.name}-{seed}",
                         seconds, cell.chips) if trace else None)
        state: Dict[str, float] = {}

        def on_start(t0: float) -> None:
            state["setup_s"] = t0 - _T_PROCESS
            if tracer is not None:
                tracer.begin(t0)

        beat.start()
        gcw.start()
        try:
            run = loadgen.run_traffic(
                dep.submit, cell.traffic, requests, seconds, on_start)
        finally:
            beat.stop()
            gcw.stop()
        setup_s = state["setup_s"]
        compiles = ledger.violations()[n_viol0:]
        completed = [b - a for a, b in zip(done0, dep.completed())]
        steps = [int(e.steps) - s for e, s in zip(dep.engines, steps0)]
        trace_obj = tracer.finish() if tracer is not None else None

        other = setup_s - sum(v for k, v in split.items() if k.endswith("_s"))
        say("setup: " + " ".join(
            f"{k}={v:.2f}" for k, v in split.items()) +
            f" other_s={other:.2f} total setup_s={setup_s:.2f}")
        recs = run["records"]
        failed = sum(1 for r in recs if not r["ok"])
        wrong = [r for r in recs if r["closed"] is not None and not r["ok"]]
        say(f"window: {len(recs)} requests due, {failed} failed, observed "
            f"until t={run['observed_until_s']:.2f}s; substeps per replica "
            f"{steps}")
        for r in wrong[:5]:
            say(f"window: failed request due t={r['due']:.2f}s: "
                f"{r['n_out']}/{r['want_out']} tokens, error {r['error']}")
        if cell.traffic["loop"] == "open":
            for line in distribution_lines(run, cell.traffic, dep.engines):
                say(line)
        for line in stall_log(run, beat, gcw, compiles, completed):
            say(line)
        for line in work_lines(run, cell.config, dep.engines):
            say(line)
        say(memory_stats_line(device["devices"]))

        metrics: Dict[str, Dict[str, Any]] = {}
        if not trace:
            for m in cell.end_to_end:
                out = end_to_end_value(
                    metric_spec("e2e_metrics", m["name"]), run, cell.traffic,
                    setup_s)
                if "count" in out:
                    say(f"metric: {m['name']} = {out['value']:.3f} over all "
                        f"{out['count']} requests of the window "
                        f"({out['beyond']} beyond the percentile)")
                metrics[m["name"]] = {"value": out["value"], "unit": m["unit"]}
        ctx = {
            "cell": cell, "config": cell.config, "traffic": cell.traffic,
            "run": run, "records": recs, "engines": dep.engines,
            "completed": completed, "steps": steps, "compiles": compiles,
            "trace": trace_obj, "peaks": device["peaks"],
            "trace_host_window": (
                tuple(x - run["t0"] for x in tracer.host_window)
                if tracer is not None and tracer.host_window else None),
        }
        if trace:
            for m in cell.per_layer:
                spec = metric_spec("layer_metrics", m["name"])
                reader = importlib.import_module(
                    f"benchmark.readers.{spec['reader']}")
                value = reader.read(ctx, **spec.get("args", {}))
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
        result: Dict[str, Any] = {
            "correct": bool(ref["ok"] and not wrong),
            "attempted": len(recs),
            "failed": failed,
            "metrics": metrics,
            "device": {
                "platform": device["platform"], "kind": device["kind"],
                "count": device["count"],
                "memory_peak_bytes": memory_peak_bytes(device["devices"]),
            },
        }
        if trace_obj is not None:
            result["device"]["busy_s"] = trace_obj.busy_s()
            result["device"]["window_s"] = trace_obj.window_s()
            result["breakdown"] = {"device_ops": trace_obj.top_ops(10),
                                   "idle_gaps": trace_obj.idle_gaps(10)}
            shutil.rmtree(tracer.dir, ignore_errors=True)
        # What `correct` compared, each number beside its limit; last in
        # the line, and the last lines of standard error (main).
        result["compared"] = {
            "ref_worst_gap": {"value": ref["worst_gap"], "limit": ref["tol"]},
            "ref_short_answers": {"value": ref["short_answers"], "limit": 0},
            "window_wrong_answers": {"value": len(wrong), "limit": 0},
        }
        return result
    finally:
        dep.close()


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = ap.parse_args(argv)
    use_checkout_cache()
    cell = Cell(a.workload)
    result = run_cell(cell, a.seed, a.seconds, bool(a.trace),
                      require_tpu=True)
    say(json.dumps(result))
    for name, c in result["compared"].items():
        print(f"compared: {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
