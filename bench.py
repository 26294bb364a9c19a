"""Headline benchmarks on the local chip.

Two parts, one JSON line:

1. **North star** (BASELINE.json): LLM decode serving through the real
   serving path (DeploymentHandle -> pow-2 Router -> LLMReplica ->
   continuous-batching DecodeEngine) under Poisson arrivals — reports
   p50/p99 TTFT and tok/s/chip. The north-star target (>=1500 tok/s/chip)
   is the baseline for ``vs_baseline``.
2. **Vision table**: throughput vs the reference's best measured numbers on
   its own hardware (RTX A6000 profiling reports, BASELINE.md), with MFU,
   median of repeats.

Timing: every timed region ends in work the host waits for. Vision
timing runs an on-device dependent ``fori_loop`` chain and reads back the
one scalar it produces, so a single dispatch covers all iterations; the
decode engine's hot loop fetches the sampled tokens every step because
the host needs them, so its request timings are wall-clock by
construction.

The record is a device measurement or nothing: without a TPU the script
exits non-zero and prints no metric, a failed row fails the run, and
every record is stamped with the platform, ``device_kind`` and device
count it ran on.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

# Reference bests on its own hardware (A6000 48GB; BASELINE.md sources).
VISION_BASELINES = {
    # ours: (baseline samples/s, batch sizes to try)
    "resnet50": (2495.1, (128, 256)),
    "shufflenet_v2": (17238.9, (256, 512)),
    "efficientnet_v2s": (1014.6, (64, 128)),
    # baseline row is ViT-G/16; the registry's giant config is ViT-G/14
    # (slightly LARGER per-sample cost, so the comparison is conservative).
    "vit_g_14": (112.1, (16, 32)),
}
NORTH_STAR_TOK_S = 1500.0  # BASELINE.json: ">=1500 tok/s/chip"
# Peak bf16 TFLOP/s of one chip, keyed by ``jax.devices()[0].device_kind``
# (MFU's denominator). Source: Google Cloud documentation, "TPU v5e" —
# 197 TFLOP/s bf16 per chip; jax 0.9.0's own table
# (jax/_src/pallas/mosaic/tpu_info.py) carries the same figure under
# both spellings of the kind. A device that is not here is an error, not
# a default: an MFU against another chip's peak is a wrong number.
PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
}


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_stamp() -> dict:
    """The accelerator this process measures on, as JAX reports it.
    Raises when there is no TPU or its peak is unknown — the bench never
    falls back to the CPU and never divides by another chip's peak."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py: no TPU (jax.devices()[0].platform == "
            f"{dev.platform!r}); a CPU run measures nothing the ledger "
            "records"
        )
    if dev.device_kind not in PEAK_BF16_TFLOPS:
        raise RuntimeError(
            f"bench.py: no peak on record for device_kind "
            f"{dev.device_kind!r}; add it to PEAK_BF16_TFLOPS with its "
            "source"
        )
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(devices)}


def bench_vision_model(name: str, baseline: float, batch_sizes,
                       iters: int = 20, warmup: int = 2,
                       repeats: int = 3) -> dict:
    """Median-of-repeats throughput for one fixed-shape model."""
    import jax

    from ray_dynamic_batching_tpu.models import registry  # noqa: F401
    from ray_dynamic_batching_tpu.models.base import get_model

    model = get_model(name)  # bf16
    params = model.init(jax.random.PRNGKey(0))
    peak_tflops = PEAK_BF16_TFLOPS[jax.devices()[0].device_kind]
    best = {"samples_per_s": 0.0}
    for b in batch_sizes:
        x = model.example_inputs(b)[0]

        def chained(params, x, n):
            def body(_, carry):
                logits = model.apply(params, carry)
                # zero-scaled feedback makes step i+1 depend on step i
                return carry + (logits[0, 0] * 0).astype(carry.dtype)

            final = jax.lax.fori_loop(0, n, body, x)
            return model.apply(params, final)[0, 0]

        fn = jax.jit(chained)  # n stays dynamic: one compile per batch
        float(fn(params, x, warmup))  # compile + warm
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(fn(params, x, iters - 1))  # the scalar IS the result
            times.append((time.perf_counter() - t0) / iters)
        dt = statistics.median(times)
        sps = b / dt
        _log(f"{name} b{b}: {dt * 1000:.2f} ms -> {sps:.1f} samples/s "
             f"(median of {repeats})")
        if sps > best["samples_per_s"]:
            flops = model.flops_per_sample() * sps
            best = {
                "samples_per_s": round(sps, 1),
                "batch": b,
                "latency_ms": round(dt * 1000, 2),
                "tflops": round(flops / 1e12, 1),
                "mfu": round(flops / 1e12 / peak_tflops, 3),
            }
    if best["samples_per_s"]:
        best["vs_baseline"] = round(best["samples_per_s"] / baseline, 3)
    return best


def bench_llm_serving(
    model_name: str = "gpt2_medium",
    num_slots: int = 64,
    max_len: int = 256,
    prompt_len: int = 48,
    max_new_tokens: int = 96,
    saturation_requests: int = 192,
    poisson_duration_s: float = 15.0,
    poisson_utilization: float = 0.6,
    decode_horizon: int = 32,
    max_admissions_per_step: int = 8,
    deployment=None,
    quantize_kv: bool = False,
    mesh: int = 1,
    spec: bool = False,
    long_frac: float = 0.0,
) -> dict:
    """North star: continuous-batching decode through the serving path.

    Phase A saturates the engine to measure peak tok/s/chip; phase B offers
    Poisson arrivals at ``poisson_utilization`` of measured capacity and
    reports p50/p99 TTFT (the BASELINE.json measurement axes).

    ``mesh`` > 1 serves through a TP slice of that many chips (ROADMAP
    item 2's A/B axis): the replica gets a ``mesh``-chip device bundle,
    so the engine runs GSPMD-sharded decode over the sharded page
    pool, and ``tok_s_per_chip`` normalizes by the
    slice width (whole-slice tokens / chips), the planner's
    per-chip-throughput convention for mesh profile rows.

    ``spec`` attaches the ``gpt2_draft`` companion (ISSUE 13's A/B
    axis: scratch-page drafts + splice
    commits), but NOT with ``mesh`` > 1, which the engine rejects
    loudly). The row stamps the measured ``spec_acceptance`` so a
    capture can never be read without its acceptance context: at ~0
    (untrained draft) the row measures the bounded-degradation floor,
    at a real acceptance it measures the Leviathan multiplier.

    ``long_frac`` mixes that fraction of OVER-BUCKET prompts (~3x the
    base prompt) into both phases — the long-prompt traffic that admits
    as multi-chunk trains under the token budget.
    """
    import numpy as np

    from ray_dynamic_batching_tpu.engine.workload import (
        RatePattern,
        WorkloadDriver,
    )
    from ray_dynamic_batching_tpu.serve.controller import DeploymentConfig
    from ray_dynamic_batching_tpu.serve.handle import DeploymentHandle
    from ray_dynamic_batching_tpu.serve.llm import LLMDeployment
    from ray_dynamic_batching_tpu.serve.router import Router

    rng = np.random.default_rng(0)
    t_build = time.perf_counter()
    if deployment is None:
        deployment = LLMDeployment(
            model_name,
            num_slots=num_slots,
            max_len=max_len,
            prompt_buckets=[prompt_len + 16],
            default_max_new_tokens=max_new_tokens,
            decode_horizon=decode_horizon,
            max_admissions_per_step=max_admissions_per_step,
            quantize_kv=quantize_kv,
            draft_model_name="gpt2_draft" if spec else None,
        )
    devices = None
    slice_pg = slice_mgr = None
    if mesh > 1:
        # Reserve the chip gang through pin_slice, not a bare
        # jax.devices() prefix: STRICT_PACK fails loudly when no single
        # host holds the gang, so a multi-host run can never commit a
        # "per-chip" TP row whose collectives secretly crossed DCN.
        from ray_dynamic_batching_tpu.parallel.placement import (
            PlacementManager,
            pin_slice,
        )

        slice_mgr = PlacementManager()
        # No gang of this width on this host raises PlacementError: a
        # row that cannot run is a failed run, not a zero-valued one.
        slice_pg, _ = pin_slice(slice_mgr, f"1x{mesh}")
        devices = slice_pg.bundle_devices(0)
    replica = deployment.make_replica(
        f"{model_name}#bench",
        DeploymentConfig(name=model_name, max_ongoing_requests=4096),
        devices=devices,
    )
    replica.start()
    router = Router(model_name, replicas=[replica], max_assign_timeout_s=30.0)
    handle = DeploymentHandle(router, default_slo_ms=300_000.0)
    vocab = deployment._model.cfg.vocab_size
    num_slots = replica.engine.num_slots  # actual (auto-sizing may differ)
    _log(f"{model_name}: built + warmed in "
         f"{time.perf_counter() - t_build:.1f}s "
         f"(slots={num_slots}, max_len={max_len})")

    # Long-prompt mix: over-bucket prompts (~3x base, capped so prompt
    # + generation fits the cache) that admit as multi-chunk trains.
    long_len = min(prompt_len * 3, max_len - max_new_tokens - 1)

    def payload():
        plen = prompt_len
        if long_frac > 0.0 and rng.random() < long_frac:
            plen = long_len
        return {
            "tokens": rng.integers(1, vocab, size=plen).tolist(),
            "max_new_tokens": max_new_tokens,
        }

    # --- phase A: saturation -> peak tok/s/chip --------------------------
    t0 = time.perf_counter()
    futs = [handle.remote(payload()) for _ in range(saturation_requests)]
    results = [f.result(timeout=600) for f in futs]
    elapsed = time.perf_counter() - t0
    total_tokens = sum(len(r.tokens) for r in results)
    # Per-CHIP normalization: a TP slice's whole-slice tok/s divided by
    # its width — the same convention as mesh profile rows, so one-chip
    # and TP rows are directly comparable.
    tok_s = total_tokens / elapsed / max(1, mesh)
    _log(f"saturation: {total_tokens} tokens / {elapsed:.1f}s = "
         f"{tok_s:.0f} tok/s/chip over {mesh} chip(s) "
         f"({saturation_requests} reqs x {max_new_tokens} new tokens)")

    # --- phase B: Poisson arrivals -> TTFT -------------------------------
    # Whole-UNIT capacity: the slice serves mesh x the per-chip rate.
    capacity_rps = tok_s * max(1, mesh) / max_new_tokens
    offered_rps = max(0.5, capacity_rps * poisson_utilization)
    # Fresh TTFT window: the breakdown must describe the Poisson phase
    # (the north-star measurement), not the saturation ramp.
    replica.engine.reset_ttft_window()
    poisson_futs = []

    def submit(_model: str, _offset: float) -> None:
        poisson_futs.append(handle.remote(payload()))

    driver = WorkloadDriver(
        submit,
        model_name,
        RatePattern("constant", base_rps=offered_rps),
        duration_s=poisson_duration_s,
        poisson=True,
        seed=7,
    )
    driver.start()
    driver.join(poisson_duration_s + 60)
    poisson_results = [f.result(timeout=600) for f in poisson_futs]
    ttfts = sorted(r.ttft_ms for r in poisson_results)
    p50 = statistics.median(ttfts)
    p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]
    # Where the TTFT milliseconds live (queue wait / in-flight-scan wait /
    # prefill), from the engine's own decomposition of the Poisson phase.
    breakdown = replica.engine.ttft_breakdown()
    _log(f"poisson @{offered_rps:.1f} rps ({len(ttfts)} reqs): "
         f"TTFT p50={p50:.0f} ms p99={p99:.0f} ms breakdown={breakdown}")

    # Decode KV residency (measured at the end of the Poisson phase):
    # useful cached tokens over the positions of the allocated pages.
    kv_occupancy = round(replica.engine.kv_occupancy(), 4)
    # Acceptance context for the spec arm (None off / before any round):
    # a spec capture without its acceptance rate is unreadable.
    acceptance = replica.engine.spec_acceptance() if spec else None
    replica.stop(timeout_s=2.0, drain=False)
    if slice_mgr is not None:
        slice_mgr.remove(slice_pg)
    return {
        "tok_s_per_chip": round(tok_s, 1),
        "ttft_p50_ms": round(p50, 1),
        "ttft_p99_ms": round(p99, 1),
        "ttft_breakdown": breakdown,
        "offered_rps": round(offered_rps, 2),
        "model": model_name,
        "num_slots": num_slots,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "mesh": mesh,
        "spec": spec,
        "spec_acceptance": (None if acceptance is None
                            else round(acceptance, 4)),
        "kv_occupancy": kv_occupancy,
        "prefill_token_budget": replica.engine.prefill_token_budget,
        "long_frac": long_frac,
    }


def bench_llama3_8b(
    max_len: int = 512,
    prompt_len: int = 48,
    max_new_tokens: int = 32,
    saturation_requests: int = 16,
    poisson_duration_s: float = 8.0,
) -> dict:
    """North-star MODEL row: int8 weight-only Llama-3-8B decode serving on
    one chip (BASELINE.json config 4's model at its real size; int8 fits
    ~8 GB of weights in a v5e's 16 GB HBM where bf16 cannot).

    Guarded: runs only against a reachable accelerator with enough free
    HBM; returns a skip record otherwise. Weights are initialized and
    quantized ON THE HOST (an 8B bf16 init on-device would OOM the chip
    before quantization could shrink it), then the int8 tree alone is
    transferred."""
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.models import registry  # noqa: F401
    from ray_dynamic_batching_tpu.models.base import get_model
    from ray_dynamic_batching_tpu.models.quant import (
        quantize_tree,
        tree_weight_bytes,
    )
    from ray_dynamic_batching_tpu.serve.llm import LLMDeployment

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return {"skipped": "no accelerator (cpu backend)"}
    need = 10 << 30  # ~8 GB int8 weights + KV/activation headroom
    try:
        stats = dev.memory_stats() or {}
        free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
        if stats.get("bytes_limit") and free < need:
            return {"skipped": f"insufficient HBM: {free / 1e9:.1f} GB "
                               f"free, need {need / 1e9:.0f} GB"}
    except Exception:  # noqa: BLE001 — no stats API: attempt anyway
        pass

    t0 = time.perf_counter()
    model = get_model("llama3_8b")
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        params = model.init(jax.random.PRNGKey(0))
        qparams = quantize_tree(params)
        del params
    _log(f"llama3_8b: host init + int8 quantize in "
         f"{time.perf_counter() - t0:.0f}s "
         f"({tree_weight_bytes(qparams) / 1e9:.2f} GB quantized)")
    t1 = time.perf_counter()
    qparams = jax.device_put(qparams, dev)
    jax.block_until_ready(qparams)
    _log(f"llama3_8b: int8 tree -> chip in {time.perf_counter() - t1:.0f}s")

    deployment = LLMDeployment(
        "llama3_8b",
        params=qparams,
        # The tree is already int8, but the flag is what makes the ENGINE
        # dequantize inside its programs (quantize_tree is idempotent, so
        # the pre-quantized params pass through _ensure_model untouched).
        quantize_weights=True,
        num_slots=0,  # auto: fill HBM after the int8 weights
        max_len=max_len,
        prompt_buckets=[prompt_len + 16],
        default_max_new_tokens=max_new_tokens,
        decode_horizon=16,
        max_admissions_per_step=4,
    )
    row = bench_llm_serving(
        model_name="llama3_8b",
        max_len=max_len,
        prompt_len=prompt_len,
        max_new_tokens=max_new_tokens,
        saturation_requests=saturation_requests,
        poisson_duration_s=poisson_duration_s,
        deployment=deployment,
    )
    row["quantization"] = "int8 weight-only"
    return row


def bench_asr_rtf(batch: int = 8, audio_s: float = 30.0,
                  decode_tokens: int = 32, repeats: int = 3,
                  model_name: str = "whisper_large_v3") -> dict:
    """Whisper-large-v3 real-time factor: seconds of audio transcribed per
    wall second. One compiled program runs encode + SOT prefill + a
    ``decode_tokens``-step greedy scan for a full batch of 30 s clips; the
    timed region ends when the sampled tokens — the transcript — reach
    the host. The reference ships no ASR at all, so the baseline is real
    time (RTF 1.0)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_dynamic_batching_tpu.models import registry  # noqa: F401
    from ray_dynamic_batching_tpu.models.base import get_model

    model = get_model(model_name)  # bf16
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0))
    frames = int(audio_s * 100)  # 10 ms mel frames

    def transcribe(params, mel, mel_mask):
        enc_states, enc_mask = model.encode(params, mel, mel_mask)
        cache = model.make_cache(batch, max_len=decode_tokens + 8)
        sot = jnp.full((batch, 1), cfg.sot_token, jnp.int32)
        last, cache = model.prefill(
            params, sot, jnp.ones_like(sot), enc_states, enc_mask, cache
        )
        tok0 = jnp.argmax(last, axis=-1).astype(jnp.int32)

        def step(carry, _):
            tok, cache = carry
            logits, cache = model.decode_step(
                params, tok[:, None], enc_states, enc_mask, cache,
                jnp.ones((batch,), bool),
            )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, cache), nxt

        (_, _), toks = jax.lax.scan(
            step, (tok0, cache), None, length=decode_tokens - 1
        )
        return toks  # [decode_tokens-1, B]

    fn = jax.jit(transcribe)
    rng = np.random.default_rng(3)
    mel = jnp.asarray(
        rng.standard_normal((batch, frames, cfg.n_mels)), jnp.float32
    )
    mel_mask = jnp.ones((batch, frames), jnp.int32)
    np.asarray(fn(params, mel, mel_mask))  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(fn(params, mel, mel_mask))  # fetch = completion
        times.append(time.perf_counter() - t0)
    dt = statistics.median(times)
    rtf = batch * audio_s / dt
    _log(f"{model_name} b{batch}x{audio_s:.0f}s: {dt * 1000:.0f} ms "
         f"-> RTF {rtf:.1f}x real time (median of {repeats})")
    return {
        "model": model_name,
        "rtf": round(rtf, 1),
        "batch": batch,
        "audio_s": audio_s,
        "decode_tokens": decode_tokens,
        "latency_ms": round(dt * 1000, 1),
        "vs_baseline": round(rtf, 1),  # baseline = real time (RTF 1.0)
    }


def main() -> dict:
    stamp = device_stamp()  # raises without a TPU: no record, no metric
    fast = os.environ.get("RDB_BENCH_FAST") == "1"
    # llm scope: ONLY the north-star serving row (the full record adds
    # the int8-KV, vision, ASR and host-quantized 8B rows).
    llm_only = os.environ.get("RDB_BENCH_SCOPE") == "llm"
    # One config dict feeds BOTH llm rows: the int8-KV variant must
    # measure the same configuration as the bf16 row it is compared to.
    # --mesh N (RDB_BENCH_MESH) serves the llm rows through an N-chip TP
    # slice — ROADMAP item 2's A/B axis (1 = the classic single-chip
    # record).
    mesh = int(os.environ.get("RDB_BENCH_MESH", "1") or 1)
    # --spec on (RDB_BENCH_SPEC=1) attaches the gpt2_draft companion —
    # ISSUE 13's A/B axis (scratch-page drafts + splice commits). The
    # rows stamp the measured acceptance rate.
    spec = os.environ.get("RDB_BENCH_SPEC") == "1"
    # RDB_BENCH_LONG_FRAC mixes over-bucket prompts (multi-chunk trains)
    # into both phases.
    long_frac = float(os.environ.get("RDB_BENCH_LONG_FRAC", "0") or 0)
    llm_kwargs = dict(
        num_slots=8 if fast else 64,
        saturation_requests=16 if fast else 192,
        poisson_duration_s=5.0 if fast else 15.0,
        decode_horizon=8 if fast else 32,
        mesh=mesh,
        spec=spec,
        long_frac=long_frac,
    )
    # A row that raises fails the run: no row's failure is caught, so a
    # record that exists is a record in which every row ran.
    llm = bench_llm_serving(**llm_kwargs)
    # Int8-KV variant of the north-star row (full scope only): at 64
    # slots the KV scan (~3.2 GB/substep for gpt2_medium at S=256)
    # dwarfs the weight read, so the 1-byte scan is the dominant-traffic
    # lever — this row measures it end to end through the serving path.
    if llm_only or fast:
        llm_i8 = {"skipped": "llm/fast scope"}
    else:
        llm_i8 = bench_llm_serving(quantize_kv=True, **llm_kwargs)
    vision = {}
    targets = (
        {} if llm_only
        else {"resnet50": VISION_BASELINES["resnet50"]} if fast
        else VISION_BASELINES
    )
    for name, (baseline, batches) in targets.items():
        vision[name] = bench_vision_model(name, baseline, batches)
    if llm_only:
        asr = {"skipped": "llm scope"}
    else:
        # Fast mode swaps in the tiny ASR config and short audio: the
        # point is exercising the path, not timing a 1.6B-param encoder.
        asr = bench_asr_rtf(
            batch=2 if fast else 8,
            audio_s=2.0 if fast else 30.0,
            decode_tokens=8 if fast else 32,
            model_name="whisper_tiny_test" if fast
            else "whisper_large_v3",
        )
    if llm_only:
        llama8b = {"skipped": "llm scope"}
    elif fast:
        llama8b = {"skipped": "fast mode"}
    else:
        llama8b = bench_llama3_8b()
        if "skipped" in llama8b:  # a skip is said, never a silent pass
            _log(f"llama3_8b SKIPPED: {llama8b['skipped']}")
    return {
        "metric": "llm_tok_s_per_chip",
        "value": llm.get("tok_s_per_chip", 0.0),
        "unit": "tok/s",
        "vs_baseline": round(
            llm.get("tok_s_per_chip", 0.0) / NORTH_STAR_TOK_S, 3),
        # The device that produced these numbers, as JAX reports it.
        **stamp,
        "scope": "llm" if llm_only else "fast" if fast else "full",
        "mesh": mesh,
        "spec": spec,
        "long_frac": long_frac,
        "ttft_p50_ms": llm["ttft_p50_ms"],
        "ttft_p99_ms": llm["ttft_p99_ms"],
        "llm": llm,
        "llm_int8_kv": llm_i8,
        "llama3_8b": llama8b,
        "vision": vision,
        "asr": asr,
    }


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--mesh", type=int, choices=(1, 2, 4), default=None,
        help="serve the llm rows through an N-chip TP slice (the mesh "
             "placement A/B axis, ROADMAP item 2; also "
             "RDB_BENCH_MESH=N)",
    )
    ap.add_argument(
        "--spec", choices=("on", "off"), default=None,
        help="attach the gpt2_draft speculative companion to the llm "
             "rows (ISSUE 13's A/B axis; also RDB_BENCH_SPEC=1; "
             "rows stamp the acceptance rate; NOT with --mesh > 1 — "
             "the engine rejects spec+mesh)",
    )
    ap.add_argument(
        "--long-frac", type=float, default=None,
        help="fraction of over-bucket (~3x) prompts mixed into the llm "
             "phases (also RDB_BENCH_LONG_FRAC; they admit as "
             "multi-chunk trains)",
    )
    cli = ap.parse_args()
    if cli.mesh is not None:
        os.environ["RDB_BENCH_MESH"] = str(cli.mesh)
    if cli.spec is not None:
        os.environ["RDB_BENCH_SPEC"] = "1" if cli.spec == "on" else "0"
    if cli.long_frac is not None:
        os.environ["RDB_BENCH_LONG_FRAC"] = str(cli.long_frac)
    print(json.dumps(main()))
