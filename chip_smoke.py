"""Chip smoke: the LLM serving path, once, on the accelerator.

``python chip_smoke.py`` drives the system's main path through the entry
points a user calls — the declarative ``llm:`` target applied with
``serve.schema.apply_config`` (ServeController -> Router -> LLMReplica ->
DecodeEngine, plus the HTTPProxy) — at the full width of ``gpt2_medium``
with seeded random weights, and checks what comes out by the repo's own
means. Phases, in order; the first that fails ends the run non-zero:

  device       jax.devices() is a TPU; HBM budget fits what it reports
  kernels      every Pallas attention kernel vs the XLA reference
  serve-paged  paged pool + chunked prefill, HTTP + handle requests
  four-chips   four pinned replicas, then one TP=4 replica (>= 4 devices)

The serve phase asserts zero compiles after warmup and prints the
attention path every hot program compiled to (the *paths* table).

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without an accelerator the script exits non-zero and prints no result.
The phases are plain functions of a model name and sizes, so tier-1
drives them at ``llama_tiny`` on the CPU (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, for the set-up clock

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence  # noqa: E402

# (name, query heads N, kv heads K, head dim H) — gpt2_medium's MHA
# geometry (H=64: half a lane tile), LLAMA3_8B's GQA geometry, and the
# head slice one shard of a TP=4 gpt2_medium replica streams (4 heads:
# the head block spans the local axis instead of tiling it by 8).
KERNEL_GEOMETRIES = (
    ("gpt2_medium", 16, 16, 64),
    ("llama3_8b", 32, 8, 128),
    ("gpt2_medium/tp4", 4, 4, 64),
)

# bf16 tolerance vs the f32 "highest"-precision reference: the kernels
# round probabilities and outputs to bf16 (8 mantissa bits, 2^-8 ~ 4e-3
# relative) on O(1) values; a wrong head or a dropped tile is an O(1)
# error, two orders above this.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2


class PhaseFailed(RuntimeError):
    """A smoke phase did not meet its contract."""


def _say(msg: str) -> None:
    print(msg, flush=True)


# --- phase: device ---------------------------------------------------------
def phase_device() -> Dict[str, Any]:
    """The accelerator JAX sees, its versions, and the HBM budget check
    (``utils/config.py`` assumes a budget; the device reports a limit)."""
    import jax
    import jaxlib

    from ray_dynamic_batching_tpu.utils.config import get_config

    devices = jax.devices()
    dev = devices[0]
    stamp = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }
    if dev.platform != "tpu":
        raise PhaseFailed(
            f"no TPU: jax.devices()[0].platform == {dev.platform!r}"
        )
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    budget = get_config().hbm_budget_bytes
    _say(f"device: {stamp['platform']} {stamp['kind']!r} x{stamp['count']}"
         f"  jax {jax.__version__} jaxlib {jaxlib.__version__} "
         f"libtpu {libtpu_version}")
    _say(f"device: memory_stats bytes_limit={limit} "
         f"hbm_budget_bytes={budget}")
    if limit is None:
        raise PhaseFailed("device reports no bytes_limit to check the "
                          "HBM budget against")
    if budget > limit:
        raise PhaseFailed(
            f"hbm_budget_bytes {budget} exceeds the device's bytes_limit "
            f"{limit}"
        )
    return stamp


# --- phase: kernels --------------------------------------------------------
def _reference(q, k, v, mask, causal=False):
    """``_xla_attention`` in f32 at "highest" matmul precision — on a
    TPU an f32 matmul otherwise runs as bf16 passes."""
    import jax
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.ops.attention import _xla_attention

    with jax.default_matmul_precision("highest"):
        return _xla_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=causal, mask=mask, scale=None,
        )


def kernel_cases(N: int, K: int, H: int, *, slots: int, capacity: int,
                 page_size: int, window: int, prefill_len: int,
                 seed: int = 0):
    """Yield ``(label, kernel, reference, args)`` for every kernel entry
    point at one head geometry: ``kernel(why, *args)`` calls the Pallas
    wrapper directly (None = declined, the reason appended to ``why``),
    ``reference(*args)`` computes the same attention the plain way."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_dynamic_batching_tpu.models.decoder import paged_window_mask
    from ray_dynamic_batching_tpu.models.kv_state import (
        dequantize_kv,
        quantize_kv_rows,
    )
    from ray_dynamic_batching_tpu.ops import decode_attention as da
    from ray_dynamic_batching_tpu.ops import flash_attention as fa

    B, S, ps, T = slots, capacity, page_size, prefill_len
    NP = S // ps
    P = B * NP + 3
    Se = 1792  # see the budget-edge case below
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape, np.float32).astype(jnp.bfloat16)

    host = {
        "k": normal(B, S, K, H), "v": normal(B, S, K, H),
        "k_fill": normal(P, ps, K, H), "v_fill": normal(P, ps, K, H),
        "q1": normal(B, 1, N, H), "qw": normal(B, window, N, H),
        "qp": normal(B, T, N, H), "kp": normal(B, T, K, H),
        "vp": normal(B, T, K, H),
        "ke": normal(2, Se, K, H), "ve": normal(2, Se, K, H),
        # Lengths reach into the last page so every page of a slot is
        # scanned and the online-softmax carry crosses grid steps.
        "lengths": rng.integers(ps + 1, S - window - 1, size=B).astype(
            np.int32),
        # A random physical placement of each slot's logical run; the
        # pool's other pages hold garbage no slot may read.
        "table": rng.permutation(P)[: B * NP].reshape(B, NP).astype(
            np.int32),
        "plen": rng.integers(T // 2, T + 1, size=B).astype(np.int32),
    }

    @jax.jit
    def derive(a):
        def paged(slab, filler):
            return filler.at[a["table"].reshape(-1)].set(
                slab.reshape((B * NP, ps) + slab.shape[2:]))

        kc, ks = quantize_kv_rows(a["k"])
        vc, vs = quantize_kv_rows(a["v"])
        pos = jnp.arange(S)[None, None, None, :]
        row = jnp.arange(T)[None, None, :, None]
        return dict(
            a,
            kc=kc, ks=ks, vc=vc, vs=vs,
            k_deq=dequantize_kv(kc, ks, jnp.float32),
            v_deq=dequantize_kv(vc, vs, jnp.float32),
            k_pool=paged(a["k"], a["k_fill"]),
            v_pool=paged(a["v"], a["v_fill"]),
            kc_pool=paged(kc, jnp.zeros((P, ps, K, H), jnp.int8)),
            vc_pool=paged(vc, jnp.zeros((P, ps, K, H), jnp.int8)),
            ks_pool=paged(ks, jnp.ones((P, ps, K), jnp.float32)),
            vs_pool=paged(vs, jnp.ones((P, ps, K), jnp.float32)),
            win1=paged_window_mask(a["lengths"], S, 1),
            winw=paged_window_mask(a["lengths"], S, window),
            wine=paged_window_mask(
                jnp.asarray([Se - 2, Se // 2], jnp.int32), Se, 1),
            # The slab prefill's mask: T prompt rows against the whole
            # cache row, causal within the prompt, padding voided.
            pmask=(pos <= row) & (pos < a["plen"][:, None, None, None]),
        )

    d = derive(host)
    for Tq, q, win in ((1, d["q1"], d["win1"]), (window, d["qw"], d["winw"])):
        yield (
            f"decode_attention bf16 Tq={Tq}",
            lambda why, q, k, v, m: da.decode_attention(
                q, k, v, mask=m, why=why),
            _reference, (q, d["k"], d["v"], win),
        )
        yield (
            f"decode_attention int8 Tq={Tq}",
            lambda why, q, kc, vc, m, ks, vs, kd, vd: da.decode_attention(
                q, kc, vc, mask=m, k_scale=ks, v_scale=vs, why=why),
            lambda q, kc, vc, m, ks, vs, kd, vd: _reference(q, kd, vd, m),
            (q, d["kc"], d["vc"], win, d["ks"], d["vs"], d["k_deq"],
             d["v_deq"]),
        )
        yield (
            f"paged_decode_attention bf16 Tq={Tq}",
            lambda why, q, kp, vp, pt, ln, k, v, m:
            da.paged_decode_attention(q, kp, vp, pt, ln, why=why),
            lambda q, kp, vp, pt, ln, k, v, m: _reference(q, k, v, m),
            (q, d["k_pool"], d["v_pool"], d["table"], d["lengths"],
             d["k"], d["v"], win),
        )
        yield (
            f"paged_decode_attention int8 Tq={Tq}",
            lambda why, q, kp, vp, pt, ln, ks, vs, kd, vd, m:
            da.paged_decode_attention(
                q, kp, vp, pt, ln, k_scale=ks, v_scale=vs, why=why),
            lambda q, kp, vp, pt, ln, ks, vs, kd, vd, m:
            _reference(q, kd, vd, m),
            (q, d["kc_pool"], d["vc_pool"], d["table"], d["lengths"],
             d["ks_pool"], d["vs_pool"], d["k_deq"], d["v_deq"], win),
        )
    # The engine never passes block_k; this forces the slab kernel's
    # scratch carry across KV tiles the way the paged kernel's pages do.
    yield (
        f"decode_attention bf16 Tq=1 block_k={ps}",
        lambda why, q, k, v, m: da.decode_attention(
            q, k, v, mask=m, block_k=ps, why=why),
        _reference, (d["q1"], d["k"], d["v"], d["win1"]),
    )
    # A long slab whose picked KV tile sits at the VMEM block budget's
    # edge (8-head blocks: sb=896 of S=1792 streams 14.1 of the 15 MiB
    # budget double-buffered), scratch and temporaries on top.
    yield (
        f"decode_attention bf16 Tq=1 S={Se} (budget-edge tile)",
        lambda why, q, k, v, m: da.decode_attention(
            q, k, v, mask=m, why=why),
        _reference, (d["q1"][:2], d["ke"], d["ve"], d["wine"]),
    )
    yield (
        f"flash_attention causal T={T}",
        lambda why, q, k, v: fa.flash_attention(
            q, k, v, causal=True, why=why),
        lambda q, k, v: _reference(q, k, v, None, causal=True),
        (d["qp"], d["kp"], d["vp"]),
    )
    yield (
        f"flash_attention masked T={T} S={S}",
        lambda why, q, k, v, m: fa.flash_attention(
            q, k, v, mask=m, why=why),
        _reference, (d["qp"], d["k"], d["v"], d["pmask"]),
    )


def phase_kernels(
    geometries: Sequence = KERNEL_GEOMETRIES, *, slots: int = 8,
    capacity: int = 512, page_size: int = 128, window: int = 4,
    prefill_len: int = 128,
) -> List[Dict[str, Any]]:
    """Call every Pallas attention wrapper directly, on the default
    backend, and compare with the reference. Runs every case before
    failing, so one chip call reports every kernel the compiler
    refuses."""
    import functools

    import jax
    import numpy as np

    rows: List[Dict[str, Any]] = []
    for name, N, K, H in geometries:
        for label, kernel, reference, args in kernel_cases(
                N, K, H, slots=slots, capacity=capacity,
                page_size=page_size, window=window,
                prefill_len=prefill_len):
            row: Dict[str, Any] = {"geometry": name, "case": label}
            try:
                why: List[str] = []
                out = jax.jit(functools.partial(kernel, why))(*args)
                if out is None:
                    raise PhaseFailed("wrapper declined: " + "; ".join(why))
                out = np.asarray(jax.block_until_ready(out), np.float32)
                want = np.asarray(jax.jit(reference)(*args), np.float32)
                if out.shape != want.shape:
                    raise PhaseFailed(
                        f"shape {out.shape} != reference {want.shape}")
                if not np.all(np.isfinite(out)):
                    raise PhaseFailed("non-finite output")
                err = float(np.max(np.abs(out - want)))
                row["max_abs_err"] = round(err, 5)
                if not np.allclose(out, want, atol=KERNEL_ATOL,
                                   rtol=KERNEL_RTOL):
                    raise PhaseFailed(
                        f"max |err| {err:.4f} over tolerance "
                        f"(atol {KERNEL_ATOL}, rtol {KERNEL_RTOL})")
                row["ok"] = True
            except Exception as e:  # noqa: BLE001 — reported, then the phase fails
                row["ok"] = False
                row["error"] = f"{type(e).__name__}: {e}"
            rows.append(row)
            _say(f"kernels: {name:15s} {label:40s} "
                 + (f"ok  max|err|={row['max_abs_err']}" if row["ok"]
                    else "FAILED " + row["error"][:2000]))
    bad = [r for r in rows if not r["ok"]]
    if bad:
        raise PhaseFailed(
            f"{len(bad)} of {len(rows)} kernel cases failed: "
            + "; ".join(f"{r['geometry']} {r['case']}" for r in bad)
        )
    return rows


# --- phase: serve-paged -----------------------------------------------------
# Programs whose attention path the *paths* table must account for: the
# decode scan, and the admission (chunk) program.
HOT_PROGRAMS = ("decode_step", "chunk_prefill")


def smoke_requests(vocab: int, n: int, lo: int, hi: int, max_new: int,
                   seed: int = 0) -> List[Dict[str, Any]]:
    """The seeded request set: ``n`` greedy requests, prompt lengths
    spread evenly over [lo, hi] then shuffled, ``max_new`` tokens each."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = np.linspace(lo, hi, n).astype(int)
    rng.shuffle(lengths)
    return [
        {"tokens": rng.integers(1, vocab, size=int(L)).tolist(),
         "max_new_tokens": max_new}
        for L in lengths
    ]


def serve_document(model: str, *, num_slots: int, max_len: int,
                   prompt_buckets: Sequence[int], max_new: int,
                   num_replicas: int = 1, chips_per_replica: int = 0,
                   llm_options: Optional[Dict[str, Any]] = None,
                   ) -> Dict[str, Any]:
    """The declarative config a user writes (serve/schema.py): one
    application, one built-in ``llm:`` deployment, published on a route.
    Nothing is set beyond sizes — the constructor defaults are the paged
    pool with chunked admission; ``llm_options`` are further ``llm:``
    knobs (the four-chip phases trim the number of programs each replica
    compiles)."""
    llm: Dict[str, Any] = {
        "model": model,
        "num_slots": num_slots,
        "max_len": max_len,
        "prompt_buckets": list(prompt_buckets),
        "default_max_new_tokens": max_new,
    }
    llm.update(llm_options or {})
    deployment: Dict[str, Any] = {
        "name": f"{model}-smoke", "llm": llm,
        "num_replicas": num_replicas,
        "max_ongoing_requests": 4096,
    }
    if chips_per_replica:
        deployment["chips_per_replica"] = chips_per_replica
    return {"applications": [{
        "name": "smoke",
        "route_prefix": "/smoke",
        "deployments": [deployment],
    }]}


def _http_generate(host: str, port: int, path: str, payload: Dict[str, Any],
                   timeout_s: float) -> Dict[str, Any]:
    """One request through the HTTP proxy; a streaming request's NDJSON
    chunk lines must add up to the final result's tokens."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        conn.request("POST", path, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read().decode()
    finally:
        conn.close()
    if resp.status != 200:
        raise PhaseFailed(f"HTTP {resp.status} from {path}: {body[:300]}")
    if not payload.get("stream"):
        return json.loads(body)["result"]
    lines = [json.loads(line) for line in body.splitlines() if line]
    chunks = [line["chunk"] for line in lines if "chunk" in line]
    finals = [line["result"] for line in lines if "result" in line]
    if len(finals) != 1 or chunks != finals[0]["tokens"]:
        raise PhaseFailed(
            f"stream from {path}: {len(chunks)} chunk lines do not add up "
            f"to the final result ({len(finals)} result lines)")
    return finals[0]


def report_paths(allow_interpret: bool) -> List[Dict[str, Any]]:
    """The *paths* table: for every program that traced since the
    deployment came up, which attention path each call site compiled to and
    why any kernel declined — read from the dispatcher's trace-time
    record, not guessed from shapes. Fails when a hot program took the
    XLA reference, ran a kernel interpreted, or left no record."""
    from ray_dynamic_batching_tpu.ops.attention import (
        PATH_XLA,
        attention_paths,
    )

    records = attention_paths()
    table: Dict[tuple, Dict[str, Any]] = {}
    for r in records:
        key = (r.program, r.describe(), r.q_shape, r.kv_shape, r.kv_dtype,
               r.declines)
        row = table.setdefault(key, {
            "program": r.program, "path": r.describe(),
            "q": list(r.q_shape), "kv": list(r.kv_shape),
            "kv_dtype": r.kv_dtype, "declines": list(r.declines),
            "calls": 0,
        })
        row["calls"] += 1
    rows = list(table.values())
    for row in rows:
        _say(f"paths: {row['program'] or '<no program>':14s} "
             f"q{row['q']} kv{row['kv']} {row['kv_dtype']} x{row['calls']}"
             f" -> {row['path']}")
        for reason in row["declines"]:
            _say(f"paths:     declined: {reason}")
    hot = [r for r in records if r.program in HOT_PROGRAMS]
    interpreted = sum(r.interpret for r in hot)
    _say(f"paths: {interpreted} of {len(hot)} hot-program attention "
         "calls ran interpreted")
    on_xla = sorted({r.program for r in hot if r.path == PATH_XLA})
    if on_xla:
        raise PhaseFailed(
            f"{on_xla} compiled to the XLA reference at this "
            "geometry (reasons above)")
    missing = set(HOT_PROGRAMS) - {r.program for r in hot}
    if missing:
        raise PhaseFailed(
            f"no attention path recorded for {sorted(missing)}")
    if interpreted and not allow_interpret:
        raise PhaseFailed(
            f"{interpreted} hot-program kernel calls ran in "
            "interpret mode")
    return rows


def phase_serve(
    model: str, *, num_slots: int, max_len: int,
    prompt_buckets: Sequence[int], requests: List[Dict[str, Any]],
    http_requests: int = 6, timeout_s: float = 300.0,
    allow_interpret: bool = False, controller: Any = None,
    num_replicas: int = 1, chips_per_replica: int = 0,
    llm_options: Optional[Dict[str, Any]] = None, inspect: Any = None,
) -> Dict[str, Any]:
    """Deploy the model the way the README does, answer the request set
    (the first ``http_requests`` over HTTP, the first of those
    streaming; the rest through the DeploymentHandle), and check it:
    every request succeeded with the tokens asked for and a TTFT, zero
    compiles after warmup, every hot program on a kernel path.
    ``inspect(handle)`` runs before teardown (the four-chip checks)."""
    import concurrent.futures as cf

    from ray_dynamic_batching_tpu import serve
    from ray_dynamic_batching_tpu.ops.attention import clear_attention_paths
    from ray_dynamic_batching_tpu.serve.schema import (
        ServeConfigSchema,
        apply_config,
    )
    from ray_dynamic_batching_tpu.utils.compile_ledger import (
        PHASE_WARMUP,
        get_ledger,
    )

    ledger = get_ledger()
    clear_attention_paths()
    warm_before = sum(ledger.counts(phase=PHASE_WARMUP).values())
    max_new = requests[0]["max_new_tokens"]
    doc = serve_document(
        model, num_slots=num_slots, max_len=max_len,
        prompt_buckets=prompt_buckets, max_new=max_new,
        num_replicas=num_replicas, chips_per_replica=chips_per_replica,
        llm_options=llm_options,
    )
    name = doc["applications"][0]["deployments"][0]["name"]
    route = doc["applications"][0]["route_prefix"]
    t_deploy = time.perf_counter()
    handle = apply_config(
        ServeConfigSchema.from_dict(doc), controller=controller
    )[name]
    try:
        ready = time.perf_counter()
        out: Dict[str, Any] = {
            "replicas": f"{num_replicas} x {max(1, chips_per_replica)}",
            "setup_s": round(ready - t_deploy, 1),
            "ready_since_process_start_s": round(ready - _T0, 1),
            "warmup_compile_episodes": sum(
                ledger.counts(phase=PHASE_WARMUP).values()) - warm_before,
        }
        running = len(handle.router.replicas())
        if running != num_replicas:
            raise PhaseFailed(
                f"serve: {running} of {num_replicas} replicas started")
        _say(f"serve: ready in {out['setup_s']}s "
             f"({out['ready_since_process_start_s']}s since process start),"
             f" {out['warmup_compile_episodes']} warmup compile episodes, "
             f"{running} replica(s)")

        proxy = serve.api.get_proxy()
        host = "127.0.0.1" if proxy.host in ("0.0.0.0", "") else proxy.host
        n_http = min(http_requests, len(requests))
        t_send = time.perf_counter()
        with cf.ThreadPoolExecutor(max_workers=max(1, n_http)) as pool:
            http_futs = [
                pool.submit(
                    _http_generate, host, proxy.port, route,
                    dict(req, stream=True) if i == 0 else req, timeout_s,
                )
                for i, req in enumerate(requests[:n_http])
            ]
            handle_futs = [
                handle.remote(req, slo_ms=timeout_s * 1000.0)
                for req in requests[n_http:]
            ]
            results: List[Dict[str, Any]] = [
                f.result(timeout=timeout_s) for f in http_futs
            ]
            for f in handle_futs:
                r = f.result(timeout=timeout_s)
                results.append({
                    "tokens": list(r.tokens),
                    "finish_reason": r.finish_reason,
                    "ttft_ms": r.ttft_ms,
                })
        out["serve_s"] = round(time.perf_counter() - t_send, 2)

        for i, (req, res) in enumerate(zip(requests, results)):
            toks = res["tokens"]
            if (len(toks) != req["max_new_tokens"]
                    or res["finish_reason"] != "length"):
                raise PhaseFailed(
                    f"serve: request {i} returned {len(toks)} tokens, "
                    f"finish_reason {res['finish_reason']!r}; asked for "
                    f"{req['max_new_tokens']}")
            if not all(isinstance(t, int) and t >= 0 for t in toks):
                raise PhaseFailed(f"serve: request {i} token ids {toks}")
            ttft = res["ttft_ms"]
            if not (isinstance(ttft, (int, float)) and 0 < ttft < 1e7):
                raise PhaseFailed(f"serve: request {i} TTFT {ttft!r}")
        out["tokens"] = [res["tokens"] for res in results]
        _say(f"serve: {len(results)} requests answered "
             f"({n_http} over HTTP, 1 streaming) in {out['serve_s']}s")

        # Zero compiles after warmup — NOT softened: this is what catches
        # a kernel the compiler refuses in a program warmup did not cover
        # (the engine loop would retry it for ever) and a leaf whose
        # placement drifted between warmup and serving.
        ledger.check_steady()
        _say(f"serve: zero compiles after warmup")
        out["paths"] = report_paths(allow_interpret)
        if inspect is not None:
            inspect(handle)
        return out
    finally:
        if controller is not None:
            controller.delete_deployment(name)
        else:
            serve.delete(name)


# --- phase: four chips -----------------------------------------------------
def _check_pinned(handle: Any) -> None:
    """Four one-chip replicas: each engine's params and cache live on its
    own reserved chip, no two share one, and each served something."""
    replicas = handle.router.replicas()
    seen: Dict[Any, str] = {}
    for rep in replicas:
        if not rep.devices or len(rep.devices) != 1:
            raise PhaseFailed(f"{rep.replica_id}: devices {rep.devices}")
        resident = rep.engine.resident_devices()
        if resident != set(rep.devices):
            raise PhaseFailed(
                f"{rep.replica_id}: params/cache on {sorted(map(str, resident))}"
                f", reserved {[str(d) for d in rep.devices]}")
        dev = rep.devices[0]
        if dev in seen:
            raise PhaseFailed(
                f"{rep.replica_id} and {seen[dev]} share {dev}")
        seen[dev] = rep.replica_id
        done = int(rep.stats()["completed"])
        _say(f"four-chips: {rep.replica_id} on {dev} completed {done}")
        if done < 1:
            raise PhaseFailed(f"{rep.replica_id} served no request")


def _logit_gap(model: Any, params_a: Any, params_b: Any,
               sequence: List[int]) -> Dict[str, float]:
    """Next-token logits for ``sequence`` under two placements of the
    same weights (teacher-forced full forward, XLA attention): how far
    apart they are, and the top-1 margin a difference has to beat to
    flip a greedy token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_dynamic_batching_tpu.ops import attention

    L = len(sequence)
    T = -(-L // 64) * 64  # pad: one compile per 64-token length class
    tokens = np.zeros((1, T), np.int32)
    tokens[0, :L] = sequence
    mask = (np.arange(T)[None, :] < L).astype(np.int32)
    attention.set_attention_backend("xla")
    try:
        fwd = jax.jit(lambda p, t, m: model.apply(p, t, m)[0, L - 1])
        a = np.asarray(fwd(params_a, jnp.asarray(tokens),
                           jnp.asarray(mask)), np.float32)
        b = np.asarray(fwd(params_b, jnp.asarray(tokens),
                           jnp.asarray(mask)), np.float32)
    finally:
        attention.set_attention_backend("auto")
    top2 = np.sort(a)[-2:]
    return {"max_abs_diff": float(np.max(np.abs(a - b))),
            "top1_margin": float(top2[1] - top2[0])}


def phase_four_chips(
    model: str, *, num_slots: int, max_len: int,
    prompt_buckets: Sequence[int], requests: List[Dict[str, Any]],
    timeout_s: float = 300.0, allow_interpret: bool = False,
    devices: Optional[Sequence] = None, tp: int = 4,
    llm_options: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Four pinned one-chip replicas behind one router, then one
    TP=``tp`` replica, both paged, both answering the same seeded
    requests. The TP replica's greedy tokens must equal the one-chip
    replicas', or each first divergence must be a near-tie the measured
    logit difference between the two placements explains. (``tp`` is 4
    on the chip; tier-1 drives ``llama_tiny``, whose two KV heads split
    two ways.)"""
    import jax

    from ray_dynamic_batching_tpu.parallel.placement import PlacementManager
    from ray_dynamic_batching_tpu.serve.controller import ServeController

    ctl = ServeController(placement=PlacementManager(
        list(devices) if devices is not None else jax.devices()[:4]
    ))
    ctl.start()
    kept: Dict[str, Any] = {}

    def keep_one_chip(handle: Any) -> None:
        _check_pinned(handle)
        engine = handle.router.replicas()[0].engine
        kept["model"], kept["params"] = engine.model, engine.params

    def compare_tp(handle: Any) -> None:
        (rep,) = handle.router.replicas()
        if not rep.devices or len(rep.devices) != tp:
            raise PhaseFailed(f"TP replica devices {rep.devices}")
        if rep.engine.resident_devices() != set(rep.devices):
            raise PhaseFailed(f"TP replica state is not on its {tp} chips")
        kept["tp_params"] = rep.engine.params

    try:
        pinned = phase_serve(
            model, num_slots=num_slots, max_len=max_len,
            prompt_buckets=prompt_buckets, requests=requests,
            timeout_s=timeout_s, allow_interpret=allow_interpret,
            controller=ctl, num_replicas=4, chips_per_replica=1,
            llm_options=llm_options, inspect=keep_one_chip,
        )
        served_tp = phase_serve(
            model, num_slots=num_slots, max_len=max_len,
            prompt_buckets=prompt_buckets, requests=requests,
            timeout_s=timeout_s, allow_interpret=allow_interpret,
            controller=ctl, num_replicas=1, chips_per_replica=tp,
            llm_options=llm_options, inspect=compare_tp,
        )
        if not any(row["program"] == "decode_step"
                   and f"shard_map tp={tp}" in row["path"]
                   for row in served_tp["paths"]):
            raise PhaseFailed(
                f"TP={tp} decode_step did not compile to the shard_map'd "
                "paged kernel")
        diverged = [
            (i, next(j for j, (x, y) in enumerate(zip(a, b)) if x != y))
            for i, (a, b) in enumerate(
                zip(pinned["tokens"], served_tp["tokens"]))
            if a != b
        ]
        _say(f"four-chips: TP={tp} tokens equal the one-chip replicas' on "
             f"{len(requests) - len(diverged)} of {len(requests)} requests")
        unexplained = []
        for i, j in diverged[:4]:
            seq = requests[i]["tokens"] + pinned["tokens"][i][:j]
            gap = _logit_gap(kept["model"], kept["params"],
                             kept["tp_params"], seq)
            explained = gap["top1_margin"] <= 2.0 * gap["max_abs_diff"]
            _say(f"four-chips: request {i} diverges at token {j}: logits "
                 f"differ by max {gap['max_abs_diff']:.4f} between the "
                 f"placements, one-chip top-1 margin "
                 f"{gap['top1_margin']:.4f} -> "
                 + ("a near-tie the difference explains" if explained
                    else "NOT explained"))
            if not explained:
                unexplained.append(i)
        if unexplained:
            raise PhaseFailed(
                f"TP={tp} tokens diverge on requests {unexplained} by more "
                "than the logit difference between placements explains")
        return {"pinned": pinned, "tp": served_tp,
                "diverged": len(diverged)}
    finally:
        kept.clear()
        ctl.shutdown()


# --- main --------------------------------------------------------------------
MODEL = "gpt2_medium"
NUM_SLOTS = 32
MAX_LEN = 512           # four 128-position pages per slot
MAX_NEW = 32
N_REQUESTS = 36
PROMPT_LO, PROMPT_HI = 16, 200
# Prompts over 128 admit as two-chunk trains. Warmup compiles buckets x
# group sizes {1, 2} + three decode horizons.
PAGED_BUCKETS = (64, 128)
# Five replicas each compile their own programs (an executable is keyed
# on its devices), about half a minute apiece cold at this depth: one
# bucket, one group width and two decode horizons make it three per
# replica instead of seven.
FOUR_CHIP_BUCKETS = (128,)
FOUR_CHIP_LLM = {"max_admissions_per_step": 1, "decode_horizon": 2}

PHASES = ("device", "kernels", "serve-paged", "four-chips")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--phases", default=",".join(PHASES),
        help="comma-separated subset to run (device always runs)",
    )
    args = ap.parse_args(argv)
    want = [p for p in args.phases.split(",") if p]
    unknown = set(want) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}; known: {PHASES}")
    from ray_dynamic_batching_tpu import serve
    from ray_dynamic_batching_tpu.models import causal_lm
    from ray_dynamic_batching_tpu.utils import compile_cache

    try:
        stamp = phase_device()
        _say(f"compile cache: {compile_cache.enable()}")
        if "kernels" in want:
            phase_kernels()
        requests = smoke_requests(
            causal_lm.GPT2_MEDIUM.vocab_size, N_REQUESTS, PROMPT_LO,
            PROMPT_HI, MAX_NEW,
        )
        served = []
        if "serve-paged" in want:
            served.append(phase_serve(
                MODEL, num_slots=NUM_SLOTS, max_len=MAX_LEN,
                prompt_buckets=PAGED_BUCKETS, requests=requests))
        if "four-chips" in want:
            if stamp["count"] >= 4:
                four = phase_four_chips(
                    MODEL, num_slots=NUM_SLOTS, max_len=MAX_LEN,
                    prompt_buckets=FOUR_CHIP_BUCKETS, requests=requests,
                    llm_options=FOUR_CHIP_LLM)
                served += [four["pinned"], four["tp"]]
            else:
                _say(f"four-chips: SKIPPED — {stamp['count']} device(s) "
                     "visible, the phase needs 4 (four pinned replicas, "
                     "then one TP=4 replica)")
        for out in served:
            _say(f"set-up: {out['replicas']} chip(s) {out['setup_s']}s "
                 f"(ready {out['ready_since_process_start_s']}s after "
                 f"process start), {out['warmup_compile_episodes']} "
                 "warmup compile episodes")
    except Exception:  # noqa: BLE001 — any failed phase is a failed run
        import traceback

        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr, flush=True)
        return 1
    finally:
        serve.shutdown()
    _say(json.dumps({"ok": True, "device": stamp}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
