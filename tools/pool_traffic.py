"""Does any hot program move the KV pool? Compile only, no chip.

For a configuration under ``benchmark/configs/``, compile the engine's
decode program at every horizon and its chunk program at every (bucket,
group width) for a described, unattached TPU v5e — the pool described as
the engine holds it: in the device's default layout, which is what the
paged kernel reads (``ops/decode_attention.py::_pool_positions_minor``) —
and count in the optimised HLO, per program:

- whole-pool copies: operations other than the in-place page writes whose
  result is as large as the pool's k or v (the layout conversions the
  trace shows as ``copy_bf16_<pool shape>``);
- layer-sized operations: results as large as one layer of the pool (the
  slices XLA materialises in front of a kernel that is handed
  ``pool[layer]``: ``slice_bitcast_fusion_bf16_<layer shape>``);
- the program's temporaries (``memory_analysis().temp_size_in_bytes``).

Exits non-zero if either count is above 0 in any program.

    JAX_PLATFORMS=cpu python -m tools.pool_traffic --config gpt2-medium

By hand and in a builder's chip session only: it loads libtpu in this
process, so never import it from a test file. Nothing runs: a compile
that passes says nothing about times or results.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# Operations that name or pass on a buffer without making one.
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
              "conditional", "call", "custom-call", "optimization-barrier"}
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\][^ ]* ([\w\-]+)\(")


def count_pool_traffic(hlo: str, pool_shape, dtype: str):
    """(whole-pool copies, layer-sized operations) of an optimised HLO
    module: top-level instructions (fusion bodies skipped) that make a
    buffer of the pool's or a layer's size in the pool's dtype. An
    in-place write into the pool is a fusion or scatter ALIASED onto its
    operand; XLA prints none of that per instruction, so a pool-sized
    fusion counts only if it holds no dynamic-update-slice or scatter."""
    import math

    pool_n = math.prod(pool_shape)
    layer_n = pool_n // pool_shape[0]
    in_place = set()   # fused computations that update their operand
    body, name = [], None
    for line in hlo.splitlines():
        m = re.match(r"^%?(fused_computation[\w.\-]*) ", line)
        if m:
            name, body = m.group(1), []
        elif name is not None:
            body.append(line)
            if line.startswith("}"):
                if any(" dynamic-update-slice(" in b or " scatter(" in b
                       for b in body):
                    in_place.add(name)
                name = None
    pools = layers = 0
    fused = False
    for line in hlo.splitlines():
        if re.match(r"^%?fused_computation", line):
            fused = True
        elif line.startswith("}"):
            fused = False
        if fused:
            continue
        m = _INSTR.match(line)
        if not m or m.group(1) != dtype or m.group(3) in _NO_BUFFER:
            continue
        n = math.prod(int(d) for d in m.group(2).split(",") if d)
        op = m.group(3)
        if n == pool_n:
            called = re.search(r"calls=%?([\w.\-]+)", line)
            if op in ("dynamic-update-slice", "scatter") or (
                    op == "fusion" and called
                    and called.group(1) in in_place):
                continue
            pools += 1
        elif n == layer_n:
            layers += 1
    return pools, layers


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
    from ray_dynamic_batching_tpu.engine.queue import RequestQueue
    from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
    from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
    from ray_dynamic_batching_tpu.ops import attention

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="name of a file under benchmark/configs/")
    ap.add_argument("--layers", type=int, default=0,
                    help="compile this many layers instead of the "
                         "configuration's (a quick look: zero stays zero)")
    ap.add_argument("--hlo-dir", default="",
                    help="write each program's optimised HLO here")
    a = ap.parse_args()
    cfg = json.loads(
        (REPO / "benchmark" / "configs" / f"{a.config}.json").read_text())
    dcfg = dict(cfg["program"]["decoder_config"])
    if a.layers:
        dcfg["num_layers"] = a.layers
    llm = {k: v for k, v in cfg["deployment"]["llm"].items()
           if k != "default_max_new_tokens"}
    dtype = jnp.dtype(cfg["program"]["dtype"])
    model = CausalLM(DecoderConfig(**dcfg), name="pool_traffic", dtype=dtype)

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dt, sharding=one):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    # Zeros at the real shapes: the engine wants arrays to build itself
    # round; only their shapes reach the compiler.
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    engine = DecodeEngine(
        model, params, RequestQueue("pool_traffic", max_len=16), **llm)
    cache = engine._cache

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: sds(x.shape, x.dtype), tree)

    p_d, c_d = described(params), described(cache)
    counts_d = described(engine._counts)
    print(f"{a.config}: {dcfg['num_layers']} layers, pool "
          f"{tuple(cache.k.shape)} {cache.k.dtype}", flush=True)

    # Steer the dispatcher as the chip would: kernels on, Mosaic lowering.
    attention.set_attention_backend("pallas")
    jax.default_backend = lambda: "tpu"  # noqa: the rehearsal's steering
    B, K = engine.num_slots, engine.max_bias_entries
    i32, f32 = jnp.int32, jnp.float32

    decode = engine._decode_fn.__wrapped__
    chunk = engine._chunk_paged_fn.__wrapped__
    todo = [
        (f"decode_step h={h}", lambda h=h: decode.lower(
            p_d, c_d, sds((4, B), i32), h, sds((4, B), f32),
            sds((2, B), i32), sds((B, K), i32), sds((B, K), f32), counts_d,
            sds((B,), i32)))
        for h in sorted({1, engine.ttft_horizon, engine.decode_horizon})
    ] + [
        (f"chunk_prefill W={b} g={g}", lambda b=b, g=g: chunk.lower(
            p_d, sds(engine._new_chunk_group(g, b)[0].shape, i32), c_d))
        for b in engine.prompt_buckets for g in engine._admit_group_sizes()
    ]
    bad = 0
    print("| program | whole-pool copies | layer-sized operations | "
          "temporaries, MB | compile, s |\n| --- | --- | --- | --- | --- |")
    for name, lower in todo:
        t = time.monotonic()
        compiled = lower().compile()
        secs = time.monotonic() - t
        text = compiled.as_text()
        if a.hlo_dir:
            out = pathlib.Path(a.hlo_dir)
            out.mkdir(parents=True, exist_ok=True)
            (out / (re.sub(r"\W+", "_", name) + ".hlo")).write_text(text)
        pools, layers = count_pool_traffic(
            text, tuple(cache.k.shape), str(cache.k.dtype).replace(
                "bfloat16", "bf16").replace("int8", "s8").replace(
                "float32", "f32"))
        temp = compiled.memory_analysis().temp_size_in_bytes
        bad += pools + layers
        print(f"| {name} | {pools} | {layers} | {temp / 1e6:,.0f} | "
              f"{secs:.0f} |", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
