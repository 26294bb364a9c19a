"""Rehearsal without the chip (on-chip-measurement guide, section 2.3):
compile a configuration's hot programs — every ``decode_step`` horizon and
every ``chunk_prefill`` shape its deployment warms — at the REAL widths for
a described, unattached TPU v5e, and print ``memory_analysis()`` beside the
configuration file's own reckoning.

    JAX_PLATFORMS=cpu python3 -m benchmark.tests.rehearse_compile \
        --config mistral-7b-v0.3-1chip

Run by hand before the first chip call of a new configuration. Nothing
runs, so this says nothing about times or results; a compile that passes
is not a chip run. It loads libtpu in this process: never import it from a
test file (see the guide on the library's lock).
"""

from __future__ import annotations

import argparse
import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.run import BENCH, load_json
    from ray_dynamic_batching_tpu.engine.decode import DecodeEngine
    from ray_dynamic_batching_tpu.engine.queue import RequestQueue
    from ray_dynamic_batching_tpu.models.causal_lm import CausalLM
    from ray_dynamic_batching_tpu.models.decoder import DecoderConfig
    from ray_dynamic_batching_tpu.ops import attention

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--llm", default="{}",
                    help="JSON overrides of the deployment's llm options, "
                         "to size a configuration before it is written")
    ap.add_argument("--only-decode", action="store_true")
    a = ap.parse_args()
    cfg = load_json(BENCH / "configs" / f"{a.config}.json")
    cfg["deployment"]["llm"].update(json.loads(a.llm))
    dc = DecoderConfig(**cfg["program"]["decoder_config"])
    llm = cfg["deployment"]["llm"]
    dtype = jnp.dtype(cfg["program"]["dtype"])
    model = CausalLM(dc, name="rehearsal", dtype=dtype)

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    # Zeros at the real shapes: the engine wants arrays to build itself
    # round; only their shapes reach the compiler.
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, dtype), shapes)
    weight_bytes = sum(int(np.prod(s.shape)) * dtype.itemsize
                       for s in jax.tree_util.tree_leaves(shapes))
    engine = DecodeEngine(
        model, params, RequestQueue("rehearsal", max_len=16),
        **{k: v for k, v in llm.items() if k != "default_max_new_tokens"})
    cache = engine._cache
    pool_bytes = sum(int(x.size) * x.dtype.itemsize
                     for x in (cache.k, cache.v))
    print(f"config {a.config}: weights {weight_bytes / 1e9:.2f} GB, KV pool "
          f"{pool_bytes / 1e9:.2f} GB ({engine.num_pages} pages x "
          f"{engine.page_size}), {engine.num_slots} slots", flush=True)

    # Steer the dispatcher as the chip would: kernels on, Mosaic lowering.
    attention.set_attention_backend("pallas")
    jax.default_backend = lambda: "tpu"  # noqa: the rehearsal's steering
    B, K = engine.num_slots, engine.max_bias_entries
    i32, f32 = jnp.int32, jnp.float32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    p_d, c_d = described(params), described(cache)
    counts_d = described(engine._counts)
    rows = []
    for h in sorted({1, engine.ttft_horizon, engine.decode_horizon}):
        t = time.monotonic()
        compiled = engine._decode_fn.__wrapped__.lower(
            p_d, c_d, sds((3, B), i32), h, sds((4, B), f32),
            sds((2, B), i32), sds((B, K), i32), sds((B, K), f32), counts_d,
        ).compile()
        rows.append((f"decode_step h={h}", compiled, time.monotonic() - t))
    for b in ([] if a.only_decode else engine.prompt_buckets):
        for g in engine._admit_group_sizes():
            t = time.monotonic()
            compiled = engine._chunk_paged_fn.__wrapped__.lower(
                p_d, sds((2, g, b), i32), c_d,
                sds((g, engine._n_table_entries), i32), sds((6, g), i32),
                sds((2, g), f32), sds((g, K), i32), sds((g, K), f32),
            ).compile()
            rows.append((f"chunk_prefill W={b} g={g}", compiled,
                         time.monotonic() - t))
    for name, compiled, secs in rows:
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(f"{name}: compiled in {secs:.0f}s; arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.2f} GB (aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f}), temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB; tpu_custom_call x"
              f"{text.count('tpu_custom_call')}; 'remat' x"
              f"{text.count('remat')}", flush=True)
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"{name}: live at once {total / 1e9:.2f} GB of the chip's "
              "16 GB", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
