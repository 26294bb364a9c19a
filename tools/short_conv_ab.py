"""Time the gated short convolution alone on the chip at the published
widths (``models/short_conv.py``; one layer of ``lfm2-24b-a2b-ep8-1chip``):
the taps of a 512-row chunk (group widths 1 and 2) and of a decode row of
all slots, in the form the program keeps (the state and the rows joined and
``K`` shifted multiply-adds of the one array, which XLA is left to fuse)
against ``K`` EXPLICIT shifts (each tap's rows built by a pad and a slice of
their own), with and without the two products around them.

    chiprun -- python3 -m tools.short_conv_ab [--config lfm2-24b-a2b-ep8-1chip]

Prints one line a measurement. One call of a program costs the host about
a millisecond here whatever it holds, and these operations are tens of
microseconds: a measurement is ``--inner`` applications CHAINED inside one
program (a ``lax.scan`` that hands the state on, as the decode scan does,
and folds each result's mean into its carry so that none is dropped), the
median of ``--reps`` such calls over ``--inner``, beside the bytes the form
must move at the chip's peak bandwidth. Refuses without a TPU. A later ``perf_opt`` issue
(a Pallas kernel for the taps, the gates and the state's hand-over in one
pass) starts from these numbers."""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HBM_BYTES_PER_S = 819e9     # benchmark/peaks.json, TPU v5 lite


def _time(fn, args, reps: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t)
    return statistics.median(took)


def explicit_shifts(z, state, w):
    """The same ``c`` as ``short_conv.taps`` over ``state | z``, each tap's
    rows an array of its own: tap ``j`` reads the rows ``K - 1 - j`` back,
    the first of them out of the state."""
    import jax.numpy as jnp

    K, T = w.shape[0], z.shape[1]
    w = w.astype(jnp.float32)
    out = w[K - 1] * z.astype(jnp.float32)
    for back in range(1, K):
        head = state[:, state.shape[1] - back:].astype(z.dtype)
        shifted = jnp.concatenate([head, z], axis=1)[:, :T]
        out = out + w[K - 1 - back] * shifted.astype(jnp.float32)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="lfm2-24b-a2b-ep8-1chip")
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--inner", type=int, default=200)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("no TPU: a time from this machine is not a device time",
              file=sys.stderr)
        return 2
    from ray_dynamic_batching_tpu.models import short_conv

    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / f"{a.config}.json").read_text())
    dc, llm = cfg["program"]["decoder_config"], cfg["deployment"]["llm"]
    D, K = dc["d_model"], dc["conv_kernel"]
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    rand = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape).astype(np.float32), bf)
    w = jnp.asarray(rng.normal(size=(K, D)).astype(np.float32)) / K ** 0.5
    w_in, w_out = rand(D, 3 * D) / D ** 0.5, rand(D, D) / D ** 0.5
    print(f"device: {jax.devices()[0].device_kind}; {a.config}: d {D}, "
          f"{K} taps, one layer", flush=True)

    def whole(form):
        def f(u, state, lens):
            gate_in, gate_out, x = jnp.split(u @ w_in, 3, -1)
            z = gate_in * x
            c, new = form(z, state, lens)
            return (gate_out * c.astype(z.dtype)) @ w_out, new
        return f

    def chained(step):
        """``--inner`` applications in one program: the state handed on,
        each result's mean folded into the carry."""
        def f(rows, state, lens):
            def body(carry, _):
                state, acc = carry
                out, state = step(rows, state, lens)
                return (state, acc + out.astype(jnp.float32).mean()), None
            return jax.lax.scan(body, (state, jnp.float32(0.0)), None,
                                length=a.inner)[0]
        return jax.jit(f)

    def kept(z, state, lens):
        if z.shape[1] == 1:
            return short_conv.decode_row(z, state, lens, w)
        return short_conv.chunk(z, state, lens, w)

    def shifted(z, state, lens):
        # the state as ``kept`` leaves it; the taps by explicit shifts
        return explicit_shifts(z, state, w), kept(z, state, lens)[1]

    shapes = [(g, max(llm["prompt_buckets"])) for g in (1, 2)] + [
        (llm["num_slots"], 1)]
    for B, T in shapes:
        z, u = rand(B, T, D), rand(B, T, D)
        state = rand(B, K - 1, D)
        lens = jnp.full((B,), T if T == 1 else T - 37, jnp.int32)
        what = "decode row" if T == 1 else "chunk"
        # rows in and out once, in the model's dtype
        least = 2 * B * T * D * 2 / HBM_BYTES_PER_S
        for name, form in (("joined (kept)", kept),
                           ("explicit shifts", shifted)):
            taps_s = _time(chained(form), (z, state, lens),
                           a.reps) / a.inner
            all_s = _time(chained(whole(form)), (u, state, lens),
                          a.reps) / a.inner
            print(f"{what} [{B}, {T}, {D}] {name}: taps + state "
                  f"{taps_s * 1e6:.1f} us (rows in and out at the peak "
                  f"{least * 1e6:.1f} us); with both products "
                  f"{all_s * 1e6:.1f} us (their weights at the peak "
                  f"{4 * D * D * 2 / HBM_BYTES_PER_S * 1e6:.1f} us)",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
