"""Record the small trace the reduction's test reads
(``benchmark/tests/data/small.xplane.pb``). Run on the chip, by hand:

    python3 -m benchmark.tests.record_small_trace chiprun_out/small_trace

Three runs of one jitted program (a 4-step scan of a matmul and a tanh,
named ``bench_probe``) inside the harness's window annotation, the Python
tracer off so that the file stays a few tens of KB.
"""

import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp

    from benchmark.trace_reduce import WINDOW_ANNOTATION

    def bench_probe(x):
        def step(c, _):
            return jnp.tanh(c @ c), None

        return jax.lax.scan(step, x, None, length=4)[0]

    f = jax.jit(bench_probe)
    x = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    f(x).block_until_ready()
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jp.start_trace(sys.argv[1], profiler_options=opts)
    with jp.TraceAnnotation(WINDOW_ANNOTATION):
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.01)
    jp.stop_trace()
    print(jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
