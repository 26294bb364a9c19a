"""rdb-lint suite tests: per-rule fixtures (positive hit, clean
negative, pragma suppression, baseline suppression), the PR-1 VMEM
undercount regression fixture, and the shared-footprint-math pins that
keep the static model and the runtime ``_pick_sb`` from drifting."""

import json
import textwrap

import pytest

from tools.lint import core as lint_core
from tools.lint import load_baseline, run
from tools.lint.__main__ import main as lint_main
from tools.lint.vmem import tile_math_module

from ray_dynamic_batching_tpu.ops import decode_attention as da
from ray_dynamic_batching_tpu.ops import tile_math as tm


def lint_fixture(tmp_path, relfile, source, baseline=None, rules=None):
    path = tmp_path / relfile
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return run(paths=[tmp_path], root=tmp_path, baseline=baseline,
               rules=rules)


def rules_found(report):
    return [f.rule for f in report.new]


# --- vmem-budget ----------------------------------------------------------

# The exact pattern PR 1 fixed in _pick_sb: a whole-S KV tile at H=64.
# Raw-H math budgets the K/V pair at ~8.4 MB double-buffered; the honest
# padded footprint (H -> 128 lanes) is ~2x that and busts the budget.
PR1_UNDERCOUNT = """
    from jax.experimental import pallas as pl

    S = 1024
    KB = 16
    H = 64

    def call(kernel, args):
        return pl.pallas_call(
            kernel,
            grid=(1, 1, 1),
            in_specs=[
                pl.BlockSpec((1, S, KB, H), lambda b, j, s: (b, 0, j, 0)),
                pl.BlockSpec((1, S, KB, H), lambda b, j, s: (b, 0, j, 0)),
                pl.BlockSpec((1, 1, S), lambda b, j, s: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, KB, 8, H), lambda b, j, s: (b, j, 0, 0)
            ),
        )(*args)
"""


class TestVmemBudget:
    def test_pr1_undercount_regression_is_flagged(self, tmp_path):
        # (rules scoped: tile-alignment ALSO fires on H=64 — the very
        # 2x lane pad that caused the undercount — tested separately.)
        report = lint_fixture(tmp_path, "ops/kernel.py", PR1_UNDERCOUNT,
                              rules={"vmem-budget"})
        assert rules_found(report) == ["vmem-budget"]
        f = report.new[0]
        assert "exceeds" in f.message
        assert "_pick_sb" in f.message  # names the bug class it guards

    def test_tiled_version_of_same_kernel_is_clean(self, tmp_path):
        report = lint_fixture(
            tmp_path, "ops/kernel.py",
            PR1_UNDERCOUNT.replace("S = 1024", "S = 1024\n    SB = 128")
            .replace("(1, S, KB, H)", "(1, SB, KB, H)"),
            rules={"vmem-budget"},
        )
        assert report.new == []

    def test_static_math_agrees_with_runtime_picker(self, tmp_path):
        # The flagged whole-S fixture is exactly a tile the runtime
        # picker refuses: the static checker and _pick_sb share one
        # model, so a geometry the checker rejects can never be picked.
        assert tm.decode_tile_bytes(1024, 16, 64, 2, True) \
            > tm.VMEM_BLOCK_BUDGET_BYTES
        assert da._pick_sb(1024, 16, 64, 2, True) < 1024

    def test_unresolvable_without_guard_is_flagged(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/dyn.py", """
            from jax.experimental import pallas as pl

            def call(kernel, args, sb, h):
                return pl.pallas_call(
                    kernel,
                    in_specs=[pl.BlockSpec((1, sb, 8, h),
                                           lambda b: (b, 0, 0, 0))],
                    out_specs=pl.BlockSpec((1, sb, 8, h),
                                           lambda b: (b, 0, 0, 0)),
                )(*args)
        """)
        assert rules_found(report) == ["vmem-budget"]
        assert "not statically resolvable" in report.new[0].message

    def test_unresolvable_with_tile_math_guard_is_trusted(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/dyn.py", """
            from jax.experimental import pallas as pl
            from ray_dynamic_batching_tpu.ops import tile_math

            def call(kernel, args, sb, h):
                assert tile_math.decode_tile_bytes(sb, 8, h, 4, False) \\
                    <= tile_math.VMEM_BLOCK_BUDGET_BYTES
                return pl.pallas_call(
                    kernel,
                    in_specs=[pl.BlockSpec((1, sb, 8, h),
                                           lambda b: (b, 0, 0, 0))],
                    out_specs=pl.BlockSpec((1, sb, 8, h),
                                           lambda b: (b, 0, 0, 0)),
                )(*args)
        """)
        assert report.new == []

    def test_param_shadows_module_constant(self, tmp_path):
        # A runtime parameter named like a module constant must NOT
        # resolve to the constant: that would stamp an unguarded dynamic
        # kernel as 'statically verified'.
        report = lint_fixture(tmp_path, "ops/shadow.py", """
            from jax.experimental import pallas as pl

            S = 128

            def call(kernel, args, S):
                return pl.pallas_call(
                    kernel,
                    in_specs=[pl.BlockSpec((1, S, 16, 64),
                                           lambda b: (b, 0, 0, 0))],
                    out_specs=pl.BlockSpec((1, S, 16, 64),
                                           lambda b: (b, 0, 0, 0)),
                )(*args)
        """, rules={"vmem-budget"})
        assert rules_found(report) == ["vmem-budget"]
        assert "not statically resolvable" in report.new[0].message

    def test_other_functions_locals_do_not_leak(self, tmp_path):
        # `S = 64` inside an unrelated function is not visible here;
        # the spec must count as unresolvable (and thus need a guard).
        report = lint_fixture(tmp_path, "ops/leak.py", """
            from jax.experimental import pallas as pl

            def other():
                S = 64
                return S

            def call(kernel, args):
                S = compute()
                return pl.pallas_call(
                    kernel,
                    in_specs=[pl.BlockSpec((1, S, 16, 64),
                                           lambda b: (b, 0, 0, 0))],
                    out_specs=pl.BlockSpec((1, S, 16, 64),
                                           lambda b: (b, 0, 0, 0)),
                )(*args)
        """, rules={"vmem-budget"})
        assert rules_found(report) == ["vmem-budget"]
        assert "not statically resolvable" in report.new[0].message

    def test_comment_mention_of_tile_math_does_not_suppress(
            self, tmp_path):
        # The escape hatch requires a real import; a comment or
        # docstring mention must not satisfy it.
        report = lint_fixture(tmp_path, "ops/dyn.py", """
            # TODO: someday use tile_math / VMEM_BLOCK_BUDGET_BYTES here
            from jax.experimental import pallas as pl

            def call(kernel, args, sb):
                return pl.pallas_call(
                    kernel,
                    in_specs=[pl.BlockSpec((1, sb, 8, 64),
                                           lambda b: (b, 0, 0, 0))],
                    out_specs=pl.BlockSpec((1, sb, 8, 64),
                                           lambda b: (b, 0, 0, 0)),
                )(*args)
        """, rules={"vmem-budget"})
        assert rules_found(report) == ["vmem-budget"]

    def test_rule_only_applies_to_ops(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/kernel.py", PR1_UNDERCOUNT)
        assert "vmem-budget" not in rules_found(report)


# --- tile-alignment -------------------------------------------------------

GRID_SPEC_OVER_BUDGET = """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S = 1024
    KB = 16
    H = 64

    def call(kernel, pt, lens, args):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1, 1, 1),
            in_specs=[
                pl.BlockSpec((1, S, KB, H), lambda b, j, s, pt, ln: (0, 0, 0, 0)),
                pl.BlockSpec((1, S, KB, H), lambda b, j, s, pt, ln: (0, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, KB, 8, H), lambda b, j, s, pt, ln: (0, 0, 0, 0)
            ),
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
        )(pt, lens, *args)
"""


class TestGridSpecCollection:
    def test_over_budget_inside_grid_spec_is_flagged(self, tmp_path):
        # ISSUE 7: moving the BlockSpecs into a PrefetchScalarGridSpec
        # (the page-table kernel's form) must not exempt a kernel from
        # the budget — the checker resolves page-indexed specs through
        # the grid_spec kwarg, inline or Name-bound.
        report = lint_fixture(tmp_path, "ops/paged.py",
                              GRID_SPEC_OVER_BUDGET,
                              rules=["vmem-budget"])
        assert rules_found(report) == ["vmem-budget"]

    def test_unresolvable_grid_spec_without_guard_is_flagged(
            self, tmp_path):
        report = lint_fixture(tmp_path, "ops/paged_dyn.py", """
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def call(kernel, ps, kb, h, args):
                return pl.pallas_call(
                    kernel,
                    grid_spec=pltpu.PrefetchScalarGridSpec(
                        num_scalar_prefetch=1,
                        grid=(1, 1, 1),
                        in_specs=[
                            pl.BlockSpec((1, ps, kb, h),
                                         lambda b, j, s, pt: (0, 0, 0, 0)),
                        ],
                        out_specs=pl.BlockSpec(
                            (1, kb, 8, h), lambda b, j, s, pt: (0, 0, 0, 0)
                        ),
                    ),
                )(*args)
        """, rules=["vmem-budget"])
        assert rules_found(report) == ["vmem-budget"]
        assert "tile_math" in report.new[0].message

    def test_guarded_grid_spec_is_trusted(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/paged_ok.py", """
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu
            from ray_dynamic_batching_tpu.ops import tile_math

            def call(kernel, ps, kb, h, args):
                assert tile_math.paged_tile_bytes(ps, kb, h, 4) \\
                    <= tile_math.VMEM_BLOCK_BUDGET_BYTES
                return pl.pallas_call(
                    kernel,
                    grid_spec=pltpu.PrefetchScalarGridSpec(
                        num_scalar_prefetch=1,
                        grid=(1, 1, 1),
                        in_specs=[
                            pl.BlockSpec((1, ps, kb, h),
                                         lambda b, j, s, pt: (0, 0, 0, 0)),
                        ],
                        out_specs=pl.BlockSpec(
                            (1, kb, 8, h), lambda b, j, s, pt: (0, 0, 0, 0)
                        ),
                    ),
                )(*args)
        """, rules=["vmem-budget"])
        assert rules_found(report) == []


SCRATCH_RING = """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    {imports}

    def call(kernel, {params}args):
        ring = [pltpu.VMEM(({depth}, {ps}, 8, 128), "float32")] * 2
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(1, 1),
                in_specs=[
                    pl.BlockSpec((1, 8, 128), lambda b, j, pt: (b, j, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec(
                    (1, 8, 128), lambda b, j, pt: (b, j, 0)),
                scratch_shapes=ring + [
                    pltpu.SemaphoreType.DMA((2, {depth})),
                    pltpu.SMEM((3,), "int32"),
                ],
            ),
        )(*args)
"""


class TestScratchRing:
    """ISSUE 33: a kernel that leaves its pool in HBM and copies pages
    into VMEM scratch itself (the paged decode kernel's ring) is held to
    the same budget: the scratch counts once, an HBM-resident operand
    nothing, a semaphore or SMEM scratch nothing."""

    @pytest.mark.parametrize("depth, ps, imports, params, want", [
        # 2 x 2 x 128 x 8 x 128 f32 = 2 MiB: fits
        (2, 128, "", "", []),
        # 2 x 4 x 1,024 x 8 x 128 f32 = 32 MiB of scratch: over
        (4, 1024, "", "", ["vmem-budget"]),
        # runtime-shaped ring, no guard in the module: flagged
        ("depth", "ps", "", "depth, ps, ", ["vmem-budget"]),
        # runtime-shaped ring beside the shared model: trusted
        ("depth", "ps",
         "from ray_dynamic_batching_tpu.ops import tile_math",
         "depth, ps, ", []),
    ], ids=["fits", "over", "unguarded", "guarded"])
    def test_ring_is_held_to_the_budget(
            self, tmp_path, depth, ps, imports, params, want):
        report = lint_fixture(
            tmp_path, "ops/ring.py", SCRATCH_RING.format(
                depth=depth, ps=ps, imports=imports, params=params),
            rules=["vmem-budget"])
        assert rules_found(report) == want
        if want and depth == 4:
            assert "32.0 MB" in report.new[0].message

    def test_the_paged_kernels_ring_is_what_the_model_prices(self):
        """The kernel's scratch and ``tile_math.paged_tile_bytes`` are
        one number at the benchmark's geometry: depth x (K + V tiles) +
        the flat form's two score tiles."""
        depth = tm.paged_walk_depth(128, 8, 128, 2)
        assert tm.DOUBLE_BUFFER < depth == tm.PAGED_WALK_MAX_DEPTH
        assert tm.paged_tile_bytes(128, 8, 128, 2, depth=depth) == (
            depth * 2 * tm.padded_block_bytes((1, 128, 8, 128), 2)
            + tm.flat_score_bytes(128, 8, 1))
        # ... and a page whose least ring alone fits takes no more
        assert tm.paged_tile_bytes(896, 12, 128, 2) \
            <= tm.VMEM_BLOCK_BUDGET_BYTES
        assert tm.paged_walk_depth(896, 12, 128, 2) == tm.DOUBLE_BUFFER


class TestTileAlignment:
    def test_lane_dim_one_flags_the_128x_blowup(self, tmp_path):
        # The documented (kb, 1) trailing-dims case from
        # decode_attention.py: tile-legal, but pads (8, 128) — ~128x.
        report = lint_fixture(tmp_path, "ops/scales.py", """
            from jax.experimental import pallas as pl
            KB = 8
            SPEC = pl.BlockSpec((1, 64, KB, 1), lambda b: (b, 0, 0, 0))
        """, rules={"tile-alignment"})
        assert rules_found(report) == ["tile-alignment"]
        assert "128x" in report.new[0].message

    def test_unaligned_sublane_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/spec.py", """
            from jax.experimental import pallas as pl
            SPEC = pl.BlockSpec((1, 5, 128), lambda b: (b, 0, 0))
        """, rules={"tile-alignment"})
        assert rules_found(report) == ["tile-alignment"]
        assert "sublane" in report.new[0].message

    def test_aligned_spec_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/spec.py", """
            from jax.experimental import pallas as pl
            SPEC = pl.BlockSpec((1, 16, 256), lambda b: (b, 0, 0))
        """, rules={"tile-alignment"})
        assert report.new == []

    def test_symbolic_dims_are_skipped(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/spec.py", """
            from jax.experimental import pallas as pl

            def make(sb, h):
                return pl.BlockSpec((1, sb, h), lambda b: (b, 0, 0))
        """, rules={"tile-alignment"})
        assert report.new == []


# --- event-loop-blocking --------------------------------------------------

class TestEventLoopBlocking:
    def test_sleep_in_async_def_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/app.py", """
            import time

            async def handler():
                time.sleep(0.1)
        """)
        assert rules_found(report) == ["event-loop-blocking"]
        assert "asyncio.sleep" in report.new[0].message

    def test_await_asyncio_sleep_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/app.py", """
            import asyncio

            async def handler():
                await asyncio.sleep(0.1)
        """)
        assert report.new == []

    def test_future_result_in_async_def_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/app.py", """
            async def handler(fut):
                return fut.result()
        """)
        assert rules_found(report) == ["event-loop-blocking"]
        assert "wrap_future" in report.new[0].message

    def test_future_result_on_worker_thread_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/app.py", """
            def servicer(fut):
                return fut.result(timeout=1.0)
        """)
        assert report.new == []

    def test_nested_sync_def_resets_async_scope(self, tmp_path):
        # A sync callback defined inside async def runs wherever it is
        # later invoked — not (necessarily) on the loop. Only the sleep
        # is reported, and as the tier-wide variant, not the hard one.
        report = lint_fixture(tmp_path, "serve/app.py", """
            import time

            async def handler():
                def cb():
                    time.sleep(0.1)
                return cb
        """)
        assert rules_found(report) == ["event-loop-blocking"]
        assert "worker-thread" in report.new[0].message

    def test_blocking_io_in_async_def_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/io.py", """
            import subprocess

            async def handler(path):
                with open(path) as f:
                    data = f.read()
                subprocess.run(["ls"])
                return data
        """)
        assert sorted(rules_found(report)) == [
            "event-loop-blocking", "event-loop-blocking"
        ]

    def test_tier_sleep_outside_async_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/loop.py", """
            import time

            def worker_loop():
                time.sleep(0.05)
        """)
        assert rules_found(report) == ["event-loop-blocking"]

    def test_rule_scoped_to_serving_tier(self, tmp_path):
        report = lint_fixture(tmp_path, "runtime/loop.py", """
            import time

            def worker_loop():
                time.sleep(0.05)
        """)
        assert report.new == []


# --- host-sync-in-hot-path ------------------------------------------------

class TestHostSync:
    def test_chunk_scheduler_functions_are_hot(self, tmp_path):
        """ISSUE 15: the token-budget prefill scheduler's dispatch path
        joined the configured hot set — a bare device fetch inside a
        chunk dispatch is a finding without a reasoned pragma."""
        from tools.lint.host_sync import HOT_FUNCTIONS

        assert {"_pump_prefill", "_complete_chunk_group",
                "_spend_prefill_budget", "_grant_train_pages"} <= \
            HOT_FUNCTIONS["engine/decode.py"]
        report = lint_fixture(tmp_path, "engine/decode.py", """
            import numpy as np

            def _complete_chunk_group(self, issued):
                return np.asarray(issued.first)
        """)
        assert rules_found(report) == ["host-sync-in-hot-path"]

    def test_hot_path_marker_plus_asarray_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import numpy as np

            def _step(self, packed):  # rdb-lint: hot-path
                return np.asarray(packed)
        """)
        assert rules_found(report) == ["host-sync-in-hot-path"]
        assert "ONE fetch" in report.new[0].message

    def test_host_literals_are_exempt(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import numpy as np

            def _step(self, xs):  # rdb-lint: hot-path
                a = np.asarray([1, 2, 3])
                b = np.asarray([x for x in xs])
                c = np.asarray(np.stack([a, b]))
                return a, b, c
        """)
        assert report.new == []

    def test_block_until_ready_in_hot_path_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            def _step(self, out):  # rdb-lint: hot-path
                out.block_until_ready()
        """)
        assert rules_found(report) == ["host-sync-in-hot-path"]

    def test_unmarked_function_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import numpy as np

            def warmup(self, out):
                return np.asarray(out)
        """)
        assert report.new == []

    def test_if_on_traced_param_in_jitted_fn_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/k.py", """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                if x:
                    return x
                return x + n
        """)
        assert rules_found(report) == ["host-sync-in-hot-path"]
        assert "traced parameter 'x'" in report.new[0].message

    def test_static_and_is_none_branches_are_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/k.py", """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, mask, n):
                if n:
                    return x
                if mask is None:
                    return x
                if x.ndim != 2:
                    return x
                return x + n
        """)
        assert report.new == []

    def test_int_coercion_of_traced_param_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/k.py", """
            import jax

            @jax.jit
            def f(x):
                return int(x)
        """)
        assert rules_found(report) == ["host-sync-in-hot-path"]


# --- span-hygiene ---------------------------------------------------------

class TestSpanHygiene:
    def test_unentered_span_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/t.py", """
            from ray_dynamic_batching_tpu.utils.tracing import tracer

            def handler():
                tracer().span("orphan")
        """)
        assert rules_found(report) == ["span-hygiene"]
        assert "never runs" in report.new[0].message

    def test_with_and_enter_context_are_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/t.py", """
            from contextlib import ExitStack
            from ray_dynamic_batching_tpu.utils.tracing import tracer

            def handler():
                with tracer().span("hop") as sp:
                    with ExitStack() as spans:
                        spans.enter_context(
                            tracer().attach_context({}, "inner")
                        )
                return sp
        """)
        assert report.new == []

    def test_exporter_call_outside_try_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "utils/tr.py", """
            def _finish(self, s):
                self._exporter(s)
        """)
        assert rules_found(report) == ["span-hygiene"]
        assert "exporter" in report.new[0].message

    def test_exporter_call_inside_try_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "utils/tr.py", """
            def _finish(self, s):
                try:
                    self._exporter(s)
                except Exception:
                    pass
        """)
        assert report.new == []


# --- sim-determinism ------------------------------------------------------

class TestSimDeterminism:
    def test_wall_clock_in_sim_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/engine.py", """
            import time

            def step():
                return time.time()
        """)
        assert rules_found(report) == ["sim-determinism"]
        assert "virtual clock" in report.new[0].message

    def test_sleep_and_monotonic_flag(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/loop.py", """
            import time

            def pace():
                time.sleep(0.1)
                return time.monotonic()
        """)
        assert rules_found(report) == ["sim-determinism"] * 2

    def test_global_random_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/workload.py", """
            import random

            def jitter():
                return random.random() + random.uniform(0, 1)
        """)
        assert rules_found(report) == ["sim-determinism"] * 2
        assert "process-global RNG" in report.new[0].message

    def test_unseeded_random_instance_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/rng.py", """
            import random

            def make_rng():
                return random.Random()
        """)
        assert rules_found(report) == ["sim-determinism"]
        assert "seed" in report.new[0].message

    def test_seeded_random_instance_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/rng.py", """
            import random

            def make_rng(seed):
                return random.Random(seed * 7919 + 13)
        """)
        assert report.new == []

    def test_numpy_global_rng_flags_seeded_generator_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/noise.py", """
            import numpy as np

            def noisy():
                return np.random.normal()

            def clean(seed):
                return np.random.default_rng(seed).normal()
        """)
        assert rules_found(report) == ["sim-determinism"]

    def test_datetime_now_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/report.py", """
            import datetime

            def stamp():
                return datetime.datetime.now()
        """)
        assert rules_found(report) == ["sim-determinism"]

    def test_rule_scoped_to_sim_only(self, tmp_path):
        # The same wall-clock call outside sim/ is not this rule's
        # business (the serving tier has its own rules).
        report = lint_fixture(tmp_path, "scheduler/control.py", """
            import time

            def now():
                return time.time()
        """)
        assert report.new == []

    def test_reasoned_pragma_suppresses(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/bridge.py", """
            import time

            def wall_anchor():
                return time.time()  # rdb-lint: disable=sim-determinism (report stamping happens outside the event loop)
        """)
        assert report.new == []
        assert report.pragma_suppressed == 1

    def test_shipped_sim_tree_is_clean(self):
        report = run(
            paths=[lint_core.REPO_ROOT / "ray_dynamic_batching_tpu" / "sim"],
            rules={"sim-determinism"},
        )
        assert report.files_scanned >= 8
        assert report.new == [], report.format_text()


# --- unbounded-retry ------------------------------------------------------

# The bug class: while-True backoff with no deadline/attempt exit.
UNBOUNDED_RETRY = """
    import time

    def fetch(replica, req):
        backoff = 0.002
        while True:
            if replica.assign(req):
                return True
            time.sleep(backoff)
            backoff = min(backoff * 2, 0.1)
"""

# The compliant exemplar shape (Router.assign_request): a Compare-guarded
# return bounds the loop by a deadline.
BOUNDED_RETRY = """
    import time

    def fetch(replica, req, timeout_s):
        deadline = time.monotonic() + timeout_s
        backoff = 0.002
        while True:
            if replica.assign(req):
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(backoff)
            backoff = min(backoff * 2, 0.1)
"""


class TestUnboundedRetry:
    def test_unbounded_backoff_loop_is_flagged(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/r.py", UNBOUNDED_RETRY,
                              rules={"unbounded-retry"})
        assert rules_found(report) == ["unbounded-retry"]
        assert "deadline or attempt-budget" in report.new[0].message

    def test_deadline_guarded_loop_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/r.py", BOUNDED_RETRY,
                              rules={"unbounded-retry"})
        assert report.new == []

    def test_attempt_budget_break_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/r.py", """
            import time

            def fetch(replica, req, max_attempts):
                attempts = 0
                while True:
                    attempts += 1
                    if replica.assign(req):
                        return True
                    if attempts >= max_attempts:
                        break
                    time.sleep(0.01)
                return False
        """, rules={"unbounded-retry"})
        assert report.new == []

    def test_condition_bounded_loop_not_a_retry_loop(self, tmp_path):
        # An event-pacing loop (`while not stop:`) is bounded by its
        # condition — out of scope even though it sleeps.
        report = lint_fixture(tmp_path, "engine/pacer.py", """
            import time

            def pace(stop):
                while not stop.is_set():
                    time.sleep(0.05)
        """, rules={"unbounded-retry"})
        assert report.new == []

    def test_sleepless_while_true_is_not_flagged(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/poll.py", """
            def drain(q):
                while True:
                    item = q.pop()
                    if item is None:
                        return
        """, rules={"unbounded-retry"})
        assert report.new == []

    def test_outside_serving_tier_is_out_of_scope(self, tmp_path):
        report = lint_fixture(tmp_path, "models/loader.py",
                              UNBOUNDED_RETRY, rules={"unbounded-retry"})
        assert report.new == []

    def test_reasoned_pragma_suppresses(self, tmp_path):
        report = lint_fixture(
            tmp_path, "serve/r.py",
            UNBOUNDED_RETRY.replace(
                "while True:",
                "while True:  # rdb-lint: disable=unbounded-retry "
                "(caller enforces the deadline)",
            ),
            rules={"unbounded-retry"},
        )
        assert report.new == []
        assert report.pragma_suppressed == 1

    def test_router_exemplar_is_compliant(self):
        report = run(
            paths=[lint_core.REPO_ROOT / "ray_dynamic_batching_tpu"
                   / "serve" / "router.py"],
            rules={"unbounded-retry"},
        )
        assert report.new == [], report.format_text()


# --- retry-amplification --------------------------------------------------

# The bug class (ISSUE 19): a re-dispatch site with no budget in sight —
# under a fault storm every shed retries unbudgeted and the retry volume
# IS the overload (the metastable loop).
UNBUDGETED_REDISPATCH = """
    def on_replica_dead(router, requests, victim_id):
        router.failover.requeue(requests, victim_id, dead=True)
"""

# The compliant shape (FailoverManager.submit): admission and
# amplification priced in one function.
BUDGETED_REDISPATCH = """
    def on_replica_dead(router, requests, victim_id):
        budget = getattr(router, "retry_budget", None)
        for req in requests:
            if budget is not None and not budget.try_spend("retry"):
                req.reject(RuntimeError("budget"))
                continue
            router.failover.requeue([req], victim_id, dead=True)
"""


class TestRetryAmplification:
    def test_unbudgeted_redispatch_is_flagged(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/heal.py",
                              UNBUDGETED_REDISPATCH,
                              rules={"retry-amplification"})
        assert rules_found(report) == ["retry-amplification"]
        assert "budget consult" in report.new[0].message
        assert report.new[0].symbol == "on_replica_dead"

    def test_budget_consult_in_same_function_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/heal.py",
                              BUDGETED_REDISPATCH,
                              rules={"retry-amplification"})
        assert report.new == []

    def test_retry_budget_attribute_read_counts_as_consult(self, tmp_path):
        # The `router.retry_budget` attribute form (no getattr string).
        report = lint_fixture(tmp_path, "serve/heal.py", """
            def rescue(router, req, exc):
                if router.retry_budget.congested:
                    req.reject(exc)
                    return
                router.failover.submit(req, exc)
        """, rules={"retry-amplification"})
        assert report.new == []

    def test_failover_submit_is_a_redispatch_verb(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/heal.py", """
            def rescue(router, req, exc):
                router.failover.submit(req, exc)
        """, rules={"retry-amplification"})
        assert rules_found(report) == ["retry-amplification"]

    def test_plain_executor_submit_is_not_a_redispatch(self, tmp_path):
        # `submit` only counts on a failover object (or inside a
        # Failover/Hedge manager) — a thread-pool submit amplifies
        # nothing.
        report = lint_fixture(tmp_path, "serve/pool.py", """
            def schedule(executor, fn):
                return executor.submit(fn)
        """, rules={"retry-amplification"})
        assert report.new == []

    def test_submit_inside_hedge_manager_is_a_redispatch(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/hedge.py", """
            class HedgeManager:
                def fire(self, req):
                    self.submit(req)
        """, rules={"retry-amplification"})
        assert rules_found(report) == ["retry-amplification"]
        assert report.new[0].symbol == "HedgeManager.fire"

    def test_lambda_deferred_redispatch_is_still_flagged(self, tmp_path):
        # Deferring via lambda is still authored in this function — the
        # budget decision belongs where the re-dispatch is scheduled.
        report = lint_fixture(tmp_path, "serve/defer.py", """
            def on_failure(loop, router, req, exc):
                loop.call_later(0.05, lambda: router.failover.submit(req, exc))
        """, rules={"retry-amplification"})
        assert rules_found(report) == ["retry-amplification"]

    def test_outside_serve_is_out_of_scope(self, tmp_path):
        report = lint_fixture(tmp_path, "sim/heal.py",
                              UNBUDGETED_REDISPATCH,
                              rules={"retry-amplification"})
        assert report.new == []

    def test_reasoned_pragma_suppresses(self, tmp_path):
        report = lint_fixture(
            tmp_path, "serve/heal.py",
            UNBUDGETED_REDISPATCH.replace(
                "dead=True)",
                "dead=True)  # rdb-lint: disable=retry-amplification "
                "(drain salvage moves admitted work)",
            ),
            rules={"retry-amplification"},
        )
        assert report.new == []
        assert report.pragma_suppressed == 1

    def test_shipped_serve_tree_is_clean(self):
        # Satellite pin: every re-dispatch site in the shipped serve/
        # tree either consults a budget or carries a reasoned pragma.
        report = run(
            paths=[lint_core.REPO_ROOT / "ray_dynamic_batching_tpu"
                   / "serve"],
            rules={"retry-amplification"},
        )
        assert report.new == [], report.format_text()


# --- pragmas --------------------------------------------------------------

SLEEPY = """
    import time

    def worker_loop():
        time.sleep(0.05){pragma}
"""


class TestPragmas:
    def test_reasoned_pragma_suppresses(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/w.py",
            SLEEPY.format(pragma="  # rdb-lint: disable="
                          "event-loop-blocking (pacing thread)"),
        )
        assert report.new == []
        assert report.pragma_suppressed == 1

    def test_reasonless_pragma_suppresses_nothing_and_is_reported(
            self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/w.py",
            SLEEPY.format(pragma="  # rdb-lint: disable="
                          "event-loop-blocking"),
        )
        assert sorted(rules_found(report)) == [
            "event-loop-blocking", "pragma-hygiene"
        ]

    def test_unknown_rule_in_pragma_is_reported(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/w.py",
            SLEEPY.format(pragma="  # rdb-lint: disable=no-such-rule "
                          "(because)"),
        )
        assert "pragma-hygiene" in rules_found(report)

    def test_unused_pragma_is_reported(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/w.py", """
            def quiet():  # rdb-lint: disable=event-loop-blocking (stale)
                return 1
        """)
        assert rules_found(report) == ["pragma-hygiene"]
        assert "unused" in report.new[0].message


# --- baseline ratchet -----------------------------------------------------

def _baseline(entries):
    return {"version": 1, "entries": entries}


class TestBaseline:
    def test_baselined_finding_does_not_fail(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/w.py", SLEEPY.format(pragma=""),
            baseline=_baseline([{
                "rule": "event-loop-blocking", "path": "engine/w.py",
                "symbol": "worker_loop", "count": 1,
                "reason": "legacy pacing loop; tracked for conversion",
            }]),
        )
        assert report.new == [] and not report.failed
        assert len(report.baselined) == 1

    def test_growth_past_baseline_fails(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/w.py", """
            import time

            def worker_loop():
                time.sleep(0.05)
                time.sleep(0.06)
            """,
            baseline=_baseline([{
                "rule": "event-loop-blocking", "path": "engine/w.py",
                "symbol": "worker_loop", "count": 1, "reason": "legacy",
            }]),
        )
        assert len(report.new) == 1 and report.failed

    def test_stale_baseline_fails_the_ratchet(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/w.py", SLEEPY.format(pragma=""),
            baseline=_baseline([{
                "rule": "event-loop-blocking", "path": "engine/w.py",
                "symbol": "worker_loop", "count": 2, "reason": "legacy",
            }]),
        )
        assert report.failed
        assert any("may only shrink" in e for e in report.errors)

    def test_scoped_rules_run_does_not_trip_staleness(self, tmp_path):
        # A --rules-scoped run never executed the entry's rule: "not
        # scanned" must not be misread as "fixed" (the ratchet only
        # judges entries the run could have re-found).
        report = lint_fixture(
            tmp_path, "engine/w.py", SLEEPY.format(pragma=""),
            baseline=_baseline([{
                "rule": "event-loop-blocking", "path": "engine/w.py",
                "symbol": "worker_loop", "count": 1, "reason": "legacy",
            }]),
            rules={"vmem-budget"},
        )
        assert not report.failed, report.format_text()

    def test_path_scoped_run_does_not_trip_staleness(self, tmp_path):
        (tmp_path / "ops").mkdir()
        (tmp_path / "ops" / "clean.py").write_text("X = 1\n")
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine" / "w.py").write_text(
            textwrap.dedent(SLEEPY.format(pragma=""))
        )
        report = run(
            paths=[tmp_path / "ops"], root=tmp_path,
            baseline=_baseline([{
                "rule": "event-loop-blocking", "path": "engine/w.py",
                "symbol": "worker_loop", "count": 1, "reason": "legacy",
            }]),
        )
        assert not report.failed, report.format_text()

    def test_unknown_rule_in_baseline_fails(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/w.py", SLEEPY.format(pragma=""),
            baseline=_baseline([{
                "rule": "no-such-rule", "path": "engine/w.py",
                "symbol": "worker_loop", "count": 1, "reason": "typo",
            }]),
        )
        assert any("unknown rule" in e for e in report.errors)

    def test_reasonless_baseline_entry_fails(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/w.py", SLEEPY.format(pragma=""),
            baseline=_baseline([{
                "rule": "event-loop-blocking", "path": "engine/w.py",
                "symbol": "worker_loop", "count": 1, "reason": "",
            }]),
        )
        assert report.failed
        assert any("no reason" in e for e in report.errors)


# --- shared footprint math (the no-drift pins) ----------------------------

class TestSharedTileMath:
    def test_decode_tile_bytes_matches_legacy_inline_formula(self):
        # The formula _pick_sb used to carry inline, replayed against
        # the shared helper on the H=64 geometry PR 1 fixed (bf16,
        # S=1024, kb=16) and a spread of others.
        for sb in (128, 256, 448, 1024):
            for kb in (4, 8, 16):
                for H in (64, 128):
                    for itemsize in (1, 2, 4):
                        for with_mask in (False, True):
                            for with_scales in (False, True):
                                sublane = {4: 8, 2: 16, 1: 32}[itemsize]
                                lane_h = -(-H // 128) * 128
                                kv = (2 * sb * -(-kb // sublane) * sublane
                                      * lane_h * itemsize)
                                lane_sb = -(-sb // 128) * 128
                                mask_b = 32 * lane_sb if with_mask else 0
                                scale_b = (2 * -(-kb // 8) * 8 * lane_sb
                                           * 4 if with_scales else 0)
                                legacy = 2 * (kv + mask_b + scale_b)
                                assert tm.decode_tile_bytes(
                                    sb, kb, H, itemsize, with_mask,
                                    with_scales=with_scales,
                                ) == legacy

    def test_runtime_picker_and_static_model_agree_on_h64(self):
        # PR 1's geometry: the picked tile must satisfy the shared
        # model and the whole-S tile must violate it — from BOTH sides.
        S, kb, H, itemsize = 1024, 16, 64, 2
        sb = da._pick_sb(S, kb, H, itemsize, True)
        assert 0 < sb < S
        assert tm.decode_tile_bytes(sb, kb, H, itemsize, True) \
            <= tm.VMEM_BLOCK_BUDGET_BYTES
        assert tm.decode_tile_bytes(S, kb, H, itemsize, True) \
            > tm.VMEM_BLOCK_BUDGET_BYTES
        assert da.VMEM_BLOCK_BUDGET_BYTES == tm.VMEM_BLOCK_BUDGET_BYTES

    def test_no_duplicated_math_in_decode_attention(self):
        src = open(da.__file__).read()
        assert "decode_tile_bytes" in src
        # the sublane-pack table lives ONLY in tile_math now
        assert "{4: 8, 2: 16, 1: 32}" not in src

    def test_linter_loads_the_same_model(self):
        lm = tile_math_module()
        assert lm.VMEM_BLOCK_BUDGET_BYTES == tm.VMEM_BLOCK_BUDGET_BYTES
        assert lm.decode_tile_bytes(1024, 16, 64, 2, True) == \
            tm.decode_tile_bytes(1024, 16, 64, 2, True)

    def test_paged_model_agreement_pin(self):
        # ISSUE 7: the page-table kernel budgets pages with
        # paged_tile_bytes; the standalone-loaded lint copy must be the
        # SAME model (runtime picker <-> linter agreement, the PR-2
        # discipline applied to the paged path).
        lm = tile_math_module()
        for ps in (128, 256):
            for kb in (4, 8, 16):
                for H in (64, 128):
                    for itemsize in (1, 2, 4):
                        for ws in (False, True):
                            assert lm.paged_tile_bytes(
                                ps, kb, H, itemsize, with_scales=ws
                            ) == tm.paged_tile_bytes(
                                ps, kb, H, itemsize, with_scales=ws
                            )
        # A page is one KV tile without the mask: the two models must
        # coincide where they describe the same bytes.
        # (rows=1: both in the form their body takes, flat heads here,
        # whose two f32 score tiles both count since PR 31.)
        assert tm.paged_tile_bytes(128, 8, 64, 2, with_scales=True) == \
            tm.decode_tile_bytes(128, 8, 64, 2, False, with_scales=True,
                                 rows=1)
        assert tm.paged_tile_bytes(128, 4, 64, 2, with_scales=True) == \
            tm.decode_tile_bytes(128, 4, 64, 2, False, with_scales=True)
        assert lm.lane_aligned_page(128) and not lm.lane_aligned_page(100)

    def test_paged_runtime_guard_declines_fat_pages(self):
        # The runtime eligibility check is the same budget the linter
        # re-evaluates: a geometry whose single-page footprint busts
        # VMEM must make the kernel DECLINE (gather fallback), not lower.
        import jax.numpy as jnp
        import numpy as np

        H = 4096  # (1, 128, 8, 4096) f32 double-buffered >> 15 MB
        assert tm.paged_tile_bytes(128, 8, H, 4) \
            > tm.VMEM_BLOCK_BUDGET_BYTES
        q = jnp.zeros((1, 1, 8, H), jnp.float32)
        k = jnp.zeros((4, 128, 8, H), jnp.float32)
        pt = jnp.zeros((1, 2), jnp.int32)
        lens = jnp.asarray(np.asarray([5]), jnp.int32)
        assert da.paged_decode_attention(
            q, k, k, pt, lens, interpret=True
        ) is None

    @pytest.mark.parametrize("kb", [4, 2])
    def test_the_viewed_page_is_priced_as_it_lies(self, kb, tmp_path):
        """ISSUE 39: a head block narrower than 8 arrives in the sparse
        kernel's ring through the pool's tile view, [ps // f, kb * f, H]
        with f = 8 // kb. The runtime guard (``tile_math.
        sparse_tile_bytes``), the linter's standalone copy and the
        ``vmem-budget`` rule's own price of that scratch shape are ONE
        number; the page as it lay pads ``kb`` rows up to a tile."""
        from tools.lint.vmem import ASSUMED_ITEMSIZE

        lm = tile_math_module()
        ps, H, G, NP = 128, 128, 32 // kb, 144
        f = tm.page_view_fold(kb, ps)
        assert f == lm.page_view_fold(kb, ps) == 8 // kb
        for depth in (2, 3):
            for fold in (1, f):
                assert lm.sparse_tile_bytes(
                    ps, kb, H, 2, G, NP, fold, True, depth
                ) == tm.sparse_tile_bytes(
                    ps, kb, H, 2, G, NP, fold, True, depth)
        ring = lambda fold, depth: (  # noqa: E731 (ring alone: less depth 0)
            tm.sparse_tile_bytes(ps, kb, H, 2, G, NP, fold, True, depth)
            - tm.sparse_tile_bytes(ps, kb, H, 2, G, NP, fold, True, 0))
        # what the rule charges a pltpu.VMEM((depth, ps // f, 8, H)) pair
        # (f32-itemsize upper bound: 8 rows are a whole tile there)
        viewed = (3, ps // f, kb * f, H)
        assert ring(f, 3) == 2 * lm.padded_block_bytes(
            viewed, ASSUMED_ITEMSIZE) == 2 * 3 * ps * kb * H * 4
        # ... and the same ring read as it lay: kb rows padded to 16
        assert ring(1, 3) == f * ring(f, 3) == 2 * 3 * ps * 16 * H * 2
        assert tm.sparse_walk_depth(ps, kb, H, 2, G, NP, f) \
            == tm.PAGED_WALK_MAX_DEPTH
        # no view: 8 heads, 3 heads, a page f does not divide
        assert [tm.page_view_fold(k, p) for k, p in (
            (8, 128), (16, 128), (3, 128), (kb, 8 // kb + 1), (0, 128))
        ] == [1] * 5
        # the rule reads that very scratch ring and holds it to the budget
        report = lint_fixture(
            tmp_path, "ops/ring.py", SCRATCH_RING.format(
                depth=3, ps=ps // f, imports="", params=""),
            rules=["vmem-budget"])
        assert rules_found(report) == []

    @pytest.mark.parametrize("kb", [4, 2])
    @pytest.mark.parametrize("pages", [1, 2, 4])
    def test_a_fold_of_several_pages_is_priced_as_it_lies(self, kb, pages):
        """ISSUE 51: the sparse kernel's ring holds ``pages`` pages a
        slot, its selection block a row a fold, its score tiles a fold's
        columns: the runtime's price and the linter's standalone copy are
        one number over the same grid, and the ring's part is ``pages``
        times a page's."""
        lm = tile_math_module()
        ps, H, G, NP = 128, 128, 32 // kb, 144
        f = tm.page_view_fold(kb, ps)
        for depth in (2, 3):
            for fold in (1, f):
                assert lm.sparse_tile_bytes(
                    ps, kb, H, 2, G, NP, fold, True, depth, pages
                ) == tm.sparse_tile_bytes(
                    ps, kb, H, 2, G, NP, fold, True, depth, pages)
        ring = lambda n: (  # noqa: E731 (ring alone: less depth 0)
            tm.sparse_tile_bytes(ps, kb, H, 2, G, NP, f, True, 3, n)
            - tm.sparse_tile_bytes(ps, kb, H, 2, G, NP, f, True, 0, n))
        assert ring(pages) == pages * ring(1)
        assert lm.sparse_fold_pages(ps, kb, H, 2, G, NP, f) \
            == tm.sparse_fold_pages(ps, kb, H, 2, G, NP, f) == 4
        assert lm.sparse_walk_depth(ps, kb, H, 2, G, NP, f, True, pages) \
            == tm.sparse_walk_depth(ps, kb, H, 2, G, NP, f, True, pages) == 3

    def test_the_cells_shapes_fold_four_pages_in_a_ring_of_three(self):
        """Keye's shapes (a page of 128, 4 key heads of 128, 8 query rows
        a head, a table of 144): (4, 3), ~7 MB of the 15 MB budget. The
        picker falls by halves: a table 4 does not divide, a ring that no
        longer fits a depth past the double buffer, a fold that is not
        the narrow block's own."""
        ps, kb, H, G, NP, f = 128, 4, 128, 8, 144, 2
        pages = tm.sparse_fold_pages(ps, kb, H, 2, G, NP, f)
        depth = tm.sparse_walk_depth(ps, kb, H, 2, G, NP, f, True, pages)
        assert (pages, depth) == (tm.SPARSE_FOLD_MAX_PAGES, 3) == (4, 3)
        took = tm.sparse_tile_bytes(ps, kb, H, 2, G, NP, f, True, depth,
                                    pages)
        # 3 x 2 x 4 pages of 256 KB + the selection 2 x 40 x 2048 x 4
        # + two [32, 2048] f32 score tiles
        assert took == (3 * 2 * 4 * 256 + 640 + 512) * 1024 \
            < tm.VMEM_BLOCK_BUDGET_BYTES // 2
        assert [tm.sparse_fold_pages(ps, kb, H, 2, G, n, f)
                for n in (146, 145, 8, 4, 2, 1)] == [2, 1, 4, 4, 2, 1]
        assert tm.sparse_fold_pages(ps, kb, H, 2, G, NP, f, own=False) == 1
        # a head so wide that four pages' ring of three busts the budget
        wide = 512
        assert tm.sparse_tile_bytes(ps, kb, wide, 2, G, NP, f, True, 3, 4) \
            > tm.VMEM_BLOCK_BUDGET_BYTES
        assert tm.sparse_fold_pages(ps, kb, wide, 2, G, NP, f) == 2
        assert tm.sparse_tile_bytes(ps, kb, wide, 2, G, NP, f, True, 3, 2) \
            <= tm.VMEM_BLOCK_BUDGET_BYTES

    @pytest.mark.parametrize("kb, H, rows", [
        (4, 128, 8),      # LFM2: 4 packed rows a position, 8 query rows each
        (4, 256, 16),     # MiMo's full layers: k rows of two lane tiles
        (8, 128, 2),      # gpt2-medium packed: a block of 8
    ])
    @pytest.mark.parametrize("pages", [1, 2, 4])
    def test_a_dense_fold_of_several_pages_is_priced_as_it_lies(
            self, kb, H, rows, pages):
        """ISSUE 52: the dense paged kernel's ring holds ``pages`` pages a
        slot and its fold's score tiles are ``pages`` pages of columns
        wide: the runtime's price (``paged_tile_bytes`` with ``pages``)
        and the linter's standalone copy are one number, the ring's part
        ``pages`` times a page's, and a page a fold what it always was."""
        lm = tile_math_module()
        for depth in (2, 3):
            for itemsize in (1, 2, 4):
                assert lm.paged_tile_bytes(
                    128, kb, H, itemsize, False, 1, rows, depth, pages
                ) == tm.paged_tile_bytes(
                    128, kb, H, itemsize, False, 1, rows, depth, pages)
        price = lambda depth, n: tm.paged_tile_bytes(  # noqa: E731
            128, kb, H, 2, False, 1, rows, depth, n)
        ring = lambda n: price(3, n) - price(0, n)  # noqa: E731
        assert ring(pages) == pages * ring(1) == (
            pages * 3 * 2 * tm.padded_block_bytes((1, 128, kb, H), 2))
        # ... and what rides beside the ring: the fold's two score tiles
        assert price(0, pages) - price(0, 1) == (
            2 * (kb * rows) * pages * 128 * kb * 4 if pages > 1 else 0)
        assert price(3, 1) == tm.paged_tile_bytes(
            128, kb, H, 2, window=1, G=rows, depth=3)
        assert lm.paged_walk_depth(128, kb, H, 2, False, 1, rows, pages) \
            == tm.paged_walk_depth(128, kb, H, 2, False, 1, rows, pages)
        for narrow in (False, True):
            assert lm.paged_fold_pages(128, kb, H, 2, 1, rows, narrow) \
                == tm.paged_fold_pages(128, kb, H, 2, 1, rows, narrow)

    # name -> the (pages a fold, ring depth) its paged layers' decode
    # walk takes and the bytes ``paged_tile_bytes`` prices that at; the
    # six 8-row geometries a page a fold, PINNED.
    PAGED_WALKS = {
        "gpt2-medium": ((1, 3), 3 * 2 ** 20 + 2 * 16 * 1024 * 4),
        "gpt2-medium-x4": ((1, 3), 3 * 2 ** 20 + 2 * 16 * 1024 * 4),
        "mistral-7b-v0.3-1chip": ((1, 3), 3 * 2 ** 20 + 2 * 32 * 1024 * 4),
        "olmoe-1b-7b-1chip": ((1, 3), 3 * 2 ** 20 + 2 * 8 * 1024 * 4),
        "k-exaone-236b-ep8-1chip": ((1, 3), 3 * 2 ** 20 + 2 * 64 * 1024 * 4),
        # a ring of 3 groups of 2 pages, k + v a page 2 x 512 KB as priced
        # (4 rows pad to a 16-row tile), two [32, 1024] f32 score tiles
        "lfm2-24b-a2b-ep8-1chip": ((2, 3), 6 * 2 ** 20 + 2 * 32 * 1024 * 4),
        # 3 groups of 2 pages of 2 x 1 MB (both priced at k's 256 lanes),
        # two [64, 1024] f32 score tiles
        "mimo-v2-flash-ep16-1chip": ((2, 3), 12 * 2 ** 20 + 2 * 64 * 1024 * 4),
        "mimo-v2-flash-ep16-1chip/window": (
            (1, 3), 6 * 2 ** 20 + 2 * 64 * 1024 * 4),
        # 4 KV heads of 128 under FIVE query heads each, given as 4 x 6 rows
        # (da._group: 24 are whole sublane tiles, 20 are not): LFM2's ring
        # of 3 groups of 2 pages, two [24, 1024] f32 score tiles
        "falcon-h1-34b-1chip": ((2, 3), 6 * 2 ** 20 + 2 * 24 * 1024 * 4),
    }

    @staticmethod
    def _paged_walk(dec, kv_heads, itemsize=2):
        """The walk of one paged layer kind of a configuration's
        ``decoder_config``, as the wrapper works it out: the pool's rows a
        position, their width, the query rows a row."""
        import jax.numpy as jnp

        from ray_dynamic_batching_tpu.models.kv_state import (
            pool_head_dim,
            pool_heads_per_row,
        )

        N = dec["num_heads"]
        H = dec.get("head_dim") or dec["d_model"] // N
        by_kind = "v_head_dim" in dec
        f = 1 if by_kind else pool_heads_per_row(
            H, kv_heads, jnp.int8 if itemsize == 1 else jnp.bfloat16)
        rows_a_position, G = kv_heads // f, f * N // kv_heads
        kb = da._pick_heads_block(rows_a_position)
        # (a group that is no whole sublane tiles is given rows of zeros)
        G = da._group(G, kb, rows_a_position, 1, None, None, 0, 1)
        fold = da._narrow_fold(rows_a_position, kb, G, 128, itemsize == 1)
        walk = da._walk(fold, 128, kb, pool_head_dim(H * f), itemsize,
                        itemsize == 1, 1, G)
        return walk, tm.paged_tile_bytes(
            128, kb, pool_head_dim(H * f), itemsize, itemsize == 1, 1, G,
            walk[1], walk[0])

    def test_every_configurations_walk_is_pinned(self):
        """The picker's choice and its bytes for every geometry of
        ``benchmark/configs/*.json`` whose decode read is the paged
        kernel's (Keye's selecting layers are the sparse kernel's, Xing's
        latent rows the latent kernel's): 2 for LFM2's packed rows and
        for MiMo's full layers, 1 for the six 8-row geometries."""
        import pathlib

        root = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
        seen = {}
        for path in sorted((root / "configs").glob("*.json")):
            dec = json.loads(path.read_text())["program"]["decoder_config"]
            if "index_topk" in dec or "kv_lora_rank" in dec:
                continue
            seen[path.stem] = self._paged_walk(dec, dec["num_kv_heads"])
            if "sliding_kv_heads" in dec:
                seen[path.stem + "/window"] = self._paged_walk(
                    dec, dec["sliding_kv_heads"])
        assert seen == self.PAGED_WALKS
        assert all(took <= tm.VMEM_BLOCK_BUDGET_BYTES
                   for _, took in seen.values())

    def test_an_int8_pool_keeps_a_page_a_fold(self):
        """gpt2-medium's int8 pool (a head a row, 16 of them) and a narrow
        int8 block (4 heads: the per-head fold, which reads scales)."""
        gpt2 = dict(num_heads=16, d_model=1024)
        assert self._paged_walk(gpt2, 16, itemsize=1)[0] == (1, 3)
        assert self._paged_walk(dict(num_heads=32, head_dim=128, d_model=0),
                                4, itemsize=1)[0] == (1, 3)
        assert tm.paged_fold_pages(128, 4, 128, 1, narrow=False) == 1

    def test_the_dense_picker_falls_by_halves(self):
        """2 (``PAGED_FOLD_MAX_PAGES``: what the chip's A/B says, four is
        never faster) where a ring of three groups fits the budget, 1
        where the head is so wide that two pages' ring busts it; never
        more than 1 for a fold that is not the narrow arm's."""
        assert tm.PAGED_FOLD_MAX_PAGES == 2
        assert [tm.paged_fold_pages(128, 4, H, 2, 1, 8, narrow=True)
                for H in (128, 256, 512, 1024)] == [2, 2, 1, 1]
        assert tm.paged_tile_bytes(128, 4, 512, 2, False, 1, 8, 3, 2) \
            > tm.VMEM_BLOCK_BUDGET_BYTES >= tm.paged_tile_bytes(
                128, 4, 256, 2, False, 1, 8, 3, 2)
        assert tm.paged_fold_pages(128, 4, 128, 2, 1, 8, narrow=False) == 1
        assert tm.paged_fold_pages(128, 8, 128, 2, 1, 4) == 1

    def test_shard_heads_agreement_pin(self):
        # ROADMAP item 2: the per-shard footprint rule (a head-sharded
        # paged kernel budgets K/tp heads; an indivisible head axis
        # REPLICATES, so every shard still streams all K) is part of the
        # shared model — the standalone-loaded lint copy must agree with
        # the runtime's on the whole grid, or the static checker and the
        # mesh guard in paged_decode_attention drift.
        lm = tile_math_module()
        for K in (2, 4, 6, 8, 12, 16, 32):
            for tp in (1, 2, 4, 8):
                assert lm.shard_heads(K, tp) == tm.shard_heads(K, tp)
                if tp > 1 and K % tp == 0:
                    assert tm.shard_heads(K, tp) == K // tp
                else:
                    assert tm.shard_heads(K, tp) == K
        # The division shows up in BYTES where the head block crosses a
        # sublane boundary: K=12 spans kb=12 (pads to 16) unsharded,
        # kb=6 (pads to 8) per tp=2 shard — half the block.
        full = tm.paged_tile_bytes(128, 12, 512, 4)
        shard = tm.paged_tile_bytes(128, tm.shard_heads(12, 2), 512, 4)
        assert shard * 2 == full

    def test_mesh_guard_budgets_per_shard_block(self):
        # The runtime guard under a mesh evaluates the PER-SHARD block:
        # a K=12/H=512 pool busts the budget unsharded (the kernel
        # declines) but fits per tp=2 shard (the kernel lowers through
        # its shard_map wrapper) — same shared model both sides.
        import jax
        import jax.numpy as jnp

        from ray_dynamic_batching_tpu.parallel.mesh import (
            MeshConfig,
            build_mesh,
        )

        K, N, H = 12, 24, 512
        assert tm.paged_tile_bytes(128, K, H, 4) \
            > tm.VMEM_BLOCK_BUDGET_BYTES
        assert tm.paged_tile_bytes(128, tm.shard_heads(K, 2), H, 4) \
            <= tm.VMEM_BLOCK_BUDGET_BYTES
        q = jnp.zeros((1, 1, N, H), jnp.float32)
        k = jnp.zeros((4, 128, K, H), jnp.float32)
        pt = jnp.zeros((1, 2), jnp.int32)
        lens = jnp.ones((1,), jnp.int32)
        assert da.paged_decode_attention(
            q, k, k, pt, lens, interpret=True
        ) is None
        mesh = build_mesh(MeshConfig(tp=2), jax.devices()[:2])
        out = da.paged_decode_attention(
            q, k, k, pt, lens, interpret=True, mesh=mesh
        )
        assert out is not None and out.shape == (1, 1, N, H)

    def test_f32_is_worst_case_itemsize(self):
        # The vmem-budget checker evaluates at itemsize 4; pin that this
        # upper-bounds every narrower dtype for any block shape.
        for shape in ((1, 1024, 16, 64), (1, 128, 8, 128), (1, 5, 3),
                      (7,), (1, 448, 8, 64)):
            f32 = tm.padded_block_bytes(shape, 4)
            assert f32 >= tm.padded_block_bytes(shape, 2)
            assert f32 >= tm.padded_block_bytes(shape, 1)


# --- the shipped tree + CLI ----------------------------------------------

class TestShippedTree:
    def test_tree_is_clean_under_shipped_baseline(self):
        report = run(baseline=load_baseline(lint_core.DEFAULT_BASELINE))
        assert not report.failed, report.format_text()

    def test_cli_list_rules(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ("vmem-budget", "tile-alignment",
                     "event-loop-blocking", "host-sync-in-hot-path",
                     "span-hygiene", "sim-determinism"):
            assert rule in out

    def test_cli_json_output_and_exit_code(self, tmp_path, capsys):
        path = tmp_path / "serve" / "app.py"
        path.parent.mkdir(parents=True)
        path.write_text(
            "import time\n\nasync def h():\n    time.sleep(1)\n"
        )
        rc = lint_main([str(tmp_path), "--json", "--no-baseline"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1 and payload["failed"]
        assert payload["new"][0]["rule"] == "event-loop-blocking"

    def test_cli_rejects_unknown_rule(self):
        assert lint_main(["--rules", "bogus"]) == 2

    def test_missing_path_is_an_error_not_a_silent_clean(self, tmp_path):
        report = run(paths=[tmp_path / "nope"], root=tmp_path)
        assert report.failed
        assert any("does not exist" in e for e in report.errors)

    def test_rules_pragma_hygiene_still_scans_files(self, tmp_path):
        # pragma-hygiene is not a Checker; a --rules run selecting only
        # it must still collect files rather than report a false clean.
        report = lint_fixture(
            tmp_path, "engine/w.py",
            SLEEPY.format(pragma="  # rdb-lint: disable="
                          "event-loop-blocking"),
            rules={"pragma-hygiene"},
        )
        assert report.files_scanned == 1
        assert rules_found(report) == ["pragma-hygiene"]


# --- shed-accounting --------------------------------------------------------


UNACCOUNTED_SHED = """
    from ray_dynamic_batching_tpu.engine.request import RequestDropped

    def drop_on_full(queue, request):
        if queue.full():
            request.reject(RequestDropped("queue full"))
            return False
        return True
"""

COUNTER_ACCOUNTED_SHED = """
    from ray_dynamic_batching_tpu.engine.request import RequestDropped

    SHED_TOTAL = object()

    def drop_on_full(queue, request):
        if queue.full():
            SHED_TOTAL.inc(tags={"reason": "full"})
            request.reject(RequestDropped("queue full"))
            return False
        return True
"""


class TestShedAccounting:
    def test_unaccounted_reject_is_flagged(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/q.py", UNACCOUNTED_SHED,
                              rules={"shed-accounting"})
        assert rules_found(report) == ["shed-accounting"]
        assert "offered == completed + shed" in report.new[0].message

    def test_unaccounted_raise_is_flagged(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/a.py", """
            from ray_dynamic_batching_tpu.serve.admission import (
                AdmissionRejected,
            )

            def gate(bucket):
                if not bucket.ok():
                    raise AdmissionRejected("no tokens")
        """, rules={"shed-accounting"})
        assert rules_found(report) == ["shed-accounting"]

    def test_shed_counter_inc_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/q.py",
                              COUNTER_ACCOUNTED_SHED,
                              rules={"shed-accounting"})
        assert report.new == []

    def test_attribute_counter_increment_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/q.py", """
            from ray_dynamic_batching_tpu.engine.request import RequestStale

            def sweep(self, req):
                self.total_stale += 1
                req.reject(RequestStale("deadline missed"))
        """, rules={"shed-accounting"})
        assert report.new == []

    def test_subscript_counter_increment_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/q.py", """
            from ray_dynamic_batching_tpu.engine.request import RequestStale

            def sweep(counters, req):
                counters["stale"] += 1
                req.reject(RequestStale("deadline missed"))
        """, rules={"shed-accounting"})
        assert report.new == []

    def test_audit_record_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/q.py", """
            from ray_dynamic_batching_tpu.engine.request import (
                RequestDropped,
            )

            def displace(self, victim):
                self.audit.record("qos_shed", key=self.model)
                victim.reject(RequestDropped("displaced"))
        """, rules={"shed-accounting"})
        assert report.new == []

    def test_count_external_drop_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/r.py", """
            from ray_dynamic_batching_tpu.engine.request import (
                RequestDropped,
            )

            def stop(self):
                for req in self.drain_queue():
                    self.queue.count_external_drop(req, reason="closed")
                    req.reject(RequestDropped("stopped"))
        """, rules={"shed-accounting"})
        assert report.new == []

    def test_out_of_scope_dirs_are_ignored(self, tmp_path):
        report = lint_fixture(tmp_path, "runtime/q.py", UNACCOUNTED_SHED,
                              rules={"shed-accounting"})
        assert report.new == []

    def test_reasoned_pragma_suppresses(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/a.py", """
            from ray_dynamic_batching_tpu.serve.admission import (
                AdmissionRejected,
            )

            def gate(self, bucket):
                if not bucket.ok():
                    raise AdmissionRejected("no tokens")  # rdb-lint: disable=shed-accounting (admit() already counted this reject)
        """, rules={"shed-accounting"})
        assert report.new == []
        assert report.pragma_suppressed == 1

    def test_shipped_tree_is_clean(self):
        from tools.lint.core import DEFAULT_TARGET

        report = run(paths=[DEFAULT_TARGET], rules={"shed-accounting"})
        assert report.new == [], [f.format() for f in report.new]


# --- store-discipline ------------------------------------------------------

BARE_CONTROLLER_WRITE = """
    class ServeController:
        def deploy(self, config):
            state = self._deployments[config.name]
            state.restarts = 0
            return state
"""


class TestStoreDiscipline:
    def test_bare_write_outside_txn_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/controller.py",
                              BARE_CONTROLLER_WRITE,
                              rules={"store-discipline"})
        assert rules_found(report) == ["store-discipline"]
        assert "store transaction API" in report.new[0].message

    def test_write_inside_txn_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/controller.py", """
            class ServeController:
                def deploy(self, config):
                    with self.store.txn() as txn:
                        state = self._deployments[config.name]
                        state.restarts = 0
                        txn.put_json("k", {"restarts": 0})
        """, rules={"store-discipline"})
        assert report.new == []

    def test_chained_attribute_write_flags(self, tmp_path):
        # state.config.num_replicas = n mutates controller state through
        # the chain — the rule matches any watched name IN the chain.
        report = lint_fixture(tmp_path, "serve/controller.py", """
            class ServeController:
                def _control_step(self):
                    for state in self._deployments.values():
                        state.config.num_replicas = 3
        """, rules={"store-discipline"})
        assert rules_found(report) == ["store-discipline"]

    def test_subscript_write_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/controller.py", """
            class ServeController:
                def deploy(self, name, state):
                    self._deployments[name] = state
        """, rules={"store-discipline"})
        assert rules_found(report) == ["store-discipline"]

    def test_init_is_exempt(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/controller.py", """
            class ServeController:
                def __init__(self):
                    self._deployments = {}
                    self.restarts = 0
        """, rules={"store-discipline"})
        assert report.new == []

    def test_unwatched_attrs_and_locals_are_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/controller.py", """
            class ServeController:
                def _tick(self, state):
                    state.policy = None
                    replicas = []
                    self._last_checkpoint = "x"
        """, rules={"store-discipline"})
        assert report.new == []

    def test_rule_scoped_to_serve_controller(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/router.py",
                              BARE_CONTROLLER_WRITE,
                              rules={"store-discipline"})
        assert report.new == []
        report = lint_fixture(tmp_path, "engine/controller.py",
                              BARE_CONTROLLER_WRITE,
                              rules={"store-discipline"})
        assert report.new == []

    def test_reasoned_pragma_suppresses(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/controller.py", """
            class ServeController:
                def adopt(self, state):
                    state.restarts = 0  # rdb-lint: disable=store-discipline (adoption re-derives from the already-persisted registry)
        """, rules={"store-discipline"})
        assert report.new == []
        assert report.pragma_suppressed == 1

    def test_shipped_controller_is_clean(self):
        from tools.lint.core import DEFAULT_TARGET

        report = run(paths=[DEFAULT_TARGET], rules={"store-discipline"})
        assert report.new == [], [f.format() for f in report.new]


# --- fabric-discipline ------------------------------------------------------

DIRECT_LOG_APPEND = """
    class ReplicatedStore:
        def _commit(self, ops):
            index = self.log.append(self._repl.epoch, ops)
            return index
"""


class TestFabricDiscipline:
    def test_direct_log_append_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/store.py",
                              DIRECT_LOG_APPEND,
                              rules={"fabric-discipline"})
        assert rules_found(report) == ["fabric-discipline"]
        assert "store.append" in report.new[0].message

    def test_fabric_routed_append_is_clean(self, tmp_path):
        # The seam takes the bound method as an ARGUMENT: no watched
        # call expression exists, so routed traffic passes by
        # construction.
        report = lint_fixture(tmp_path, "serve/store.py", """
            class ReplicatedStore:
                def _commit(self, ops):
                    return self.fabric.call(
                        "store.append", self.log.append,
                        self._repl.epoch, ops,
                        src=self.owner, dst="log",
                    )
        """, rules={"fabric-discipline"})
        assert report.new == []

    def test_lease_calls_flag(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/store.py", """
            class ReplicatedStore:
                def renew(self):
                    return self.lease.renew(self.owner)

                def take(self):
                    return self.lease.acquire(self.owner)
        """, rules={"fabric-discipline"})
        assert rules_found(report) == ["fabric-discipline"] * 2

    def test_snapshot_and_read_calls_flag(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/store.py", """
            class ReplicatedStore:
                def catch_up(self):
                    recs = self.log.read_from(0)
                    self.log.install_snapshot(None)
                    return recs
        """, rules={"fabric-discipline"})
        assert rules_found(report) == ["fabric-discipline"] * 2

    def test_subscripted_receiver_still_flags(self, tmp_path):
        # self.shards[sid].absorb_states(...) must not hide behind the
        # subscript.
        report = lint_fixture(tmp_path, "serve/frontdoor.py", """
            class FrontDoor:
                def gossip_round(self):
                    for sid in sorted(self.shards):
                        self.shards[sid].absorb_states(sid, {})
        """, rules={"fabric-discipline"})
        assert rules_found(report) == ["fabric-discipline"]

    def test_bus_calls_flag(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/frontdoor.py", """
            class FrontDoor:
                def gossip_round(self):
                    self.bus.publish("fd-0", {})
                    return self.bus.collect("fd-0")
        """, rules={"fabric-discipline"})
        assert rules_found(report) == ["fabric-discipline"] * 2

    def test_long_poll_listen_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/long_poll.py", """
            class LongPollClient:
                def _loop(self):
                    return self.host.listen_for_change({}, timeout_s=1.0)
        """, rules={"fabric-discipline"})
        assert rules_found(report) == ["fabric-discipline"]

    def test_out_of_scope_files_are_clean(self, tmp_path):
        # Same code outside the watched serve files: no finding.
        report = lint_fixture(tmp_path, "serve/router.py",
                              DIRECT_LOG_APPEND,
                              rules={"fabric-discipline"})
        assert report.new == []
        report = lint_fixture(tmp_path, "engine/store.py",
                              DIRECT_LOG_APPEND,
                              rules={"fabric-discipline"})
        assert report.new == []

    def test_reasoned_pragma_suppresses(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/frontdoor.py", """
            class FrontDoor:
                def gossip_round(self):
                    self.bus.publish("fd-0", {})  # rdb-lint: disable=fabric-discipline (the board is process-local; the network edge is the absorb)
        """, rules={"fabric-discipline"})
        assert report.new == []
        assert report.pragma_suppressed == 1

    def test_shipped_tree_is_clean(self):
        from tools.lint.core import DEFAULT_TARGET

        report = run(paths=[DEFAULT_TARGET], rules={"fabric-discipline"})
        assert report.new == [], [f.format() for f in report.new]


class TestSimDeterminismCoversFabric:
    def test_wall_clock_in_serve_fabric_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/fabric.py", """
            import time

            def partition_open(self):
                return time.time() - self.t0 > self.at_s
        """, rules={"sim-determinism"})
        assert rules_found(report) == ["sim-determinism"]

    def test_unseeded_rng_in_serve_fabric_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/fabric.py", """
            import random

            def draw(self):
                return random.Random().random()
        """, rules={"sim-determinism"})
        assert rules_found(report) == ["sim-determinism"]

    def test_other_serve_files_stay_uncovered(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/router.py", """
            import time

            def now(self):
                return time.time()
        """, rules={"sim-determinism"})
        assert report.new == []

    def test_shipped_fabric_is_clean(self):
        from tools.lint.core import DEFAULT_TARGET

        report = run(paths=[DEFAULT_TARGET], rules={"sim-determinism"})
        assert report.new == [], [f.format() for f in report.new]


class TestSimDeterminismCoversObservatory:
    """ISSUE 16: the observatory's instruments run verbatim inside
    SimScheduler at virtual time, so serve/observatory.py carries the
    same no-wall-clock contract as sim/ and serve/fabric.py."""

    def test_wall_clock_in_observatory_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/observatory.py", """
            import time

            class BurnWindow:
                def observe(self, misses, accounted):
                    self._snaps.append((time.monotonic(), misses, accounted))
        """, rules={"sim-determinism"})
        assert rules_found(report) == ["sim-determinism"]

    def test_clock_injected_observatory_is_clean(self, tmp_path):
        # The shipped idiom: clock=time.monotonic as a constructor
        # DEFAULT is an attribute reference, not a call — epochs rotate
        # off self._clock() so the sim twin swaps in virtual time.
        report = lint_fixture(tmp_path, "serve/observatory.py", """
            import time

            class BurnWindow:
                def __init__(self, clock=time.monotonic):
                    self._clock = clock

                def observe(self, misses, accounted):
                    self._snaps.append((self._clock(), misses, accounted))
        """, rules={"sim-determinism"})
        assert report.new == []

    def test_shipped_observatory_is_clean(self):
        from tools.lint.core import DEFAULT_TARGET

        report = run(paths=[DEFAULT_TARGET], rules={"sim-determinism"})
        assert report.new == [], [f.format() for f in report.new]


# --- lock-discipline ------------------------------------------------------

# The PR-6/8/9 bug shape: _n is written under the lock in inc(), read
# bare in peek().
UNGUARDED_READ = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def inc(self):
            with self._lock:
                self._n += 1

        def peek(self):
            return self._n{pragma}
"""


class TestLockDiscipline:
    def test_unguarded_read_is_flagged(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/c.py",
                              UNGUARDED_READ.format(pragma=""),
                              rules={"lock-discipline"})
        assert rules_found(report) == ["lock-discipline"]
        f = report.new[0]
        assert "read of `self._n` outside `_lock`" in f.message
        assert f.symbol == "Counter.peek"

    def test_fully_guarded_class_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/c.py", """
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def inc(self):
                    with self._lock:
                        self._n += 1

                def peek(self):
                    with self._lock:
                        return self._n
        """, rules={"lock-discipline"})
        assert report.new == []

    def test_unlocked_iteration_is_the_pr8_registry_race(self, tmp_path):
        # The exact PR-8 shape: a dict another thread resizes, walked
        # bare — gets the dedicated container finding, not a plain read.
        report = lint_fixture(tmp_path, "serve/reg.py", """
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._metrics = {}

                def register(self, name, m):
                    with self._lock:
                        self._metrics[name] = m

                def snapshot(self):
                    return {k: v for k, v in self._metrics.items()}
        """, rules={"lock-discipline"})
        assert rules_found(report) == ["lock-discipline"]
        assert "PR-8 registry race" in report.new[0].message
        assert "snapshot it under the lock" in report.new[0].message

    def test_check_then_act_is_a_toctou_finding(self, tmp_path):
        # The classic lazy-init race: the None check runs outside the
        # lock that guards the write IN THE SAME function.
        report = lint_fixture(tmp_path, "serve/eng.py", """
            import threading

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._model = None

                def ensure(self):
                    if self._model is None:
                        with self._lock:
                            self._model = object()
                    return self._model
        """, rules={"lock-discipline"})
        assert all(r == "lock-discipline" for r in rules_found(report))
        assert any("check-then-act race (TOCTOU)" in f.message
                   for f in report.new)

    def test_assert_owner_marks_method_as_guarded(self, tmp_path):
        # A callers-hold-it helper opening with assert_owner(self._lock)
        # is analyzed as running entirely under the lock.
        report = lint_fixture(tmp_path, "engine/c.py", """
            import threading

            from ray_dynamic_batching_tpu.utils.concurrency import (
                assert_owner,
            )

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def inc(self):
                    with self._lock:
                        self._n += 1

                def _n_locked(self):
                    assert_owner(self._lock)
                    return self._n
        """, rules={"lock-discipline"})
        assert report.new == []

    def test_nested_def_does_not_inherit_the_lock(self, tmp_path):
        # A closure is one submit() away from another thread: the
        # enclosing with-block's guarantee must not transfer.
        report = lint_fixture(tmp_path, "engine/c.py", """
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def inc(self):
                    with self._lock:
                        self._n += 1

                def arm(self):
                    with self._lock:
                        def cb():
                            return self._n
                        return cb
        """, rules={"lock-discipline"})
        assert rules_found(report) == ["lock-discipline"]
        assert "read of `self._n`" in report.new[0].message

    def test_condition_aliases_its_lock(self, tmp_path):
        # Guarding under self._cond IS guarding under self._lock when
        # the condition wraps it.
        report = lint_fixture(tmp_path, "engine/q.py", """
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition(self._lock)
                    self._items = []

                def put(self, x):
                    with self._lock:
                        self._items.append(x)
                        self._cond.notify()

                def pop(self):
                    with self._cond:
                        return self._items.pop()
        """, rules={"lock-discipline"})
        assert report.new == []

    def test_reasoned_pragma_suppresses(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/c.py",
            UNGUARDED_READ.format(
                pragma="  # rdb-lint: disable=lock-discipline "
                       "(atomic int read; staleness tolerated)"),
            rules={"lock-discipline"},
        )
        assert report.new == []
        assert report.pragma_suppressed == 1

    def test_baseline_suppresses(self, tmp_path):
        report = lint_fixture(
            tmp_path, "engine/c.py", UNGUARDED_READ.format(pragma=""),
            baseline=_baseline([{
                "rule": "lock-discipline", "path": "engine/c.py",
                "symbol": "Counter.peek", "count": 1,
                "reason": "legacy bare read; conversion tracked",
            }]),
            rules={"lock-discipline"},
        )
        assert report.new == [] and not report.failed


# --- lock-ordering --------------------------------------------------------

class TestLockOrdering:
    def test_rank_inversion_is_flagged(self, tmp_path):
        # metrics (130) is the innermost rank: taking store (20) while
        # holding it inverts the declared hierarchy.
        report = lint_fixture(tmp_path, "serve/x.py", """
            from ray_dynamic_batching_tpu.utils.concurrency import (
                OrderedLock,
            )

            class X:
                def __init__(self):
                    self._m = OrderedLock("metrics")
                    self._s = OrderedLock("store")

                def bad(self):
                    with self._m:
                        with self._s:
                            pass
        """, rules={"lock-ordering"})
        assert rules_found(report) == ["lock-ordering"]
        msg = report.new[0].message
        assert "rank inversion" in msg
        assert "'store' (rank 20)" in msg and "'metrics' (rank 130)" in msg

    def test_declared_order_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/x.py", """
            from ray_dynamic_batching_tpu.utils.concurrency import (
                OrderedLock,
            )

            class X:
                def __init__(self):
                    self._s = OrderedLock("store")
                    self._m = OrderedLock("metrics")

                def good(self):
                    with self._s:
                        with self._m:
                            pass
        """, rules={"lock-ordering"})
        assert report.new == []

    def test_inversion_through_one_level_call(self, tmp_path):
        # The edge resolves through a same-class call: bad() holds
        # metrics while _grab() takes store.
        report = lint_fixture(tmp_path, "serve/x.py", """
            from ray_dynamic_batching_tpu.utils.concurrency import (
                OrderedLock,
            )

            class X:
                def __init__(self):
                    self._m = OrderedLock("metrics")
                    self._s = OrderedLock("store")

                def bad(self):
                    with self._m:
                        self._grab()

                def _grab(self):
                    with self._s:
                        pass
        """, rules={"lock-ordering"})
        assert rules_found(report) == ["lock-ordering"]
        assert "via X._grab()" in report.new[0].message

    def test_self_deadlock_lexical(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/x.py", """
            import threading

            class X:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        with self._lock:
                            pass
        """, rules={"lock-ordering"})
        assert rules_found(report) == ["lock-ordering"]
        assert "self-deadlock" in report.new[0].message

    def test_self_deadlock_via_call(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/x.py", """
            import threading

            class X:
                def __init__(self):
                    self._lock = threading.Lock()

                def outer(self):
                    with self._lock:
                        self._inner()

                def _inner(self):
                    with self._lock:
                        pass
        """, rules={"lock-ordering"})
        assert rules_found(report) == ["lock-ordering"]
        assert "via X._inner()" in report.new[0].message

    def test_reentrant_reacquire_is_clean_lexically_and_via_call(
            self, tmp_path):
        # The controller pattern: a reentrant lock re-taken by a helper
        # the holder calls (deploy -> _checkpoint) is safe, not a
        # self-deadlock — lexically or through the call edge.
        report = lint_fixture(tmp_path, "serve/x.py", """
            import threading

            class X:
                def __init__(self):
                    self._lock = threading.RLock()

                def outer(self):
                    with self._lock:
                        with self._lock:
                            self._inner()

                def _inner(self):
                    with self._lock:
                        pass
        """, rules={"lock-ordering"})
        assert report.new == []

    def test_unknown_rank_is_flagged(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/x.py", """
            from ray_dynamic_batching_tpu.utils.concurrency import (
                OrderedLock,
            )

            class X:
                def __init__(self):
                    self._l = OrderedLock("bogus")
        """, rules={"lock-ordering"})
        assert rules_found(report) == ["lock-ordering"]
        assert "unknown rank 'bogus'" in report.new[0].message

    def test_cycle_reported_with_witness_path(self, tmp_path):
        # Two module-local locks taken in opposite orders by two
        # functions: no ranks, so no inversion — but the whole-run
        # graph has an a->b->a cycle, reported with the witness.
        report = lint_fixture(tmp_path, "serve/x.py", """
            import threading

            a = threading.Lock()
            b = threading.Lock()

            def forward():
                with a:
                    with b:
                        pass

            def backward():
                with b:
                    with a:
                        pass
        """, rules={"lock-ordering"})
        assert rules_found(report) == ["lock-ordering"]
        msg = report.new[0].message
        assert "potential deadlock" in msg
        assert "serve/x.py:a" in msg and "serve/x.py:b" in msg
        # The witness names both edges' functions and ends where it
        # started.
        assert "in forward" in msg and "in backward" in msg
        assert msg.count("->") >= 2

    def test_lock_graph_rides_json_output(self, tmp_path, capsys):
        path = tmp_path / "serve" / "x.py"
        path.parent.mkdir(parents=True)
        path.write_text(textwrap.dedent("""
            from ray_dynamic_batching_tpu.utils.concurrency import (
                OrderedLock,
            )

            class X:
                def __init__(self):
                    self._s = OrderedLock("store")
                    self._m = OrderedLock("metrics")

                def good(self):
                    with self._s:
                        with self._m:
                            pass
        """))
        rc = lint_main([str(tmp_path), "--json", "--no-baseline",
                        "--rules", "lock-ordering"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        graph = payload["lock_graph"]
        assert graph["ranks"]["metrics"] == 130
        ids = {n["id"] for n in graph["nodes"]}
        assert {"rank:store", "rank:metrics"} <= ids
        assert any(e["from"] == "rank:store" and e["to"] == "rank:metrics"
                   for e in graph["edges"])

    def test_baseline_suppresses(self, tmp_path):
        report = lint_fixture(
            tmp_path, "serve/x.py", """
            import threading

            class X:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        with self._lock:
                            pass
            """,
            baseline=_baseline([{
                "rule": "lock-ordering", "path": "serve/x.py",
                "symbol": "X.bad", "count": 1,
                "reason": "legacy recursive hold; refactor tracked",
            }]),
            rules={"lock-ordering"},
        )
        assert report.new == [] and not report.failed


# --- event-loop-blocking: sync-primitive tier ------------------------------

class TestEventLoopSyncPrimitives:
    def test_sync_lock_with_in_async_serve_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/proxy.py", """
            async def handler(self):
                with self._lock:
                    return 1
        """, rules={"event-loop-blocking"})
        assert rules_found(report) == ["event-loop-blocking"]
        assert "synchronous lock `_lock`" in report.new[0].message

    def test_lock_acquire_in_async_serve_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/proxy.py", """
            async def handler(self):
                self._lock.acquire()
        """, rules={"event-loop-blocking"})
        assert rules_found(report) == ["event-loop-blocking"]
        assert ".acquire()" in report.new[0].message

    def test_queue_get_in_async_serve_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "serve/proxy.py", """
            async def handler(self):
                return self._queue.get()
        """, rules={"event-loop-blocking"})
        assert rules_found(report) == ["event-loop-blocking"]
        assert ".get()" in report.new[0].message

    def test_sync_def_lock_use_is_clean(self, tmp_path):
        # Worker threads may block on locks; only the event loop can't.
        report = lint_fixture(tmp_path, "serve/proxy.py", """
            def worker(self):
                with self._lock:
                    return self._queue.get()
        """, rules={"event-loop-blocking"})
        assert report.new == []

    def test_engine_async_lock_is_out_of_scope(self, tmp_path):
        # The sync-primitive tier is serve/-only: engine async code is
        # the (stricter) domain of the engine's own structure.
        report = lint_fixture(tmp_path, "engine/x.py", """
            async def step(self):
                with self._lock:
                    return 1
        """, rules={"event-loop-blocking"})
        assert report.new == []


# --- concurrency rules: shipped-tree parity --------------------------------

class TestConcurrencyRulesShipped:
    def test_new_rules_are_in_the_default_set(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        assert "lock-discipline" in out
        assert "lock-ordering" in out

    def test_baseline_ships_empty_for_concurrency_rules(self):
        baseline = load_baseline(lint_core.DEFAULT_BASELINE)
        rules = {e["rule"] for e in baseline.get("entries", [])}
        assert "lock-discipline" not in rules
        assert "lock-ordering" not in rules

    def test_shipped_tree_clean_under_lock_rules(self):
        report = run(rules={"lock-discipline", "lock-ordering"})
        assert report.new == [], [f.format() for f in report.new]

    def test_linter_lock_table_matches_runtime(self):
        # The tile_math pattern: one model, two enforcers. The checker
        # loads concurrency.py standalone; drift here means the static
        # graph and the armed runtime disagree about the hierarchy.
        from tools.lint import lockorder

        from ray_dynamic_batching_tpu.utils.concurrency import LOCK_RANKS

        assert lockorder.LOCK_RANKS == LOCK_RANKS


# --- jit discipline rules (ISSUE 20) ---------------------------------------

# The exact hazard the tree-sweep found three times (parallel/mesh.py
# sharded-cache alloc, parallel/train.py + pipeline.py optimizer init):
# a jax.jit created and invoked in one expression — the compile cache
# dies with the expression, so EVERY call re-traces.
SWEPT_IMMEDIATE_INVOKE = """
    import jax

    def make_sharded_alloc(make_fn, shardings):
        {pragma}
        return jax.jit(make_fn, out_shardings=shardings)()
"""


class TestJitRetraceHazard:
    def test_swept_immediate_invoke_regression_is_flagged(self, tmp_path):
        report = lint_fixture(
            tmp_path, "parallel/alloc.py",
            SWEPT_IMMEDIATE_INVOKE.format(pragma=""),
            rules={"jit-retrace-hazard"})
        assert rules_found(report) == ["jit-retrace-hazard"]
        assert "immediately invoked" in report.new[0].message

    def test_factory_return_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "parallel/train.py", """
            import jax

            def make_step(step):
                return jax.jit(step, donate_argnums=(0,))
        """, rules={"jit-retrace-hazard"})
        assert report.new == []

    def test_pragma_with_reason_suppresses(self, tmp_path):
        src = """
            import jax

            def make_sharded_alloc(make_fn, shardings):
                return jax.jit(make_fn, out_shardings=shardings)()  # rdb-lint: disable=jit-retrace-hazard (one-shot alloc at construction)
        """
        report = lint_fixture(tmp_path, "parallel/alloc.py", src,
                              rules={"jit-retrace-hazard"})
        assert report.new == [] and report.pragma_suppressed >= 1

    def test_baselined_hazard_does_not_fail(self, tmp_path):
        report = lint_fixture(
            tmp_path, "parallel/alloc.py",
            SWEPT_IMMEDIATE_INVOKE.format(pragma="pass"),
            rules={"jit-retrace-hazard"},
            baseline=_baseline([{
                "rule": "jit-retrace-hazard", "path": "parallel/alloc.py",
                "symbol": "make_sharded_alloc", "count": 1,
                "reason": "legacy one-shot alloc; conversion tracked",
            }]),
        )
        assert report.new == [] and len(report.baselined) == 1

    def test_jit_of_lambda_inside_function_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            def make(x):
                return jax.jit(lambda y: y + x)
        """, rules={"jit-retrace-hazard"})
        assert rules_found(report) == ["jit-retrace-hazard"]
        assert "lambda" in report.new[0].message

    def test_module_level_jit_of_lambda_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            double = jax.jit(lambda y: y * 2)
        """, rules={"jit-retrace-hazard"})
        assert report.new == []

    def test_non_literal_static_argnums_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/build.py", """
            import jax

            def build(impl, statics):
                return jax.jit(impl, static_argnums=statics)
        """, rules={"jit-retrace-hazard"})
        assert rules_found(report) == ["jit-retrace-hazard"]
        assert "not a literal" in report.new[0].message

    def test_branch_on_traced_param_in_registered_impl_flags(
            self, tmp_path):
        # decode.py jits _decode_impl via jax.jit(self._decode_impl) at
        # init — no decorator, so host-sync never saw its body. The
        # registry (ops/jit_model.py) closes the gap: params is traced
        # (arg 0; only jit arg 3 = horizon is static).
        report = lint_fixture(tmp_path, "ops/decode.py", """
            class Engine:
                def _decode_impl(self, params, cache, ids, horizon):
                    if params:
                        return ids
                    return cache
        """, rules={"jit-retrace-hazard"})
        assert rules_found(report) == ["jit-retrace-hazard"]
        assert "'params'" in report.new[0].message

    def test_branch_on_static_param_in_registered_impl_is_clean(
            self, tmp_path):
        # horizon is def index 4 = jit arg 3 — static per the registry
        # contract for decode_step, so a Python branch on it is legal.
        report = lint_fixture(tmp_path, "ops/decode.py", """
            class Engine:
                def _decode_impl(self, params, cache, ids, horizon):
                    if horizon:
                        return ids
                    return cache
        """, rules={"jit-retrace-hazard"})
        assert report.new == []

    def test_same_body_in_unregistered_method_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/decode.py", """
            class Engine:
                def _decode_helper(self, params, cache, ids, horizon):
                    if params:
                        return ids
                    return cache
        """, rules={"jit-retrace-hazard"})
        assert report.new == []

    def test_int_coercion_in_registered_impl_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "ops/decode.py", """
            class Engine:
                def _decode_impl(self, params, cache, ids, horizon):
                    n = int(ids)
                    return n
        """, rules={"jit-retrace-hazard"})
        assert rules_found(report) == ["jit-retrace-hazard"]


class TestDonationDiscipline:
    def test_contract_drift_is_flagged(self, tmp_path):
        # Registry records donate_argnums=(1, 8) for _decode_impl; a
        # creation site passing (1,) un-donates the counts buffer.
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            class Engine:
                def __init__(self):
                    self._decode_fn = jax.jit(
                        self._decode_impl, donate_argnums=(1,),
                        static_argnums=(3,))
        """, rules={"donation-discipline"})
        assert rules_found(report) == ["donation-discipline"]
        assert "(1, 8)" in report.new[0].message

    def test_matching_contract_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            class Engine:
                def __init__(self):
                    self._decode_fn = jax.jit(
                        self._decode_impl, donate_argnums=(1, 8),
                        static_argnums=(3,))
        """, rules={"donation-discipline"})
        assert report.new == []

    def test_non_literal_donate_argnums_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            DONATE = (1, 8)

            class Engine:
                def __init__(self):
                    self._decode_fn = jax.jit(
                        self._decode_impl, donate_argnums=DONATE,
                        static_argnums=(3,))
        """, rules={"donation-discipline"})
        assert any("not a literal" in f.message for f in report.new)

    def test_use_after_donate_is_flagged(self, tmp_path):
        # _decode_fn donates args (1, 8): reading self._cache after the
        # call without rebinding reads a deleted buffer.
        report = lint_fixture(tmp_path, "engine/eng.py", """
            class Engine:
                def step(self):
                    out = self._decode_fn(self.params, self._cache)
                    return self._cache.sum()
        """, rules={"donation-discipline"})
        assert rules_found(report) == ["donation-discipline"]
        assert "read again" in report.new[0].message

    def test_rebind_in_same_statement_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            class Engine:
                def step(self):
                    out, self._cache = self._decode_fn(
                        self.params, self._cache)
                    return out
        """, rules={"donation-discipline"})
        assert report.new == []

    def test_donated_attr_never_rebound_is_flagged(self, tmp_path):
        # zero_counts donates arg 0; a bare call leaves self._counts
        # pointing at a deleted buffer.
        report = lint_fixture(tmp_path, "engine/eng.py", """
            class Engine:
                def boot(self):
                    self._zero_counts_fn(self._counts)
        """, rules={"donation-discipline"})
        assert rules_found(report) == ["donation-discipline"]
        assert "never rebound" in report.new[0].message

    def test_later_rebind_then_read_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            class Engine:
                def boot(self):
                    self._counts = self._zero_counts_fn(self._counts)
                    return self._counts
        """, rules={"donation-discipline"})
        assert report.new == []

    def test_pragma_with_reason_suppresses(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            class Engine:
                def boot(self):
                    self._zero_counts_fn(self._counts)  # rdb-lint: disable=donation-discipline (counts rebuilt from scratch next step)
        """, rules={"donation-discipline"})
        assert report.new == [] and report.pragma_suppressed >= 1


class TestWarmupCoverage:
    COMPLETE = """
        import jax

        class Engine:
            def __init__(self):
                self._decode_fn = jax.jit(
                    self._decode_impl, donate_argnums=(1, 8),
                    static_argnums=(3,))
            def _warmup_decode(self):
                self._decode_fn(None, None, None, 1)
    """

    def test_complete_warmup_is_clean(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", self.COMPLETE,
                              rules={"warmup-coverage"})
        assert report.new == []

    def test_unregistered_jit_in_engine_class_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            class Engine:
                def __init__(self):
                    self._decode_fn = jax.jit(
                        self._decode_impl, donate_argnums=(1, 8),
                        static_argnums=(3,))
                    self._magic_fn = jax.jit(self._magic_impl)
                def _warmup_decode(self):
                    self._decode_fn(None, None, None, 1)
        """, rules={"warmup-coverage"})
        assert rules_found(report) == ["warmup-coverage"]
        assert "_magic_impl" in report.new[0].message

    def test_missing_warmup_method_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            class Engine:
                def __init__(self):
                    self._decode_fn = jax.jit(
                        self._decode_impl, donate_argnums=(1, 8),
                        static_argnums=(3,))
        """, rules={"warmup-coverage"})
        assert rules_found(report) == ["warmup-coverage"]
        assert "_warmup_decode" in report.new[0].message

    def test_warmup_not_invoking_program_flags(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            class Engine:
                def __init__(self):
                    self._decode_fn = jax.jit(
                        self._decode_impl, donate_argnums=(1, 8),
                        static_argnums=(3,))
                def _warmup_decode(self):
                    pass
        """, rules={"warmup-coverage"})
        assert rules_found(report) == ["warmup-coverage"]
        assert "never invokes" in report.new[0].message

    def test_non_engine_dir_is_out_of_scope(self, tmp_path):
        report = lint_fixture(tmp_path, "parallel/eng.py", """
            import jax

            class Engine:
                def __init__(self):
                    self._decode_fn = jax.jit(
                        self._decode_impl, donate_argnums=(1, 8),
                        static_argnums=(3,))
        """, rules={"warmup-coverage"})
        assert report.new == []

    def test_class_without_registered_impls_is_out_of_scope(
            self, tmp_path):
        # worker.py-style AOT compiles of model.apply are not the
        # registry's purview — only classes that jit registered impls.
        report = lint_fixture(tmp_path, "engine/worker.py", """
            import jax

            class ModelWorker:
                def compile(self, model, args):
                    return jax.jit(model.apply).lower(*args).compile()
        """, rules={"warmup-coverage"})
        assert report.new == []

    def test_pragma_with_reason_suppresses(self, tmp_path):
        report = lint_fixture(tmp_path, "engine/eng.py", """
            import jax

            class Engine:
                def __init__(self):
                    self._decode_fn = jax.jit(
                        self._decode_impl, donate_argnums=(1, 8),
                        static_argnums=(3,))
                    self._magic_fn = jax.jit(self._magic_impl)  # rdb-lint: disable=warmup-coverage (cold admin path, compiles once per restart)
                def _warmup_decode(self):
                    self._decode_fn(None, None, None, 1)
        """, rules={"warmup-coverage"})
        assert report.new == [] and report.pragma_suppressed >= 1


class TestJitRulesShipped:
    def test_new_rules_are_in_the_default_set(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ("jit-retrace-hazard", "donation-discipline",
                     "warmup-coverage"):
            assert rule in out

    def test_baseline_ships_empty_for_jit_rules(self):
        baseline = load_baseline(lint_core.DEFAULT_BASELINE)
        rules = {e["rule"] for e in baseline.get("entries", [])}
        assert not rules & {"jit-retrace-hazard", "donation-discipline",
                            "warmup-coverage"}

    def test_shipped_tree_clean_under_jit_rules(self):
        report = run(rules={"jit-retrace-hazard", "donation-discipline",
                            "warmup-coverage"})
        assert report.new == [], [f.format() for f in report.new]

    def test_linter_registry_matches_runtime(self):
        # One model, two enforcers: the standalone importlib load the
        # rules use must expose the same registry the engine warms.
        from tools.lint import jit_discipline

        from ray_dynamic_batching_tpu.ops import jit_model

        lint_model = jit_discipline._jit_model()
        assert lint_model.registered_impls() == (
            jit_model.registered_impls())
        assert [p.name for p in lint_model.HOT_PROGRAMS] == [
            p.name for p in jit_model.HOT_PROGRAMS]

    def test_json_output_has_per_rule_timings(self, tmp_path, capsys):
        assert lint_main(["--json", str(tmp_path / "empty")]) in (0, 1)
        out = capsys.readouterr().out
        payload = json.loads(out)
        # Path doesn't exist -> error run, but the timing block is
        # structural: every active rule reports a number.
        assert "timings" in payload
