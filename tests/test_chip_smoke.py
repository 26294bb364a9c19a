"""The chip smoke's phases, driven at ``llama_tiny`` on the CPU cluster.

``chip_smoke.py`` itself never passes without a TPU; its phases are
plain functions of a model name and sizes, so the logic that will run on
the chip — deploy through ``apply_config``, answer HTTP and handle
requests, assert zero compiles after warmup, read the attention path
each hot program compiled to, check pinned and TP placement — is
exercised here with Pallas forced (interpret mode). What the CPU cannot
show is whether Mosaic compiles the kernels and what anything costs.
"""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke
from ray_dynamic_batching_tpu import serve
from ray_dynamic_batching_tpu.ops.attention import set_attention_backend
from ray_dynamic_batching_tpu.utils.compile_ledger import get_ledger

REPO = Path(__file__).resolve().parents[1]
TINY = dict(num_slots=4, max_len=256)  # two 128-position pages per slot


@pytest.fixture
def pallas_forced():
    """Kernels on (interpret mode off-TPU, strict), a fresh compile
    ledger, and the module controller/proxy torn down afterwards."""
    get_ledger().reset()
    set_attention_backend("pallas")
    try:
        yield
    finally:
        set_attention_backend("auto")
        serve.shutdown()
        get_ledger().reset()


def _requests(n=8):
    # llama_tiny's vocab is 512; prompts of 4..60 cross the 16/32 buckets
    # and the longest admit as multi-chunk trains on the paged arm.
    return chip_smoke.smoke_requests(512, n, 4, 60, 6)


def test_kernels_phase_matches_reference():
    rows = chip_smoke.phase_kernels(
        (("llama_tiny", 4, 2, 16),), slots=2, capacity=256,
        page_size=128, window=3, prefill_len=32,
    )
    assert len(rows) == 12 and all(r["ok"] for r in rows)
    assert {r["case"].split()[0] for r in rows} == {
        "decode_attention", "paged_decode_attention", "flash_attention"}


def test_kernels_phase_fails_on_a_wrong_answer(monkeypatch):
    """A kernel that compiles and computes the wrong head is as bad as
    one that does not compile: the phase compares values."""
    from ray_dynamic_batching_tpu.ops import flash_attention as fa

    real = fa.flash_attention
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, **kw: real(q, k, v, **kw)[:, :, ::-1],  # heads swapped
    )
    cases = chip_smoke.kernel_cases
    monkeypatch.setattr(  # one case is enough, and a third of the time
        chip_smoke, "kernel_cases",
        lambda *a, **kw: (c for c in cases(*a, **kw)
                          if c[0].startswith("flash_attention causal")),
    )
    with pytest.raises(chip_smoke.PhaseFailed, match="flash_attention"):
        chip_smoke.phase_kernels(
            (("llama_tiny", 4, 2, 16),), slots=2, capacity=256,
            page_size=128, window=3, prefill_len=32,
        )


def test_serve_paged_phase(pallas_forced):
    out = chip_smoke.phase_serve(
        "llama_tiny", prompt_buckets=(16, 32),
        requests=_requests(), http_requests=3, allow_interpret=True,
        timeout_s=120, **TINY,
    )
    assert len(out["tokens"]) == 8
    assert out["warmup_compile_episodes"] > 0
    by_program = {(r["program"], r["path"]) for r in out["paths"]}
    assert ("decode_step", "paged kernel (stacked pool)") in by_program
    assert ("chunk_prefill", "gather-then-flash kernel") in by_program
    # The designed decline is visible, with its reason.
    chunk = next(r for r in out["paths"] if r["program"] == "chunk_prefill")
    assert any("prefill-shaped" in why for why in chunk["declines"])
    assert serve.status() == {}  # the phase deleted its deployment
    # On the chip no hot program may run interpreted; here every one
    # did, and the same record fails the table when that is not allowed.
    with pytest.raises(chip_smoke.PhaseFailed, match="interpret mode"):
        chip_smoke.report_paths(allow_interpret=False)


def test_paths_table_fails_a_hot_program_on_the_xla_reference():
    """A hot program that compiled to the XLA einsum fails the table,
    with the reason it got there stated — backend "auto" on the CPU is
    exactly that program."""
    import jax.numpy as jnp

    from ray_dynamic_batching_tpu.ops import attention
    from ray_dynamic_batching_tpu.utils.compile_ledger import instrument

    attention.clear_attention_paths()
    q = jnp.ones((2, 1, 4, 16), jnp.bfloat16)
    kv = jnp.ones((2, 128, 2, 16), jnp.bfloat16)
    step = instrument("decode_step", jax.jit(
        lambda q, kv: attention.dot_product_attention(q, kv, kv)))
    step(q, kv)
    (record,) = attention.attention_paths()
    assert record.program == "decode_step"
    assert record.path == attention.PATH_XLA
    assert "pallas off" in record.declines[0]
    with pytest.raises(chip_smoke.PhaseFailed, match="XLA reference"):
        chip_smoke.report_paths(allow_interpret=True)
    attention.clear_attention_paths()


def test_four_chip_phase(pallas_forced, eight_devices):
    """Four pinned one-chip replicas on four distinct devices, each
    serving, then a TP replica — both with ZERO compiles after warmup.
    At the parent commit a pinned replica compiled ``chunk_prefill`` and
    ``decode_step`` again on its first live request (the refreshed page
    table and the reset lengths arrived uncommitted), and a TP replica
    did the same (token counts handed back vocab-sharded, cache specs
    spelled with trailing Nones)."""
    # 32 requests: the router's pow-2 pick reads queue lengths through a
    # short-lived cache, so a burst spreads uniformly at random — with 8,
    # one of four replicas stays idle in about two runs out of five.
    out = chip_smoke.phase_four_chips(
        "llama_tiny", prompt_buckets=(32,), requests=_requests(32),
        allow_interpret=True, timeout_s=120,
        devices=eight_devices[:4], tp=2,
        llm_options=chip_smoke.FOUR_CHIP_LLM, **TINY,
    )
    # llama_tiny has two KV heads: they split two ways.
    assert any(r["program"] == "decode_step"
               and r["path"] == "paged kernel (stacked pool, shard_map tp=2)"
               for r in out["tp"]["paths"])
    assert any(r["program"] == "chunk_prefill"
               and r["path"] == "gather-then-flash kernel (shard_map tp=2)"
               for r in out["tp"]["paths"])


def _run_smoke(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_script_fails_without_a_tpu():
    proc = _run_smoke(REPO, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_script_fails_alone_in_a_directory(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
